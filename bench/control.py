"""Readings that set and prove the correctness limits of a cell.

    python3 bench/control.py --workload <cell> --seeds 1,2,... \
        --control-seeds 3,4,5 --seconds 3

For each ``--seeds`` seed, one short run of the cell gives the program's
readings. For each ``--control-seeds`` seed it also gives the readings of
the control and of the faults the cell can have:

* ``bfloat16`` — the plain reference computed in bfloat16 put in the
  program's place (the configuration states float32); serving also
  ``bfloat16_f32_out``, the same with the last layer summed and finished
  in float32; training also ``mixed_bf16``, float32 weights with the
  passes in bfloat16;
* serving: ``altered`` (one answer per dispatch altered where it is
  produced) and ``half`` (half of each batch never computed);
* training: ``half`` (half of the batch left out, the mean over the
  rest). A step that returns its state unchanged reads 1 by the
  comparison's own measure and needs no run.

Prints one JSON line per (seed, what). Benchmark runs never run this.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import run as benchrun  # noqa: E402
from harness import faults, loadgen, registry, serve, system, train  # noqa: E402


def emit(cell, seed, what, checks) -> None:
    limits = cell.config["limits"]
    fails = any(not (v <= limits.get(k, lim)) for k, (v, lim) in
                checks.items())
    print(json.dumps({"workload": cell.name, "seed": seed, "what": what,
                      "readings": {k: v for k, (v, _) in checks.items()},
                      "fails_limits": fails}), flush=True)


def one(cell, seed, seconds, device, what):
    """A run of the cell with ``what`` planted ("program": nothing)."""
    kind = cell.traffic["kind"]
    run = benchrun.Run(cell=cell, seed=seed, seconds=seconds,
                       peaks=benchrun.peaks(device["kind"]),
                       device=dict(device))
    if kind == "train":
        planted = {"program": None, "half": faults.train_half}[what]
        mine = train.run_training(run, time.perf_counter(), False, planted)
        return run, mine
    ctx = {"program": contextlib.nullcontext, "altered": faults.serve_altered,
           "half": faults.serve_half}[what]
    with ctx():
        answers = serve.run_serving(run, time.perf_counter(), False)
    return run, answers


# training's controls: the reference in bfloat16 throughout, or in mixed
# precision (float32 weights and moments, bfloat16 passes, float32 losses)
TRAIN_CONTROLS = {"bfloat16": {"dtype": "bfloat16"},
                  "mixed_bf16": {"compute": "bfloat16"}}


def control(cell, seed, got, what="bfloat16"):
    """The reference in bfloat16 (the control ``what``) in the program's
    place."""
    cfg, mix = cell.config, cell.traffic
    ref = system.reference(cfg)
    if mix["kind"] == "train":
        data = train.Images(seed, mix["global_batch"], *_out_hw_c(cfg))
        zseed = loadgen.jax_seed(seed) >> 1
        how = TRAIN_CONTROLS[what]
        low = train.reference_readings(ref, cfg, mix, seed, data, zseed,
                                       **how)
        return {k: (v, cfg["limits"][k]) for k, v in
                train.compare(low, ref, cfg, mix, seed, data, zseed).items()}
    return {k: (v, cfg["limits"][k]) for k, v in
            serve.compare(ref, cfg, seed, got, control=what).items()}


def _out_hw_c(cfg):
    hw = 2 * cfg["layers"][-1][0] - cfg["kernel"] + 2 * cfg["padding"]
    return hw, cfg["layers"][-1][2]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args(argv)
    cell = registry.cell(registry.load_benchmark(), args.workload)
    benchrun.setup_caches()
    try:
        device = benchrun.device_info(cell.chips)
    except benchrun.NoChip as e:
        print(e, file=sys.stderr)
        return 2
    seeds = [int(s) for s in args.seeds.split(",") if s]
    ctl = [int(s) for s in args.control_seeds.split(",") if s]
    fault_kinds = (("half",) if cell.traffic["kind"] == "train"
                   else ("altered", "half"))
    for seed in seeds + [s for s in ctl if s not in seeds]:
        run, got = one(cell, seed, args.seconds, device, "program")
        emit(cell, seed, "program", run.checks)
        if seed in ctl:
            controls = tuple(TRAIN_CONTROLS if cell.traffic["kind"]
                             == "train" else serve.CONTROLS)
            for what in controls:
                emit(cell, seed, what, control(cell, seed, got, what))
            for what in fault_kinds:
                run, _ = one(cell, seed, args.seconds, device, what)
                emit(cell, seed, what, run.checks)
    return 0


if __name__ == "__main__":
    sys.exit(main())
