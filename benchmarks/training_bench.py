"""Training-loop benchmark: step-time trendline, resume overhead, and the
crash-resume smoke gate.

The `training` section this writes into BENCH_transpose_conv.json answers
the production question the fault-tolerant trainer exists for: what does a
step cost over time (the trendline exposes compile-vs-steady-state and any
per-step drift), what does a restart cost (restore + re-placement, in
steps' worth of wall time), and — the gate — does a killed-and-relaunched
run actually land back on the uninterrupted loss trajectory **bit-exactly**?

The gate is the benchmark-shaped twin of tests/test_fault_injection.py:
a reference run trains straight through; a chaos run is killed at the
midpoint by the fault-injection harness and relaunched; under ``--check``
the section fails CI unless the relaunch resumed from the expected
checkpoint and every overlapping step's (g_loss, d_loss) is bit-identical
to the reference (exact float equality, not a tolerance).

Quick mode (CI) uses a tiny GAN and a short run; full mode runs the
reduced DCGAN at more steps for a meaningful trendline.
"""
from __future__ import annotations

import argparse
import json
import tempfile
import time
from pathlib import Path

from repro.compile_cache import use_compile_cache


def bench_training(*, quick: bool) -> dict:
    import jax

    from repro.data import SyntheticImages
    from repro.models import gan
    from repro.train.fault_injection import (
        FaultInjector, FaultPlan, SimulatedCrash, trajectories_equal,
    )
    from repro.train.gan_trainer import GanTrainer, GanTrainerConfig

    if quick:
        cfg = gan.GANConfig("tiny", 8, ((4, 4, 4), (8, 4, 3)))
        steps, global_batch = 8, 2
    else:
        cfg = gan.reduced_config(gan.GAN_ZOO["dcgan"], scale=64)
        steps, global_batch = 12, 4
    tcfg = GanTrainerConfig(global_batch=global_batch, ckpt_every=2,
                            log_every=10**9)
    kill_at = steps // 2

    def data():
        micro, _ = tcfg.micro_accum
        return SyntheticImages(
            hw=cfg.out_hw(cfg.layers[-1][0]), channels=cfg.layers[-1][2],
            global_batch=micro,
        )

    quiet = lambda *a: None  # noqa: E731

    # ---- reference: uninterrupted run; its timer is the step trendline
    ref_tr = GanTrainer(cfg, tcfg, data(), log_fn=quiet)
    _, ref_hist = ref_tr.run(ref_tr.init_state(jax.random.key(0)),
                             steps=steps)
    trend = [float(t) for t in ref_tr.timer.steps]

    # ---- chaos run: killed at the midpoint, then relaunched
    with tempfile.TemporaryDirectory() as ckpt_dir:
        inj = FaultInjector(FaultPlan(kill_at_step=kill_at))
        tr1 = GanTrainer(cfg, tcfg, data(), ckpt_dir=ckpt_dir, hooks=inj,
                         log_fn=quiet)
        killed = False
        try:
            tr1.run(tr1.init_state(jax.random.key(0)), steps=steps)
        except SimulatedCrash:
            killed = True

        tr2 = GanTrainer(cfg, tcfg, data(), ckpt_dir=ckpt_dir, log_fn=quiet)
        state = tr2.init_state(jax.random.key(0))
        t0 = time.perf_counter()
        resumed_at, state = tr2.resume(state)
        resume_overhead_s = time.perf_counter() - t0
        _, hist2 = tr2.run(state, steps=steps)

    mean_step = ref_tr.timer.mean() if len(trend) > 1 else (
        trend[0] if trend else 0.0)
    expected_resume = (kill_at // tcfg.ckpt_every) * tcfg.ckpt_every
    return {
        "backend": jax.default_backend(),
        "quick": quick,
        "model": cfg.name,
        "steps": steps,
        "global_batch": global_batch,
        "ckpt_every": tcfg.ckpt_every,
        "kill_at": kill_at,
        "killed": killed,
        "resumed_at": resumed_at,
        "expected_resume": expected_resume,
        "step_time_s": {
            "trend": trend,
            "mean": mean_step,
            "median": ref_tr.timer.median() if len(trend) > 1 else mean_step,
        },
        "resume_overhead_s": resume_overhead_s,
        "resume_overhead_steps": (
            resume_overhead_s / mean_step if mean_step else 0.0),
        "trajectory_bit_exact": bool(trajectories_equal(ref_hist, hist2)),
    }


def check(section: dict) -> list[str]:
    """The acceptance gates: the kill fired, the relaunch resumed from the
    newest checkpoint, and the resumed trajectory is bit-identical to the
    uninterrupted reference."""
    bad = []
    if not section["killed"]:
        bad.append("training: injected kill never fired")
    if section["resumed_at"] != section["expected_resume"]:
        bad.append(
            f"training: resumed at {section['resumed_at']}, expected "
            f"checkpoint {section['expected_resume']}"
        )
    if not section["trajectory_bit_exact"]:
        bad.append(
            "training: resumed trajectory diverges from the uninterrupted "
            "reference (resume contract is BIT-exact)"
        )
    return bad


def main(argv=None):
    use_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help="smoke subset: tiny GAN, short run")
    ap.add_argument("--out", default="BENCH_transpose_conv.json")
    ap.add_argument("--check", action="store_true",
                    help="exit nonzero unless the crash-resume smoke run "
                         "reproduces the reference trajectory bit-exactly")
    args = ap.parse_args(argv)

    section = bench_training(quick=args.quick)

    out_path = Path(args.out)
    merged = {}
    if out_path.exists():   # merge into the shared perf artifact
        try:
            merged = json.loads(out_path.read_text())
            if not isinstance(merged, dict):
                merged = {}
        except (json.JSONDecodeError, OSError):
            merged = {}
    merged["training"] = section
    out_path.write_text(json.dumps(merged, indent=1, sort_keys=True))

    st = section["step_time_s"]
    print(f"# training ({'quick' if args.quick else 'full'}, "
          f"backend={section['backend']}): {section['model']} "
          f"batch {section['global_batch']} x {section['steps']} steps")
    print(f"step time mean {st['mean'] * 1e3:.1f}ms "
          f"median {st['median'] * 1e3:.1f}ms "
          f"(trend first {st['trend'][0] * 1e3:.1f}ms "
          f"last {st['trend'][-1] * 1e3:.1f}ms); "
          f"kill@{section['kill_at']} -> resumed@{section['resumed_at']} "
          f"(restore+replace {section['resume_overhead_s'] * 1e3:.1f}ms "
          f"= {section['resume_overhead_steps']:.2f} steps); "
          f"trajectory bit-exact: {section['trajectory_bit_exact']}")

    bad = check(section)
    if bad:
        print("PERF REGRESSION on:", "; ".join(bad))
        if args.check:
            raise SystemExit(1)
    elif args.check:
        print("# check ok: kill fired, resumed from newest checkpoint, "
              "trajectory bit-exact vs uninterrupted reference")


if __name__ == "__main__":
    main()
