import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
)

# Multi-pod dry-run: lower + compile every (arch x input-shape) cell on the
# production meshes and record memory/cost/collective analyses.
#
# This is the proof that the distribution config is coherent without real
# hardware: a sharding mismatch, compile-time OOM, or unsupported collective
# fails the cell. Results feed EXPERIMENTS.md §Dry-run and §Roofline.
#
# NOTE: the XLA_FLAGS assignment above MUST stay the first statement — jax
# locks the device count at first init, and smoke tests/benches must keep
# seeing 1 device (the flag is scoped to this process only).
#
# Usage:
#   PYTHONPATH=src python -m repro.launch.dryrun --arch llama3-8b --shape train_4k
#   PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both

import argparse
import json
import time
import traceback

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import SHAPES, get_config, input_specs, runnable
from repro.configs.registry import ARCH_IDS
from repro.distributed.sharding import param_specs
from repro.launch.mesh import make_production_mesh
from repro.launch.hlo_analysis import analyze as hlo_analyze
from repro.launch.roofline import roofline_report
from repro.models.lm import build_model
from repro.train.train_step import (
    TrainConfig,
    abstract_train_state,
    make_serve_step,
    make_train_step,
)
from repro.optim.adamw import AdamWConfig


def _ns(mesh, spec):
    return NamedSharding(mesh, spec)


def batch_shardings(mesh, batch_specs, *, seq_shard=False):
    """Shard the batch dim over the data-parallel axes; long-context (B=1)
    cells instead leave batch replicated (sequence/state dims shard via the
    cache)."""
    from repro.distributed.sharding import batch_axes

    axes = mesh.axis_names
    dp = tuple(a for a in batch_axes() if a in axes)
    out = {}
    for k, v in batch_specs.items():
        if v.shape and v.shape[0] > 1 and v.shape[0] % _dp_size(mesh) == 0:
            out[k] = _ns(mesh, P(dp, *([None] * (len(v.shape) - 1))))
        else:
            out[k] = _ns(mesh, P(*([None] * len(v.shape))))
    return out




def opt_specs_from(params_specs, opt_abstract):
    """Optimizer-state PartitionSpecs: moments inherit the param spec;
    int8-quantized second moments shard their block dim over (data, model)."""
    from repro.distributed.sharding import _filter

    def v_spec(leaf_spec, leaf):
        if isinstance(leaf, dict):  # int8 {q, scale}: blocked (..., nb, 256)
            # inherit the param spec on the leading axes; the (nb, 256)
            # block axes of the last param dim stay unsharded
            base = tuple(leaf_spec) if leaf_spec is not None else ()
            spec = P(*base[:-1], None, None) if base else P(None, None)
            q = _filter(spec, leaf["q"].shape) or P()
            s = _filter(spec, leaf["scale"].shape) or P()
            return {"q": q, "scale": s}
        return leaf_spec

    m_specs = params_specs
    v_specs = jax.tree_util.tree_map(
        v_spec, params_specs, opt_abstract["v"],
        is_leaf=lambda x: isinstance(x, P) or (
            isinstance(x, dict) and set(x) == {"q", "scale"}
        ),
    )
    return {"m": m_specs, "v": v_specs, "count": P()}


def cache_specs(cfg, cache_abstract, shape):
    """KV/state cache PartitionSpecs.

    Normal decode (B >= dp): batch over (pod,data), heads/state over model.
    long_500k (B == 1): sequence axis of attention caches over data
    (sequence parallelism); state dims over model.
    """
    long_ctx = shape.global_batch == 1
    from repro.distributed.sharding import get_abstract_mesh, mesh_axis_sizes

    mesh = get_abstract_mesh()
    axes = tuple(mesh.axis_names)
    sizes = mesh_axis_sizes(mesh)
    dp = tuple(a for a in ("pod", "data") if a in axes)
    dp_n = 1
    for a in dp:
        dp_n *= sizes[a]
    model_n = sizes.get("model", 1)

    def spec_for(leaf):
        shp = leaf.shape
        # stacked leading layer axis(es) then batch; find batch dim == B
        # attention KVCache leaves: (periods, B, S, KV, hd)
        # mamba conv: (periods, B, k-1, di); ssm: (periods, B, di, ds)
        # mlstm C: (periods, B, nh, hd, hd); whisper: (layers, B, S, KV, hd)
        nd = len(shp)
        entries = [None] * nd
        if nd >= 4 and shp[-2] and cfg.n_kv_heads and shp[-2] == cfg.n_kv_heads:
            # (..., S, KV, hd) attention cache: batch over dp, and the
            # SEQUENCE dim over model (flash-decoding style) — KV-head
            # sharding is a dead end (kv=2..8 never divides a 16-way axis,
            # leaving the cache replicated: 16x HBM waste and pathological
            # gathers). With seq sharded, scores stay local and the sharded
            # softmax/contraction inserts only tiny (B,KV,G,1) reductions.
            if not long_ctx and shp[1] % dp_n == 0:
                entries[1] = dp
            seq_axes = tuple(
                a for a in (("data",) if long_ctx else ()) + ("model",)
            )
            seq_n = 1
            for a in seq_axes:
                seq_n *= sizes.get(a, 1)
            if shp[-3] % seq_n == 0:
                entries[-3] = seq_axes
        else:
            # state caches: shard the largest trailing dim over model
            if not long_ctx and nd >= 2 and shp[1] % dp_n == 0:
                entries[1] = dp
            big = max(range(2, nd), key=lambda i: shp[i]) if nd > 2 else None
            if big is not None and shp[big] % model_n == 0:
                entries[big] = "model"
        return P(*entries)

    return jax.tree_util.tree_map(spec_for, cache_abstract)


def compile_cell(arch: str, shape_name: str, *, multi_pod: bool = False,
                 extra_flags=None):
    """Lower + compile one cell; returns (compiled, meta) for profiling."""
    cfg = get_config(arch)
    if extra_flags:
        import dataclasses

        cfg = dataclasses.replace(cfg, **extra_flags)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    compiled, timings = _compile(cfg, shape, mesh, arch)
    return compiled, {"cfg": cfg, "shape": shape, "mesh": mesh, **timings}


def dryrun_cell(arch: str, shape_name: str, *, multi_pod: bool,
                verbose=True, extra_flags=None):
    """Lower + compile one (arch x shape x mesh) cell; return the report."""
    cfg = get_config(arch)
    if extra_flags:
        import dataclasses

        cfg = dataclasses.replace(cfg, **extra_flags)
    shape = SHAPES[shape_name]
    ok, reason = runnable(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape_name, "skipped": reason}

    mesh = make_production_mesh(multi_pod=multi_pod)
    compiled, timings = _compile(cfg, shape, mesh, arch)
    return _report(compiled, cfg, shape, mesh, arch, shape_name,
                   timings, verbose)


def _dp_size(mesh):
    from repro.distributed.sharding import batch_axes

    n = 1
    for a in batch_axes():
        if a in mesh.axis_names:
            n *= mesh.shape[a]
    return n


def _compile(cfg, shape, mesh, arch):
    from repro.distributed.sharding import set_parallelism

    mode = (cfg.train_parallelism if shape.kind == "train"
            else cfg.parallelism)
    if mode == "fsdp" and shape.global_batch % mesh.size != 0:
        # ZeRO-3 over the whole mesh needs >=1 sequence per chip; with
        # global_batch 256 on the 512-chip multi-pod mesh the batch would
        # replicate (measured 100x regression). Production answer: scale the
        # batch with the mesh; here we fall back to TP for such cells.
        mode = "tp"
    set_parallelism(mode)
    model = build_model(cfg)
    train_cfg = TrainConfig(
        optimizer=AdamWConfig(
            moment_dtype="int8" if arch.startswith("kimi") else (
                "bfloat16" if cfg.fsdp else "float32"
            )
        )
    )
    t0 = time.time()
    batch_abs = input_specs(cfg, shape)

    with jax.set_mesh(mesh):
        p_abs, o_abs = abstract_train_state(model, train_cfg)
        p_specs = param_specs(p_abs, cfg.fsdp)
        p_shard = jax.tree_util.tree_map(
            lambda s: _ns(mesh, s), p_specs,
            is_leaf=lambda s: isinstance(s, P),
        )
        b_shard = batch_shardings(mesh, batch_abs)

        if shape.kind == "train":
            o_specs = opt_specs_from(p_specs, o_abs)
            o_shard = jax.tree_util.tree_map(
                lambda s: _ns(mesh, s), o_specs,
                is_leaf=lambda s: isinstance(s, P),
            )
            step_fn = make_train_step(model, train_cfg)
            lowered = jax.jit(
                step_fn,
                in_shardings=(p_shard, o_shard, b_shard),
                out_shardings=(p_shard, o_shard, None),
            ).lower(p_abs, o_abs, batch_abs)
        elif shape.kind == "prefill":
            def prefill_fn(params, batch):
                return model.prefill(params, batch)

            lowered = jax.jit(
                prefill_fn, in_shardings=(p_shard, b_shard),
            ).lower(p_abs, batch_abs)
        else:  # decode
            cache_abs = model.init_cache(
                shape.global_batch, shape.seq_len, abstract=True
            )
            c_specs = cache_specs(cfg, cache_abs, shape)
            c_shard = jax.tree_util.tree_map(
                lambda s: _ns(mesh, s), c_specs,
                is_leaf=lambda s: isinstance(s, P),
            )
            serve_fn = make_serve_step(model)
            lowered = jax.jit(
                serve_fn,
                in_shardings=(p_shard, c_shard, b_shard),
                out_shardings=(None, c_shard),
            ).lower(p_abs, cache_abs, batch_abs)

        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
    return compiled, {"lower_s": round(t_lower, 1),
                      "compile_s": round(t_compile, 1)}


def _report(compiled, cfg, shape, mesh, arch, shape_name, timings, verbose):
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    walk = hlo_analyze(compiled.as_text())  # trip-count-aware (per chip)
    n_chips = mesh.size
    report = {
        "arch": arch,
        "shape": shape_name,
        "mesh": "x".join(str(mesh.shape[a]) for a in mesh.axis_names),
        "chips": n_chips,
        **timings,
        # trip-count-aware walker numbers (per chip) — the roofline source
        "flops": walk["flops"],
        "bytes_accessed": walk["bytes"],
        "collectives": walk["collectives"],
        # raw XLA cost_analysis (counts while bodies once) for reference
        "xla_flops_once": cost.get("flops", 0.0) if cost else None,
        "xla_bytes_once": cost.get("bytes accessed", 0.0) if cost else None,
        "memory": {
            k: getattr(mem, k)
            for k in (
                "argument_size_in_bytes",
                "output_size_in_bytes",
                "temp_size_in_bytes",
                "generated_code_size_in_bytes",
            )
            if mem is not None and hasattr(mem, k)
        },
    }
    report["roofline"] = roofline_report(report, cfg, shape)
    if verbose:
        print(json.dumps(report, indent=1, default=str))
    return report


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, choices=ARCH_IDS + [None])
    ap.add_argument("--shape", default=None, choices=list(SHAPES) + [None])
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args()

    arches = ARCH_IDS if (args.all or not args.arch) else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    os.makedirs(args.out, exist_ok=True)
    failures = []
    for arch in arches:
        for shape in shapes:
            for mp in meshes:
                tag = f"{arch}_{shape}_{'512' if mp else '256'}"
                path = os.path.join(args.out, tag + ".json")
                if os.path.exists(path):
                    print(f"[skip-existing] {tag}")
                    continue
                print(f"[dryrun] {tag} ...", flush=True)
                try:
                    rep = dryrun_cell(arch, shape, multi_pod=mp, verbose=False)
                    with open(path, "w") as f:
                        json.dump(rep, f, indent=1, default=str)
                    keys = ("skipped", "flops", "compile_s")
                    print(f"[done] {tag}: " + str({
                        k: rep.get(k) for k in keys if k in rep
                    }), flush=True)
                except Exception as e:
                    traceback.print_exc()
                    failures.append((tag, f"{type(e).__name__}: {e}"))
    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for t, e in failures:
            print(" ", t, e[:200])
        raise SystemExit(1)
    print("\nall requested dry-run cells passed")


if __name__ == "__main__":
    main()
