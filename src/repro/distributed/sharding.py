"""Sharding rules: logical parameter/activation axes -> mesh PartitionSpecs.

Megatron-style tensor parallelism over the ``model`` axis, (optionally FSDP-)
data parallelism over ``data``, and pure data parallelism over ``pod`` for the
multi-pod mesh. Expert parallelism (MoE) also maps onto ``model``.

Conventions (all weights stored transposed-for-matmul, ``x @ W``):

  embedding     (vocab, d_model)        -> (model, fsdp?)     vocab-parallel
  attn in-proj  (d_model, heads*hd)     -> (fsdp?, model)     column-parallel
  attn out-proj (heads*hd, d_model)     -> (model, fsdp?)     row-parallel
  mlp up/gate   (d_model, d_ff)         -> (fsdp?, model)
  mlp down      (d_ff, d_model)         -> (model, fsdp?)
  moe experts   (E, d_model, d_ff)      -> (model=EP, fsdp?, None)
  norms/bias    replicated (fsdp over longest dim when fsdp=True)

Activations: batch over (pod, data); attention heads / ffn hidden over model;
for long-context decode the KV cache sequence axis is sharded over ``data``
(sequence parallelism — batch=1 leaves ``data`` idle otherwise).

All helpers degrade to no-ops when no mesh is active, so the exact same model
code runs in single-device smoke tests and in the 512-chip dry-run.
"""
from __future__ import annotations

import re

import jax
from jax.sharding import PartitionSpec as P

# logical axis names used by the model code
BATCH = ("pod", "data")   # global batch is split across pod x data
MODEL = "model"
DATA = "data"

# Parallelism mode (set by the launcher per arch config):
#   "tp"   — Megatron TP over `model` + (optionally FSDP-)DP over `data`.
#   "fsdp" — ZeRO-3 over ALL non-pod axes: `model` becomes a second
#            data-parallel axis; params/opt fully sharded; no tensor
#            parallelism. Right regime for <=13B dense models where TP
#            activation all-reduces dominate (EXPERIMENTS §Perf cell 4).
_MODE = {"mode": "tp"}


def set_parallelism(mode: str):
    assert mode in ("tp", "fsdp"), mode
    _MODE["mode"] = mode


def get_parallelism() -> str:
    return _MODE["mode"]


def batch_axes() -> tuple:
    return ("pod", "data", "model") if _MODE["mode"] == "fsdp" else BATCH


def get_abstract_mesh():
    """The ambient ``jax.sharding.get_abstract_mesh()``, with an *empty*
    mesh (no axes) normalized to ``None`` so callers can treat "no mesh"
    uniformly. Tests monkeypatch ``jax.sharding.get_abstract_mesh``."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh is None or not mesh.axis_names:
        return None
    return mesh


def get_concrete_mesh():
    """The ambient *device-backed* mesh, or None — what ``jax.set_mesh``
    activates. Distinct from :func:`get_abstract_mesh`: an abstract mesh
    names axes for the sharding *rules* but carries no devices, so
    ``shard_map`` over real (non-NamedSharding) arrays needs the concrete
    one. Read through ``jax._src.mesh`` because the public
    ``jax.sharding.get_mesh`` refuses to run inside a trace.
    """
    from jax._src import mesh as mesh_lib

    mesh = mesh_lib.get_concrete_mesh()
    if mesh is None or not mesh.axis_names:
        return None
    return mesh


def mesh_axis_sizes(mesh) -> dict:
    """{axis name: size} for a (possibly abstract) mesh."""
    if mesh is None:
        return {}
    return dict(zip(tuple(mesh.axis_names), tuple(mesh.shape.values())))


def _mesh_axes() -> tuple:
    mesh = get_abstract_mesh()
    return tuple(mesh.axis_names) if mesh is not None else ()


def _filter(spec: P, shape=None) -> P | None:
    """Drop spec entries whose axes aren't in the active mesh, or whose mesh
    extent doesn't divide the tensor dim (forcing XLA into involuntary full
    rematerialization / padded reshards); None if nothing remains."""
    mesh = get_abstract_mesh()
    axes = tuple(mesh.axis_names) if mesh is not None else ()
    sizes = mesh_axis_sizes(mesh)

    def axis_size(entry):
        if isinstance(entry, tuple):
            n = 1
            for a in entry:
                n *= sizes.get(a, 1)
            return n
        return sizes.get(entry, 1)

    fsdp_mode = _MODE["mode"] == "fsdp"
    out = []
    for i, entry in enumerate(spec):
        dim = None if shape is None or i >= len(shape) else shape[i]
        if entry is None:
            out.append(None)
            continue
        if fsdp_mode:
            # `model` is a batch axis: widen BATCH entries, drop bare
            # tensor-parallel constraints
            if entry == BATCH:
                entry = ("pod", "data", "model")
            elif entry == MODEL:
                out.append(None)
                continue
        if isinstance(entry, tuple):
            kept = tuple(a for a in entry if a in axes)
            entry = kept if kept else None
        elif entry not in axes:
            entry = None
        if entry is not None and dim is not None and dim % axis_size(entry):
            entry = None
        out.append(entry)
    if all(e is None for e in out):
        return None
    return P(*out)


def constrain(x, *entries):
    """with_sharding_constraint that no-ops outside a mesh context and drops
    non-divisible axis constraints (see _filter)."""
    spec = _filter(P(*entries), x.shape)
    if spec is None:
        return x
    return jax.lax.with_sharding_constraint(x, spec)


def shard_batch(x):
    """Shard the leading (batch) axis over (pod, data)."""
    return constrain(x, BATCH, *([None] * (x.ndim - 1)))


def shard_plan_apply(apply_fn, params, z, plan, *, mesh=None):
    """Run a compiled :class:`repro.kernels.plan.TconvPlan` generator under
    ``shard_map``, batch-sharded over the data-parallel mesh axes.

    ``apply_fn(params, z, plan) -> out`` with the leading axis of ``z`` and
    ``out`` being the batch (e.g. ``lambda p, z, plan:
    generator_apply(p, cfg, z, plan=plan)``). The plan is closed over as a
    static value, so every shard executes the exact operator stack the plan
    compiled — the per-shard trace never re-consults the autotune cache,
    and the shard-mapped generator traces exactly once per (plan, shapes).
    Parameters are replicated; only the batch is split.

    Degrades gracefully: with no mesh (or no ``pod``/``data`` axis, or a
    batch the data-parallel extent doesn't divide) it runs ``apply_fn``
    unsharded — the exact same code serves single-device tests and the
    multi-chip dry-run, like every other helper here. The ambient mesh is
    resolved via :func:`get_concrete_mesh` (NOT the abstract mesh an
    axis-rule dry-run installs): ``shard_map`` can only partition plain
    arrays over a device-backed mesh, so an abstract-only context — which
    used to crash here mid-trace — now degrades to the unsharded path.
    """
    from jax.sharding import PartitionSpec as P

    mesh = mesh if mesh is not None else get_concrete_mesh()
    if mesh is None:
        return apply_fn(params, z, plan)
    axes = tuple(mesh.axis_names)
    dp = tuple(a for a in ("pod", "data") if a in axes)
    sizes = mesh_axis_sizes(mesh)
    n_shards = 1
    for a in dp:
        n_shards *= sizes[a]
    if not dp or n_shards <= 0 or z.shape[0] % n_shards:
        return apply_fn(params, z, plan)

    def local_fn(p, zl):
        return apply_fn(p, zl, plan)

    # short specs: shard_map treats missing trailing dims as replicated, so
    # P(dp) means "batch-leading, everything else replicated" for any rank
    fn = jax.shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(P(), P(dp)),
        out_specs=P(dp),
        check_vma=False,
    )
    return fn(params, z)


# ---------------------------------------------------------------------------
# Parameter sharding rules, keyed by parameter path (joined with '/').
# Order matters: first regex match wins.
# ---------------------------------------------------------------------------

def param_rules(fsdp: bool):
    f = DATA if fsdp else None
    return [
        # MoE expert banks: (E, d_in, d_out) -> experts over model (EP)
        (r"experts?/(w_gate|w_up)$", P(MODEL, f, None)),
        (r"experts?/w_down$", P(MODEL, None, f)),
        (r"router/w$", P(f, None)),
        # embeddings / lm head: vocab-parallel
        (r"(embed|lm_head)/w$", P(MODEL, f)),
        (r"pos_embed/w$", P(None, f)),
        # attention projections
        (r"(wq|wk|wv|in_proj|qkv)/w$", P(f, MODEL)),
        (r"(wq|wk|wv|in_proj|qkv)/b$", P(MODEL)),
        (r"(wo|out_proj)/w$", P(MODEL, f)),
        (r"(wo|out_proj)/b$", P(None)),
        # dense mlp
        (r"(w_gate|w_up)/w$", P(f, MODEL)),
        (r"w_down/w$", P(MODEL, f)),
        # mamba / xlstm mixers: inner dim over model
        (r"mamba/(w_in|dt_w)$", P(f, MODEL)),
        (r"mamba/(w_out)$", P(MODEL, f)),
        (r"mamba/(conv_w)$", P(None, MODEL)),
        (r"mamba/(a_log)$", P(MODEL, None)),
        (r"mamba/(conv_b|d|dt_bias)$", P(MODEL)),
        (r"mamba/(w_bcdt)$", P(MODEL, None)),
        (r"(mlstm|slstm)/(w_qkv|w_if|w_in)$", P(f, MODEL)),
        (r"(mlstm|slstm)/(w_out|w_down)$", P(MODEL, f)),
        (r"slstm/w_rec$", P(MODEL, None, None)),
        # conv frontends (whisper stub projection, gan)
        (r"conv\d*/w$", P(None, None, f, MODEL)),
        # norms, scalars, biases: replicate (or fsdp the single dim)
        (r".*", None),
    ]


def spec_for_path(path: str, fsdp: bool) -> P:
    for pattern, spec in param_rules(fsdp):
        if re.search(pattern, path):
            return spec if spec is not None else P()
    return P()


def _leaf_paths(tree):
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    for keypath, leaf in flat:
        parts = []
        for k in keypath:
            if hasattr(k, "key"):
                parts.append(str(k.key))
            elif hasattr(k, "idx"):
                parts.append(str(k.idx))
            else:
                parts.append(str(k))
        yield "/".join(parts), leaf


def param_specs(params, fsdp: bool = False):
    """PartitionSpec pytree matching ``params``.

    Leading stacked-layer axes (from scan-stacked parameter trees) are
    detected by rank mismatch: rules describe the per-layer rank, and any
    extra leading dims get ``None`` entries prepended. Axis entries whose
    mesh extent doesn't divide the dim are dropped (jit in_shardings
    requires exact divisibility).
    """
    def one(path, leaf):
        if _MODE["mode"] == "fsdp":
            # ZeRO-3: shard ONE dim of every matrix over (data x model).
            # Try dims largest-first so a non-divisible preferred dim falls
            # back instead of silently replicating (codeqwen's d_ff=13440
            # doesn't divide 256 -> 17.9 GB/chip replicated before this).
            if leaf.ndim >= 1:
                order = sorted(
                    range(leaf.ndim), key=lambda i: -leaf.shape[i]
                )
                for i in order:
                    base = [None] * leaf.ndim
                    base[i] = ("data", "model")
                    spec = _filter(P(*base), leaf.shape)
                    if spec is not None:
                        return spec
            return P()
        spec = spec_for_path(path, fsdp)
        extra = leaf.ndim - len(spec)
        if extra > 0:
            spec = P(*([None] * extra), *spec)
        elif extra < 0:
            spec = P(*spec[-leaf.ndim:]) if leaf.ndim else P()
        return _filter(spec, leaf.shape) or P()

    paths = dict(_leaf_paths(params))
    flat, treedef = jax.tree_util.tree_flatten(params)
    specs = [one(path, leaf) for path, leaf in paths.items()]
    return jax.tree_util.tree_unflatten(treedef, specs)


def named_shardings(params, mesh, fsdp: bool = False):
    specs = param_specs(params, fsdp)
    return jax.tree_util.tree_map(
        lambda s: jax.sharding.NamedSharding(mesh, s), specs,
        is_leaf=lambda s: isinstance(s, P),
    )
