"""Compile-once execution plans for the transpose-conv dispatch stack.

Before this module existed, every ``transpose_conv2d(method="auto")`` call
re-consulted the autotune cache at trace time, re-resolved the backward
method inside the custom VJP, and keyed jit on a mutable ``_dispatch_epoch``
counter — per-call dispatch overhead one level above the kernels, exactly
the per-piece launch overhead the paper's unified kernel removes one level
below. HUGE² (arXiv:1907.11210) and GANAX (arXiv:1806.01107) both plan a
whole generator's layer sequence ahead of execution instead of deciding
per-op; this module is that planning step:

* :class:`LayerPlan` — an immutable, hashable record of EVERYTHING dispatch
  needs for one layer: the layer signature (batch, N, n, Cin, Cout, P,
  dtype) plus the resolved forward method (+ fused-kernel tiles) and the
  resolved backward method (+ dx tiles). Being hashable, it is a valid
  static jit argument: **jit keys on the plan value**, so two cache
  generations that resolve to the same decisions share one trace (the old
  epoch key retraced on every cache touch, even a no-op one).
* :class:`TconvPlan` — an ordered stack of ``LayerPlan``s for a whole
  generator, compiled **once** from the autotune cache (plus the cold-cache
  napkin rule) via :func:`compile_plan`.
* :func:`execute_layer` — runs one resolved layer. It is called at trace
  time only; no cache consult, no import, no file stat happens on the hot
  path. Pallas methods flow through :mod:`repro.kernels.ops` with the plan
  itself as the backward selector, so the custom VJP skips
  ``_resolve_bwd`` entirely.
* :func:`plan_layer` / :func:`plan_layer_cached` — single-layer resolution;
  the cached variant memoizes per (layer signature, cache generation) and
  is what the legacy ``transpose_conv2d(method="auto")`` wrapper uses, so
  repeated eager calls build the plan once per cache state.
* :func:`compile_plan_buckets` — ``{batch: TconvPlan}`` over a set of batch
  buckets, resolved through the memo; the serving engine's warmup
  (:mod:`repro.serve.gan_engine`) and the serving benchmark compile their
  fixed executable sets with this instead of hand-rolling the loop.

Resolution rules (identical to the dispatch they replace):

* ``method="auto"`` — the tuned ``step`` entry in training mode, else the
  tuned ``fwd`` entry; cold cache falls back to the §Perf napkin rule
  (segregated form iff the per-phase GEMM has ``ceil(M/2) >= 8`` rows).
* explicit ``pallas``/``pallas_fused``/``pallas_phase``/``pallas_gemm`` —
  the method is pinned; tuned tiles (spatial for the fused kernel, GEMM
  m/cout/cin for the implicit-GEMM kernel) are still picked up when the
  cache has them.
* backward — the tuned ``bwd`` entry (method + dx tiles); cold cache
  defaults to the segregated Pallas backward on a real accelerator backend
  and the lax VJP elsewhere.
* epilogue — a layer's fused bias+activation tail
  (:mod:`repro.kernels.epilogue`) is PART of the layer signature: the plan
  resolves the whole ``act(tconv + b)`` unit, including whether the Pallas
  kernels run the epilogue in-kernel or as composed post-ops
  (``fuse_epilogue``, raced by the autotuner since cache schema v3).
* layer-pair fusion — :func:`fuse_pairs` (run by :func:`compile_plan` for
  serving-mode plans) walks the compiled stack, checks pair legality
  (adjacent stride-2 tconv -> tconv, bias epilogue on the interface, the
  producer's whole output plane + consumer halo within the VMEM budget of
  :func:`repro.kernels.transpose_conv2d_pair.pair_vmem_bytes`) and replaces
  eligible adjacent ``LayerPlan`` pairs with a :class:`FusedPairPlan` when
  the autotuner's ``pair`` race (cache schema v4) picked the fused kernel —
  the interface activation then never touches HBM. Train-mode plans stay
  unfused: gradients always flow through the per-layer tuned backward.
"""
from __future__ import annotations

import dataclasses
import functools

import jax

from repro.core import segregation as seg
from repro.kernels import epilogue as epilib
from repro.kernels.epilogue import Epilogue

# forward methods that resolve through plans (everything the autotuner can
# pick, plus the explicit Pallas spellings)
PLANNED_METHODS = (
    "auto", "pallas", "pallas_fused", "pallas_phase", "pallas_gemm",
)
_PALLAS_FWD = ("pallas", "pallas_fused", "pallas_phase", "pallas_gemm")


@dataclasses.dataclass(frozen=True)
class LayerPlan:
    """Resolved dispatch for ONE transpose-conv layer. Immutable + hashable
    — usable directly as a static jit argument."""

    # layer signature
    batch: int
    n_in: int
    n_k: int
    cin: int
    cout: int
    padding: int
    dtype: str = "float32"
    # elementwise tail of the layer (act(y + b)); None = bare transpose conv
    epilogue: Epilogue | None = None
    # resolved forward
    method: str = "unified_reshape"
    tile_h: int | None = None     # fused Pallas forward spatial tiles
    tile_w: int | None = None
    tile_m: int | None = None     # implicit-GEMM forward tiles (rows,
    tile_n: int | None = None     # cout lanes, cin reduction) — set only
    tile_k: int | None = None     # when method resolves to pallas_gemm
    # whether the Pallas kernels run the epilogue in-kernel (fused on the
    # fp32 accumulator) or the layer composes it as post-ops — the autotuner
    # races both; lax methods always compose (XLA fuses elementwise tails)
    fuse_epilogue: bool = True
    # resolved backward
    bwd_method: str = "lax"
    bwd_tile_h: int | None = None  # Pallas dx spatial tiles
    bwd_tile_w: int | None = None
    # provenance: "tuned" (autotune cache hit) or "cold" (napkin rule).
    # compare=False keeps it out of eq/hash: a cold->tuned transition that
    # resolves to the identical dispatch decision must share the jit trace.
    source: str = dataclasses.field(default="cold", compare=False)

    def describe(self) -> str:
        tiles = (f"[{self.tile_h}x{self.tile_w}]"
                 if self.tile_h is not None else "")
        if self.tile_m is not None:
            tiles = f"[{self.tile_m}x{self.tile_n}x{self.tile_k}]"
        btiles = (f"[{self.bwd_tile_h}x{self.bwd_tile_w}]"
                  if self.bwd_tile_h is not None else "")
        epi = ""
        if self.epilogue is not None:
            fused = "fused" if self.fuse_epilogue else "postops"
            epi = f" epi={self.epilogue.tag()}({fused})"
        return (
            f"{self.n_in}x{self.n_in}x{self.cin}->{self.cout} "
            f"k{self.n_k} p{self.padding} b{self.batch} {self.dtype}: "
            f"fwd={self.method}{tiles} bwd={self.bwd_method}{btiles}{epi} "
            f"({self.source})"
        )


@dataclasses.dataclass(frozen=True)
class FusedPairPlan:
    """TWO adjacent :class:`LayerPlan`s resolved to one fused-pair launch
    (:func:`repro.kernels.transpose_conv2d_pair.transpose_conv2d_pair_pallas`).

    The per-layer plans are kept verbatim: they are the racing baseline
    (back-to-back launches), the fallback when an entry is executed
    standalone, and the backward path — gradients through a fused pair
    recompute the interface and fall back to each layer's tuned backward.
    Immutable + hashable like every plan object (static jit key).
    """

    first: LayerPlan
    second: LayerPlan
    # tuned pair-kernel channel tiles (None = kernel defaults)
    tile_ci: int | None = None
    tile_mid: int | None = None
    tile_co: int | None = None
    source: str = dataclasses.field(default="cold", compare=False)

    # what the pair executes as (class attribute, not a field: every
    # FusedPairPlan IS the fused kernel — a back-to-back winner simply
    # stays two LayerPlans)
    method = "pallas_pair"

    @property
    def batch(self) -> int:
        return self.first.batch

    @property
    def padding(self) -> int:
        return self.first.padding

    @property
    def epilogue(self):
        """The pair's OUTPUT epilogue (the interface epilogue is
        ``first.epilogue``, applied on the fp32 scratch accumulator)."""
        return self.second.epilogue

    def describe(self) -> str:
        tiles = ""
        if self.tile_ci or self.tile_mid or self.tile_co:
            tiles = f"[{self.tile_ci}x{self.tile_mid}x{self.tile_co}]"
        return (
            f"{self.first.n_in}x{self.first.n_in}x{self.first.cin}"
            f"->{self.first.cout}->{self.second.cout} "
            f"k{self.first.n_k} p{self.padding} b{self.batch} "
            f"{self.first.dtype}: fwd=pallas_pair{tiles} "
            f"iface={self.first.epilogue.tag()}@vmem "
            f"epi={self.second.epilogue.tag()} ({self.source})"
        )


@dataclasses.dataclass(frozen=True)
class TconvPlan:
    """An ordered stack of plan entries for a whole generator.

    ``layers`` holds the plan ENTRIES in execution order — ``LayerPlan``s,
    with eligible adjacent pairs possibly replaced by a
    :class:`FusedPairPlan` (the :func:`fuse_pairs` pass). Logical-layer
    views are preserved: ``len(plan)``/iteration/indexing flatten fused
    pairs back to per-layer ``LayerPlan``s, so a plan always matches its
    config's layer count and any logical layer can still be executed (or
    differentiated) standalone. Executors walk ``plan.entries`` instead.

    Immutable and hashable: close over it (or pass it as a static jit
    argument) and the traced computation is pinned — per-call dispatch is
    gone and retuning can only take effect through an explicit recompile
    (see docs/ARCHITECTURE.md: compile -> execute -> retune -> recompile).
    """

    name: str
    layers: tuple  # tuple[LayerPlan | FusedPairPlan, ...] — entries

    @property
    def entries(self) -> tuple:
        """Plan entries in execution order (pairs NOT flattened)."""
        return self.layers

    @functools.cached_property
    def _logical(self) -> tuple:
        out = []
        for e in self.layers:
            if isinstance(e, FusedPairPlan):
                out.extend((e.first, e.second))
            else:
                out.append(e)
        return tuple(out)

    def __len__(self) -> int:
        return len(self._logical)

    def __iter__(self):
        return iter(self._logical)

    def __getitem__(self, i) -> LayerPlan:
        return self._logical[i]

    def describe(self) -> str:
        n_pairs = sum(isinstance(e, FusedPairPlan) for e in self.layers)
        head = f"TconvPlan({self.name}, {len(self)} layers"
        head += f", {n_pairs} fused pairs)" if n_pairs else ")"
        lines = [head]
        i = 0
        for e in self.layers:
            if isinstance(e, FusedPairPlan):
                lines.append(f"  [{i}-{i + 1}] {e.describe()}")
                i += 2
            else:
                lines.append(f"  [{i}] {e.describe()}")
                i += 1
        return "\n".join(lines)


def _cold_fwd(n_in: int, n_k: int, padding: int) -> str:
    """The §Perf napkin rule the autotuner falls back to when cold."""
    m = seg.output_size(n_in, n_k, padding)
    return "unified_reshape" if (m + 1) // 2 >= 8 else "conventional"


def _cold_bwd() -> str:
    """Cold backward default: Pallas on a real accelerator, lax VJP on CPU
    (where Pallas only interprets at Python speed)."""
    return "pallas" if jax.default_backend() == "tpu" else "lax"


def _known_fwd(method: str) -> bool:
    from repro.core import transpose_conv as tc

    if method in _PALLAS_FWD:
        return True
    fn = tc.METHODS.get(method)
    return fn is not None and fn is not tc.transpose_conv_auto


def fwd_fits_vmem(method: str, b: int, n_in: int, n_k: int, cin: int,
                  cout: int, padding: int, dtype: str = "float32") -> bool:
    """Whether forward ``method`` fits the Mosaic VMEM budget at this layer.

    Only the whole-plane kernels (``pallas_phase``, ``pallas_gemm``) are
    screened: their VMEM grows with the spatial plane. Every other method is
    bounded by its tiles (or is not a Pallas kernel) and always fits.
    """
    import jax.numpy as jnp

    from repro.kernels import mosaic

    itemsize = jnp.dtype(dtype).itemsize
    if method == "pallas_phase":
        from repro.kernels.transpose_conv2d import phase_vmem_bytes

        need = phase_vmem_bytes(n_in, n_k, padding, cin, cout, itemsize)
    elif method == "pallas_gemm":
        from repro.kernels.transpose_conv2d_gemm import gemm_vmem_bytes

        need = gemm_vmem_bytes(b, n_in, n_k, padding, cin, cout, itemsize)
    else:
        return True
    return mosaic.fits_vmem(need)


def plan_layer(
    b: int, n_in: int, n_k: int, cin: int, cout: int, padding: int,
    dtype: str = "float32", *, method: str = "auto", train: bool = False,
    epilogue: Epilogue | None = None,
) -> LayerPlan:
    """Resolve one layer's dispatch from the autotune cache (or cold rules).

    This is the ONLY place the plan subsystem consults the cache; it runs
    at plan-compile time, never per executed call. ``method="auto"`` follows
    the tuned winner (``step`` in training mode, else ``fwd``); explicit
    methods are pinned but still pick up tuned fused tiles / the tuned
    backward entry. ``epilogue`` is part of the layer signature (cache
    schema v3): an epilogue'd layer tunes — and resolves — the WHOLE
    ``act(tconv + b)`` unit, including whether the Pallas kernels fuse the
    epilogue in-kernel or compose it as post-ops (``fuse_epilogue``).
    """
    from repro.kernels import autotune

    epilogue = epilib.canonical(epilogue)
    rec = autotune.best_entry(
        b, n_in, n_k, cin, cout, padding, dtype, epilogue=epilogue
    ) or {}
    fwd = rec.get("fwd") or {}
    source = "cold"
    tile_h = tile_w = None
    tile_m = tile_n = tile_k = None
    fuse_epi = True  # cold default: the fused epilogue is the point
    if method == "auto":
        entry = (rec.get("step") if train else None) or fwd or None
        if entry is not None and _known_fwd(entry.get("method", "")):
            resolved = entry["method"]
            # step winners carry the fwd race's tiles; fall back to the fwd
            # entry's tiles when only the fwd direction was tuned
            tile_h = entry.get("tile_h", fwd.get("tile_h"))
            tile_w = entry.get("tile_w", fwd.get("tile_w"))
            tile_m = entry.get("tile_m", fwd.get("tile_m"))
            tile_n = entry.get("tile_n", fwd.get("tile_n"))
            tile_k = entry.get("tile_k", fwd.get("tile_k"))
            fuse_epi = entry.get(
                "fuse_epilogue", fwd.get("fuse_epilogue", True)
            )
            source = "tuned"
        else:
            resolved = _cold_fwd(n_in, n_k, padding)
    else:
        if not _known_fwd(method):
            raise ValueError(f"unknown method {method!r} for LayerPlan")
        resolved = "pallas_fused" if method == "pallas" else method
        if not fwd_fits_vmem(resolved, b, n_in, n_k, cin, cout, padding, dtype):
            raise ValueError(
                f"{resolved} does not fit the VMEM budget at layer "
                f"b{b} {n_in}x{n_in}x{cin}->{cout} k{n_k} p{padding} {dtype}"
            )
        if resolved == "pallas_fused" and fwd.get("method") == "pallas_fused":
            tile_h, tile_w = fwd.get("tile_h"), fwd.get("tile_w")
            fuse_epi = fwd.get("fuse_epilogue", True)
            source = "tuned"  # pinned method, but tiles came from the cache
        elif resolved == "pallas_gemm" and fwd.get("method") == "pallas_gemm":
            tile_m, tile_n = fwd.get("tile_m"), fwd.get("tile_n")
            tile_k = fwd.get("tile_k")
            fuse_epi = fwd.get("fuse_epilogue", True)
            source = "tuned"
    if resolved not in ("pallas_fused", "pallas"):
        tile_h = tile_w = None
    if resolved != "pallas_gemm":
        tile_m = tile_n = tile_k = None
    if resolved not in _PALLAS_FWD or epilogue is None:
        fuse_epi = True  # only meaningful for epilogue'd Pallas layers

    bwd = rec.get("bwd")
    if bwd is not None:
        bwd_method = bwd.get("method", "lax")
        bwd_tile_h, bwd_tile_w = bwd.get("tile_h"), bwd.get("tile_w")
    else:
        bwd_method = _cold_bwd()
        bwd_tile_h = bwd_tile_w = None

    return LayerPlan(
        batch=b, n_in=n_in, n_k=n_k, cin=cin, cout=cout, padding=padding,
        dtype=dtype, epilogue=epilogue, method=resolved,
        tile_h=tile_h, tile_w=tile_w,
        tile_m=tile_m, tile_n=tile_n, tile_k=tile_k,
        fuse_epilogue=fuse_epi,
        bwd_method=bwd_method, bwd_tile_h=bwd_tile_h, bwd_tile_w=bwd_tile_w,
        source=source,
    )


@functools.lru_cache(maxsize=None)
def _plan_layer_cached(
    b, n_in, n_k, cin, cout, padding, dtype, method, train, epilogue, epoch
) -> LayerPlan:
    del epoch  # part of the memo key only: new cache generation -> new entry
    return plan_layer(
        b, n_in, n_k, cin, cout, padding, dtype, method=method, train=train,
        epilogue=epilogue,
    )


def plan_layer_cached(
    b: int, n_in: int, n_k: int, cin: int, cout: int, padding: int,
    dtype: str = "float32", *, method: str = "auto", train: bool = False,
    epilogue: Epilogue | None = None,
) -> LayerPlan:
    """Memoized :func:`plan_layer`, keyed by (signature, cache generation).

    The legacy per-call wrapper (``transpose_conv2d(method="auto")``) goes
    through this: within one cache generation a layer signature resolves
    exactly once, and a retune (generation bump) transparently yields a
    fresh plan — whose *value* is the jit key, so an unchanged decision
    does not retrace.
    """
    from repro.kernels import autotune

    return _plan_layer_cached(
        b, n_in, n_k, cin, cout, padding, dtype, method, train,
        epilib.canonical(epilogue), autotune.generation(),
    )


# --------------------------------------------------------------- pair fusion

def pair_legal(lp1: LayerPlan, lp2: LayerPlan) -> tuple[bool, str]:
    """Legality of fusing two adjacent layer plans into one pair launch.

    Checks the stride-2 tconv -> tconv chain (consumer input extent equals
    the producer output extent, channel chain intact, same kernel/padding),
    a bias-carrying epilogue on the interface AND the output (the pair
    kernel applies both on fp32 accumulators), the fp32 interface contract,
    and the VMEM budget: the producer's whole output plane + the consumer's
    halo + both sub-kernel stacks must fit
    :data:`repro.kernels.mosaic.VMEM_BUDGET_BYTES`.
    Returns ``(ok, reason)`` — the reason string names the failed check.
    """
    from repro.kernels import mosaic
    from repro.kernels import transpose_conv2d_pair as pairlib

    if lp1.batch != lp2.batch:
        return False, f"batch mismatch ({lp1.batch} vs {lp2.batch})"
    if lp1.n_k != lp2.n_k or lp1.padding != lp2.padding:
        return False, "kernel/padding mismatch"
    m1 = seg.output_size(lp1.n_in, lp1.n_k, lp1.padding)
    if lp2.n_in != m1:
        return False, f"not adjacent (consumer n_in {lp2.n_in} != M1 {m1})"
    if lp1.cout != lp2.cin:
        return False, f"channel chain broken ({lp1.cout} -> {lp2.cin})"
    epi1, epi2 = lp1.epilogue, lp2.epilogue
    if epi1 is None or not epi1.bias:
        return False, "no bias epilogue on the interface"
    if epi2 is None or not epi2.bias:
        return False, "no bias epilogue on the output"
    if lp2.dtype != "float32":
        return False, (
            f"consumer dtype {lp2.dtype} != float32 (the interface is the "
            "fp32 accumulator)"
        )
    if lp1.dtype not in ("float32", "bfloat16"):
        return False, f"unsupported producer dtype {lp1.dtype}"
    need = pairlib.pair_vmem_bytes(
        lp1.n_in, lp1.n_k, lp1.cin, lp1.cout, lp2.cout, lp1.padding,
        dtype_bytes=2 if lp1.dtype == "bfloat16" else 4,
    )
    if not mosaic.fits_vmem(need):
        return False, (
            f"VMEM estimate {need} B > budget {mosaic.VMEM_BUDGET_BYTES} B"
        )
    return True, "ok"


def plan_pair(lp1: LayerPlan, lp2: LayerPlan, *,
              fuse="auto") -> FusedPairPlan | None:
    """Resolve whether an adjacent pair fuses. Returns the
    :class:`FusedPairPlan` or None (= stay back-to-back).

    ``fuse="auto"`` consults the autotuner's ``pair`` race (cache schema
    v4): the pair fuses iff the recorded winner is the fused kernel, with
    tuned channel tiles picked up; a cold cache mirrors the cold-backward
    napkin rule (fuse on a real accelerator backend, stay back-to-back on
    CPU where Pallas only interprets). ``fuse=True``/``"force"`` fuses
    every legal pair regardless of the race; ``fuse=False``/``"off"``
    never fuses. Illegal pairs never fuse.
    """
    from repro.kernels import autotune

    if fuse is False or fuse == "off":
        return None
    ok, _why = pair_legal(lp1, lp2)
    if not ok:
        return None
    if fuse in (True, "force"):
        return FusedPairPlan(first=lp1, second=lp2, source="forced")
    rec = autotune.best_pair(
        lp1.batch, lp1.n_in, lp1.n_k, lp1.cin, lp1.cout, lp2.cout,
        lp1.padding, lp1.dtype,
        epilogue1=lp1.epilogue, epilogue2=lp2.epilogue,
    )
    if rec is not None:
        if rec.get("method") == "pallas_pair":
            return FusedPairPlan(
                first=lp1, second=lp2,
                tile_ci=rec.get("tile_ci"), tile_mid=rec.get("tile_mid"),
                tile_co=rec.get("tile_co"), source="tuned",
            )
        return None  # the race picked back-to-back launches
    if jax.default_backend() == "tpu":
        return FusedPairPlan(first=lp1, second=lp2, source="cold")
    return None


def fuse_pairs(plan: TconvPlan, *, train: bool = False,
               fuse="auto") -> TconvPlan:
    """The plan-level fusion pass: legality -> VMEM estimate -> race winner
    -> :class:`FusedPairPlan` substitution.

    Walks the logical layer stack greedily left-to-right, fusing each
    eligible adjacent pair per :func:`plan_pair` (a fused layer is consumed
    and the walk continues after it). Train-mode plans are returned
    unfused: fusion is forward/serving-first, and gradients always use the
    per-layer tuned backward. Idempotent — refusing a plan re-flattens and
    re-resolves, so a generation bump can change the decisions.
    """
    if train or fuse is False or fuse == "off":
        return plan
    logical = tuple(plan)  # flatten any existing fusion first
    entries: list = []
    i = 0
    while i < len(logical):
        fp = None
        if i + 1 < len(logical):
            fp = plan_pair(logical[i], logical[i + 1], fuse=fuse)
        if fp is not None:
            entries.append(fp)
            i += 2
        else:
            entries.append(logical[i])
            i += 1
    return TconvPlan(name=plan.name, layers=tuple(entries))


def compile_plan(cfg, batch: int, dtype="float32", *, train: bool = False,
                 method: str = "auto", epilogues=None,
                 fuse="auto") -> TconvPlan:
    """Compile a whole-generator :class:`TconvPlan` from the autotune cache.

    ``cfg`` is a GAN config (anything with ``layers`` as ``(input_hw, cin,
    cout)`` triples plus ``kernel``/``padding``/``name``). Call it once,
    after tuning and before tracing; thread the result through
    ``generator_apply(plan=...)`` / the train step. Retuning requires an
    explicit recompile — compiled plans are immutable by design.

    ``epilogues`` is an optional per-layer tuple of
    :class:`~repro.kernels.epilogue.Epilogue` (or None entries) baking each
    layer's bias+activation tail into its plan —
    :func:`repro.models.gan.generator_plan` derives the generator's
    (bias+relu ... bias+tanh) stack automatically.

    Serving-mode plans (``train=False``) then run the :func:`fuse_pairs`
    pass, controlled by ``fuse`` (``"auto"`` — pair-race winner / cold
    rule, ``True``/``"force"`` — every legal pair, ``False``/``"off"`` —
    never).
    """
    import jax.numpy as jnp

    dt = str(jnp.dtype(dtype))
    if epilogues is None:
        epilogues = (None,) * len(cfg.layers)
    if len(epilogues) != len(cfg.layers):
        raise ValueError(
            f"epilogues has {len(epilogues)} entries for "
            f"{len(cfg.layers)} layers"
        )
    layers = tuple(
        plan_layer(batch, hw, cfg.kernel, cin, cout, cfg.padding, dt,
                   method=method, train=train, epilogue=epi)
        for (hw, cin, cout), epi in zip(cfg.layers, epilogues)
    )
    plan = TconvPlan(name=getattr(cfg, "name", "tconv"), layers=layers)
    return fuse_pairs(plan, train=train, fuse=fuse)


def compile_plan_buckets(cfg, batches, dtype="float32", *,
                         train: bool = False, method: str = "auto",
                         epilogues=None, fuse="auto") -> dict:
    """Compile one :class:`TconvPlan` per batch bucket: ``{batch: plan}``.

    The serving engine (and the serving benchmark) run a fixed set of batch
    **buckets** so their steady state is a fixed set of executables; this is
    the one-call warmup for that set. Layer resolution goes through
    :func:`plan_layer_cached`, so buckets sharing a layer signature resolve
    it once per autotune-cache generation instead of re-consulting the
    cache per bucket — and a later ``compile_plan_buckets`` call in the same
    generation is pure memo lookups. Arguments mirror
    :func:`compile_plan`; ``batches`` is any iterable of ints (duplicates
    collapse).
    """
    import jax.numpy as jnp

    dt = str(jnp.dtype(dtype))
    if epilogues is None:
        epilogues = (None,) * len(cfg.layers)
    if len(epilogues) != len(cfg.layers):
        raise ValueError(
            f"epilogues has {len(epilogues)} entries for "
            f"{len(cfg.layers)} layers"
        )
    name = getattr(cfg, "name", "tconv")
    plans = {}
    for batch in sorted({int(b) for b in batches}):
        if batch < 1:
            raise ValueError(f"batch buckets must be positive, got {batch}")
        layers = tuple(
            plan_layer_cached(batch, hw, cfg.kernel, cin, cout, cfg.padding,
                              dt, method=method, train=train, epilogue=epi)
            for (hw, cin, cout), epi in zip(cfg.layers, epilogues)
        )
        plans[batch] = fuse_pairs(
            TconvPlan(name=name, layers=layers), train=train, fuse=fuse
        )
    return plans


def execute_layer(lp: LayerPlan, x, kernel, *, bias=None, precision=None):
    """Run one resolved layer. Runs at TRACE time only (the plan is a static
    jit key); no cache consult or backward re-resolution happens here.

    Epilogue'd plans execute the WHOLE layer ``act(tconv + b)``: Pallas
    methods fuse the epilogue in-kernel when the plan says so
    (``fuse_epilogue``, the backward then flows through the fused
    ``g·act'(y)`` prologue + dual dw/db accumulator); lax methods compose
    the identical :meth:`Epilogue.apply` post-ops, so every method stays
    numerically interchangeable.
    """
    if isinstance(lp, FusedPairPlan):
        raise TypeError(
            "a FusedPairPlan spans two layers (two kernels, two biases) — "
            "execute it via execute_pair, or execute its .first/.second "
            "LayerPlans standalone"
        )
    if (x.shape[1], kernel.shape[0], kernel.shape[2], kernel.shape[3]) != (
        lp.n_in, lp.n_k, lp.cin, lp.cout
    ) or str(x.dtype) != lp.dtype:
        raise ValueError(
            f"LayerPlan mismatch: plan is for {lp.describe()!r}, got input "
            f"{x.shape}/{x.dtype} kernel {kernel.shape}"
        )
    epi = lp.epilogue
    if (epi is not None and epi.bias) != (bias is not None):
        raise ValueError(
            f"LayerPlan epilogue mismatch: plan is for {lp.describe()!r}, "
            f"got bias={'set' if bias is not None else None}"
        )
    if lp.method in _PALLAS_FWD:
        from repro.kernels import ops

        fuse = epi is not None and lp.fuse_epilogue
        kernel_epi = epi if fuse else None
        kernel_bias = bias if fuse else None
        if lp.method == "pallas_phase":
            y = ops.transpose_conv2d_pallas_phase(
                x, kernel, lp.padding, lp, kernel_epi, kernel_bias
            )
        elif lp.method == "pallas_gemm":
            y = ops.transpose_conv2d_pallas_gemm(
                x, kernel, lp.padding, lp.tile_m, lp.tile_n, lp.tile_k,
                lp, kernel_epi, kernel_bias,
            )
        else:
            y = ops.transpose_conv2d_pallas(
                x, kernel, lp.padding, lp.tile_h, lp.tile_w, lp,
                kernel_epi, kernel_bias,
            )
        if epi is not None and not fuse:
            y = epi.apply(y, bias)
        return y
    from repro.core import transpose_conv as tc

    fn = tc.METHODS.get(lp.method)
    if fn is None or fn is tc.transpose_conv_auto:
        raise ValueError(f"LayerPlan resolved to unknown method {lp.method!r}")
    y = fn(x, kernel, lp.padding, precision=precision)
    if epi is not None:
        y = epi.apply(y, bias)
    return y


def execute_pair(fp: FusedPairPlan, x, k1, k2, *, bias1=None, bias2=None):
    """Run one fused layer pair from a single pair-kernel launch.

    Trace-time only, like :func:`execute_layer`. ``k1``/``bias1`` belong to
    the producer (interface epilogue, applied on the fp32 VMEM scratch
    accumulator), ``k2``/``bias2`` to the consumer. Differentiable: the
    custom VJP (:func:`repro.kernels.ops.transpose_conv2d_pair`) recomputes
    the interface and falls back to each layer's tuned per-layer backward.
    """
    lp1, lp2 = fp.first, fp.second
    if (x.shape[1], k1.shape[0], k1.shape[2], k1.shape[3]) != (
        lp1.n_in, lp1.n_k, lp1.cin, lp1.cout
    ) or str(x.dtype) != lp1.dtype:
        raise ValueError(
            f"FusedPairPlan mismatch: pair is {fp.describe()!r}, got input "
            f"{x.shape}/{x.dtype} k1 {k1.shape}"
        )
    if (k2.shape[0], k2.shape[2], k2.shape[3]) != (
        lp2.n_k, lp2.cin, lp2.cout
    ):
        raise ValueError(
            f"FusedPairPlan mismatch: pair is {fp.describe()!r}, "
            f"got k2 {k2.shape}"
        )
    for name, epi, bias in (
        ("interface", lp1.epilogue, bias1), ("output", lp2.epilogue, bias2)
    ):
        if (epi is not None and epi.bias) != (bias is not None):
            raise ValueError(
                f"FusedPairPlan {name} epilogue mismatch: pair is "
                f"{fp.describe()!r}, got "
                f"bias={'set' if bias is not None else None}"
            )
    from repro.kernels import ops

    return ops.transpose_conv2d_pair(fp, x, k1, k2, bias1, bias2)
