"""Benchmark-driven per-layer operator selection with a persistent cache.

The dispatch problem created by having many mathematically-identical
transpose-conv implementations (conventional / unified_reshape /
unified_matmul / unified_fused / pallas_phase / pallas_fused) is the one
HUGE² (arXiv:1907.11210) solves with *measured* per-layer operator selection:
no napkin rule survives contact with real hardware, so the winner for a layer
shape is decided by timing candidates on the machine at hand and remembered.

Since cache schema **v2** the training step is the tuned unit; since
schema **v3** the layer signature additionally carries the layer's fused
bias+activation **epilogue** (:mod:`repro.kernels.epilogue` — key component
``e:<tag>``), and for epilogue'd layers the races compare the
fused-epilogue Pallas kernels against their unfused
kernel-plus-post-ops variants in every direction. Since schema **v4**
eligible adjacent layer *pairs* additionally get their own ``|pair|``
keys (:func:`pair_key`) whose ``pair`` entry records the fused-pair race:
the megafusion kernel (``repro.kernels.transpose_conv2d_pair`` — both
layers in one launch, interface activation VMEM-resident) vs two
back-to-back fused launches. Each layer record carries per-direction
entries —

* ``fwd``   — the forward operator race (what v1 stored);
* ``bwd``   — the backward race between the segregated Pallas backward
  (``repro.kernels.transpose_conv2d_bwd`` — dx + dw kernels) and the lax
  VJP of ``transpose_conv_unified``; the winner is what
  ``repro.kernels.ops``'s custom VJP dispatches to (``bwd="auto"``);
* ``step``  — the full fwd+bwd ``value_and_grad`` race per forward method:
  the winner is what ``method="auto"`` dispatches to in **training** mode
  (``train=True``), where a forward that is fast to run but slow to
  differentiate must lose;
* ``pair``  — on ``|pair|`` keys only: ``pallas_pair`` vs ``back_to_back``
  (:data:`PAIR_CANDIDATES`); the winner is what the plan fusion pass
  (``repro.kernels.plan.fuse_pairs``) consults via :func:`best_pair`.

Components:

* :func:`tune_layer` — times every candidate for one layer shape (several
  spatial-tile variants for the Pallas kernels) and records the winner;
  ``train=True`` additionally tunes the ``bwd`` and ``step`` directions.
* A persistent JSON cache keyed by ``(backend, batch, N, n, Cin, Cout, P,
  dtype, epilogue)``; location from ``$REPRO_AUTOTUNE_CACHE`` (default
  ``~/.cache/repro/autotune.json``). Concurrent writers last-write-win on an
  atomic rename; the in-memory view reloads on file mtime change. **v1–v3
  cache files migrate on load** (v1 flat entries become the ``fwd``
  direction; v1/v2 keys gain the ``e:none`` epilogue component; v3 is a
  strict subset of v4 — layer keys and records load verbatim, they simply
  predate ``|pair|`` keys — tuned tiles survive every hop) and are
  rewritten as v4 on the next save; unknown versions are ignored (and set
  aside, never clobbered, on save), and v4 records whose recorded winner
  method this build cannot dispatch (written by a NEWER checkout — e.g. a
  kernel this build predates) are likewise set aside on load: excluded
  from every lookup, merged back verbatim on save (see
  :func:`known_winner_methods`).
  ``--prune`` (or :func:`prune_cache`) drops entries whose key no longer
  parses under the current schema instead of carrying them forever.
* :func:`best_method` / :func:`best_bwd` / :func:`best_entry` — cache-only
  consults used at trace time by ``transpose_conv_auto`` (fwd/step) and the
  custom VJP in ``repro.kernels.ops`` (bwd). A miss falls back to the old
  heuristic (cold-cache behaviour is unchanged).
* :func:`roofline_proxy` / :func:`gemm_roofline_proxy` /
  :func:`bwd_roofline_proxy` — analytic
  ``max(flops/peak_flops, bytes/peak_bw)`` seconds for the Pallas grids and
  their lax counterparts. The lax-based candidates always race on wall
  clock. The Pallas kernels race on wall clock only on a real accelerator
  backend (and can then win dispatch); on CPU they only run in interpret
  mode (Python-speed, not predictive of TPU), so there they are *reported*
  via the proxy and never selected as the winner.

Cache entry format (``docs/AUTOTUNE.md``)::

    {"fwd":  {"method": "unified_reshape", "time_s": 1.2e-4,
              "source": "measured", "tile_h": 8, "tile_w": 128,
              "candidates": {...}, "proxy": {...}},
     "bwd":  {"method": "lax", "time_s": 3.1e-4, "source": "measured",
              "candidates": {...}, "proxy": {"pallas": ..., "lax": ...}},
     "step": {"method": "unified_reshape", "time_s": 4.4e-4,
              "candidates": {...}}}
"""
from __future__ import annotations

import json
import os
import re
import tempfile
from pathlib import Path
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import segregation as seg
from repro.kernels import epilogue as epilib
from repro.kernels.mosaic import fits_vmem
from repro.kernels.transpose_conv2d import default_tiles
from repro.kernels.transpose_conv2d_bwd import (
    default_bwd_tiles,
    default_dw_tile,
)
from repro.obs import audit as obs_audit
from repro.timing import time_fn as _time_fn

# Nominal accelerator peaks for the roofline proxy (TPU v4-ish; only the
# RATIO between candidates matters for selection, not the absolute numbers).
PEAK_FLOPS = 275e12
PEAK_BW = 1.2e12

_CACHE_VERSION = 4
_DIRECTIONS = ("fwd", "bwd", "step", "pair")
# what a well-formed v4 key looks like — a v3-style layer signature or a
# |pair| fused-pair signature; --prune drops everything else
_KEY_RE = re.compile(
    r"^[A-Za-z0-9_]+\|b\d+\|n\d+\|k\d+\|ci\d+\|co\d+\|p\d+"
    r"\|[A-Za-z0-9_.]+\|e:[A-Za-z0-9.+_-]+$"
    r"|^[A-Za-z0-9_]+\|pair\|b\d+\|n\d+\|k\d+\|ci\d+\|mid\d+\|co\d+\|p\d+"
    r"\|[A-Za-z0-9_.]+\|e1:[A-Za-z0-9.+_-]+\|e2:[A-Za-z0-9.+_-]+$"
)
# in-memory cache state; "generation" bumps whenever entries change (record,
# clear, reload-from-disk) so 'auto' dispatch can retrace (see generation()).
# "alien" holds v3 records whose winner method this build doesn't know
# (written by a newer checkout): excluded from every lookup, merged back
# verbatim on save — set aside, never served, never clobbered.
_STATE: dict[str, Any] = {
    "path": None, "mtime": -1.0, "entries": {}, "alien": {}, "generation": 0,
}

# Spatial-tile variants raced for the fused forward Pallas kernel.
_FUSED_TILES = ((8, 128), (16, 128), (8, 64), (32, 32))
# (tile_m, tile_n, tile_k) variants raced for the implicit-GEMM forward
# (per shape they are clamped/deduped by _gemm_tile_variants).
_GEMM_TILES = ((128, 128, 512), (256, 128, 512), (512, 128, 512),
               (256, 128, 256))
# dx spatial-tile variants raced for the Pallas backward (dw races its
# default reduction tile; the dx grid dominates the backward traffic).
_BWD_TILES = ((8, 128), (16, 128), (8, 64), (32, 32))
# (cin, mid, cout) channel-tile variants raced for the fused-pair kernel
# (per shape they are snapped to dividing tiles by _pair_tile_variants).
_PAIR_TILES = ((128, 64, 256), (256, 256, 512), (64, 128, 512))


def cache_path() -> Path:
    p = os.environ.get("REPRO_AUTOTUNE_CACHE")
    if p:
        return Path(p)
    return Path.home() / ".cache" / "repro" / "autotune.json"


def layer_key(
    b: int, n_in: int, n_k: int, cin: int, cout: int, padding: int,
    dtype: str = "float32", backend: str | None = None,
    epilogue=None,
) -> str:
    backend = backend or jax.default_backend()
    epi = epilib.canonical(epilogue)
    tag = "none" if epi is None else epi.tag()
    return (
        f"{backend}|b{b}|n{n_in}|k{n_k}|ci{cin}|co{cout}|p{padding}|{dtype}"
        f"|e:{tag}"
    )


def pair_key(
    b: int, n_in: int, n_k: int, c0: int, c1: int, c2: int, padding: int,
    dtype: str = "float32", backend: str | None = None,
    *, epilogue1=None, epilogue2=None,
) -> str:
    """Cache key for a fused layer pair (schema v4 ``|pair|`` signature).

    ``(n_in, c0) -> (c1) -> (c2)`` is the producer's input extent and the
    channel chain; ``dtype`` is the producer's input dtype (the interface
    is always the fp32 accumulator), and the two epilogues are the
    interface tail and the output tail.
    """
    backend = backend or jax.default_backend()
    e1 = epilib.canonical(epilogue1)
    e2 = epilib.canonical(epilogue2)
    t1 = "none" if e1 is None else e1.tag()
    t2 = "none" if e2 is None else e2.tag()
    return (
        f"{backend}|pair|b{b}|n{n_in}|k{n_k}|ci{c0}|mid{c1}|co{c2}"
        f"|p{padding}|{dtype}|e1:{t1}|e2:{t2}"
    )


def _normalize(entry: dict) -> dict:
    """Flat v1-style entries become the ``fwd`` direction of a v2 record."""
    if any(d in entry for d in _DIRECTIONS):
        return entry
    return {"fwd": entry}


def _migrate_key(key: str) -> str:
    """v1/v2 keys (no epilogue component) describe epilogue-less layers:
    they become the ``e:none`` signature of the v3 schema."""
    return key if "|e:" in key else key + "|e:none"


def known_winner_methods(direction: str = "fwd") -> frozenset:
    """Winner-method names THIS build can dispatch for ``direction``.

    The forward-compat boundary: a v4 cache written by a newer checkout may
    record winners this build has no kernel for — those records are set
    aside on load (see :func:`_load`) instead of crashing dispatch or being
    clobbered on the next save.
    """
    if direction == "bwd":
        return frozenset(BWD_CANDIDATES)
    if direction == "pair":
        return frozenset(PAIR_CANDIDATES)
    from repro.core import transpose_conv as tc

    return frozenset(
        (set(tc.METHODS) - {"auto"}) | set(PALLAS_CANDIDATES) | {"pallas"}
    )


def _record_is_native(rec) -> bool:
    """True iff every direction's recorded winner is dispatchable here."""
    if not isinstance(rec, dict):
        return False
    for d in _DIRECTIONS:
        e = rec.get(d)
        if (
            isinstance(e, dict)
            and e.get("method") is not None
            and e["method"] not in known_winner_methods(d)
        ):
            return False
    return True


def _partition_native(entries: dict) -> tuple[dict, dict]:
    """Split loaded entries into (native, alien-set-aside)."""
    native, alien = {}, {}
    for k, rec in entries.items():
        (native if _record_is_native(rec) else alien)[k] = rec
    return native, alien


def _load() -> dict:
    """Reload the persistent cache if the file changed since last read.

    Change detection uses (st_mtime_ns, st_size) — mtime alone misses
    rewrites that land within one filesystem timestamp tick.
    """
    path = cache_path()
    if _STATE["path"] != str(path):
        _STATE.update(path=str(path), mtime=-1.0, entries={}, alien={})
        _STATE["generation"] += 1
    try:
        st = path.stat()
        sig = (st.st_mtime_ns, st.st_size)
    except OSError:
        return _STATE["entries"]
    if sig != _STATE["mtime"]:
        try:
            blob = json.loads(path.read_text())
            if not isinstance(blob, dict):
                blob = {}  # valid JSON but not a cache: treat as foreign
            if blob.get("version") in (_CACHE_VERSION, 3):
                # v3 -> v4 is purely additive (the |pair| key form): v3
                # layer keys and records are valid v4 verbatim, they just
                # predate pair entries. The next _save() rewrites as v4.
                loaded = blob.get("entries", {})
            elif blob.get("version") in (1, 2):
                # older schemas migrate in place — none of the tuned data is
                # lost: v1 flat entries become the fwd direction, and
                # v1/v2 keys (which predate epilogue'd signatures) become
                # the e:none signature of v3/v4. The next _save() rewrites
                # the file as v4.
                loaded = {
                    _migrate_key(k): _normalize(dict(e))
                    for k, e in blob.get("entries", {}).items()
                }
            else:  # foreign version: don't pin stale entries as current
                loaded = {}
            # forward compat WITHIN v4: records whose winner method this
            # build can't dispatch (written by a newer checkout) are set
            # aside — never served by lookup(), merged back on save
            _STATE["entries"], _STATE["alien"] = _partition_native(loaded)
            _STATE["generation"] += 1
        except (json.JSONDecodeError, OSError):
            pass  # corrupt/unreadable cache: keep the in-memory view
        _STATE["mtime"] = sig
    return _STATE["entries"]


def _save() -> None:
    path = cache_path()
    path.parent.mkdir(parents=True, exist_ok=True)
    try:  # never clobber a newer tool's cache: set it aside, don't destroy
        prev = json.loads(path.read_text())
        ver = prev.get("version") if isinstance(prev, dict) else None
        if ver is not None and ver not in (1, 2, 3, _CACHE_VERSION):
            path.replace(path.with_name(path.name + f".v{ver}.bak"))
    except (json.JSONDecodeError, OSError):
        pass  # corrupt/missing cache: overwriting it loses nothing
    # alien (newer-build) records ride along untouched; a key this build
    # re-tuned overrides its set-aside version (last write wins, as between
    # concurrent same-version tuners)
    blob = {
        "version": _CACHE_VERSION,
        "entries": {**_STATE["alien"], **_STATE["entries"]},
    }
    fd, tmp = tempfile.mkstemp(dir=str(path.parent), suffix=".tmp")
    try:
        with os.fdopen(fd, "w") as f:
            json.dump(blob, f, indent=1, sort_keys=True)
        os.replace(tmp, path)  # atomic: concurrent tuners last-write-win
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)
    try:
        st = path.stat()
        _STATE["mtime"] = (st.st_mtime_ns, st.st_size)
    except OSError:
        pass


def lookup(key: str) -> dict | None:
    """Full per-direction record for ``key`` (see module docstring)."""
    return _load().get(key)


def record(
    key: str, entry: dict, *, direction: str | None = None,
    persist: bool = True,
) -> None:
    """Store ``entry`` for ``key``.

    ``direction=None`` replaces the whole record (flat entries are treated
    as the ``fwd`` direction for v1 compatibility); ``direction="fwd"``/
    ``"bwd"``/``"step"`` merges that one direction into the existing record.
    """
    _load()
    if direction is None:
        _STATE["entries"][key] = _normalize(entry)
    else:
        if direction not in _DIRECTIONS:
            raise ValueError(f"unknown direction {direction!r}")
        rec = dict(_STATE["entries"].get(key) or {})
        rec[direction] = entry
        _STATE["entries"][key] = rec
    _STATE["generation"] += 1
    if persist:
        _save()


def clear_cache(*, memory_only: bool = False) -> None:
    _STATE.update(mtime=-1.0, entries={}, alien={})
    _STATE["generation"] += 1
    if not memory_only:
        try:
            cache_path().unlink()
        except OSError:
            pass


def prune_cache(*, persist: bool = True) -> list[str]:
    """Drop entries whose layer signature no longer parses under the
    current schema version (cache hygiene: migrations keep *valid* old
    entries, but malformed or hand-edited keys would otherwise ride along
    forever). Returns the dropped keys."""
    entries = _load()
    dropped = [k for k in entries if not _KEY_RE.match(k)]
    if dropped:
        for k in dropped:
            del entries[k]
        _STATE["generation"] += 1
        if persist:
            _save()
    return dropped


def generation() -> int:
    """Monotonic counter that changes whenever the cache content changes.

    ``transpose_conv2d`` threads this through as a static jit argument for
    ``method="auto"``, so tuning *within* a process invalidates previously
    traced dispatch decisions instead of silently keeping the stale winner.
    """
    _load()
    return _STATE["generation"]


def best_entry(
    b: int, n_in: int, n_k: int, cin: int, cout: int, padding: int,
    dtype: str = "float32", *, epilogue=None,
) -> dict | None:
    """Cache-only consult: the full per-direction record, or None."""
    return lookup(
        layer_key(b, n_in, n_k, cin, cout, padding, dtype, epilogue=epilogue)
    )


def best_method(
    b: int, n_in: int, n_k: int, cin: int, cout: int, padding: int,
    dtype: str = "float32", *, epilogue=None,
) -> dict | None:
    """Cache-only consult (no measurement): the ``fwd`` entry or None."""
    rec = best_entry(b, n_in, n_k, cin, cout, padding, dtype,
                     epilogue=epilogue)
    return rec.get("fwd") if rec else None


def best_bwd(
    b: int, n_in: int, n_k: int, cin: int, cout: int, padding: int,
    dtype: str = "float32", *, epilogue=None,
) -> dict | None:
    """Cache-only consult (no measurement): the ``bwd`` entry or None."""
    rec = best_entry(b, n_in, n_k, cin, cout, padding, dtype,
                     epilogue=epilogue)
    return rec.get("bwd") if rec else None


def best_pair(
    b: int, n_in: int, n_k: int, c0: int, c1: int, c2: int, padding: int,
    dtype: str = "float32", *, epilogue1=None, epilogue2=None,
) -> dict | None:
    """Cache-only consult (no measurement): a pair's ``pair`` entry or None.

    This is what the plan fusion pass (``repro.kernels.plan.plan_pair``)
    consults: the pair fuses iff the recorded winner is ``pallas_pair``.
    """
    rec = lookup(pair_key(b, n_in, n_k, c0, c1, c2, padding, dtype,
                          epilogue1=epilogue1, epilogue2=epilogue2))
    return rec.get("pair") if rec else None


# ------------------------------------------------------------------ roofline

def _tile_geometry(
    n_in: int, n_k: int, padding: int,
    tile_h: int | None, tile_w: int | None,
    cin: int, cout: int,
):
    m = seg.output_size(n_in, n_k, padding)
    R = seg.ceil_half(n_k)
    Hp = Wp = (m + 1) // 2
    # tile defaults come from the kernel itself so the model can't drift
    dth, dtw, ct, ci = default_tiles(n_in, n_k, padding, cin, cout)
    th = min(tile_h or dth, Hp)
    tw = min(tile_w or dtw, Wp)
    n_h = -(-Hp // th)
    n_w = -(-Wp // tw)
    return m, R, Hp, Wp, th, tw, n_h, n_w, ct, ci


def epilogue_postop_bytes(b: int, m: int, cout: int) -> int:
    """Extra HBM traffic of running a layer's bias+activation as post-ops:
    one more fused elementwise pass over the fp32 output map (read the
    conv result back + write the activated map) that the in-kernel
    epilogue eliminates."""
    return 2 * b * m * m * cout * 4


def roofline_proxy(
    method: str, b: int, n_in: int, n_k: int, cin: int, cout: int,
    padding: int = 0, *, tile_h: int | None = None, tile_w: int | None = None,
    dtype_bytes: int = 4, epilogue=None, fuse_epilogue: bool = True,
) -> float:
    """Analytic seconds for the forward Pallas grids: max(compute, HBM).

    Models exactly what each grid moves per step: the per-phase kernel
    re-fetches the full ``(Np, Np, ci)`` plane for every ``(phase, cout_tile,
    cin_tile)`` step; the fused kernel fetches one halo'd spatial tile per
    step and serves all four phases from it. An ``epilogue`` adds its
    elementwise FLOPs either way; with ``fuse_epilogue=False`` it also adds
    the post-op output round trip (:func:`epilogue_postop_bytes`) the
    in-kernel epilogue avoids.
    """
    m, R, Hp, Wp, th, tw, n_h, n_w, ct, ci = _tile_geometry(
        n_in, n_k, padding, tile_h, tile_w, cin, cout
    )
    n_co, n_ci = cout // ct, cin // ci
    flops = 2 * b * seg.flop_count(n_in, n_k, cin, cout, padding)
    epi = epilib.canonical(epilogue)
    epi_bytes = 0
    if epi is not None:
        flops += (int(epi.bias) + int(epi.act != "none")) * b * m * m * cout
        if not fuse_epilogue:
            epi_bytes = epilogue_postop_bytes(b, m, cout)
    # fp32 out blocks are written n_ci times and re-read (n_ci - 1) times
    out_rw = (2 * n_ci - 1) * 4
    if method in ("pallas_phase", "pallas-phase"):
        np_ = n_in + n_k  # padded plane extent (upper bound)
        in_b = b * 4 * n_co * n_ci * np_ * np_ * ci * dtype_bytes
        w_b = b * 4 * n_co * n_ci * R * R * ci * ct * dtype_bytes
        out_b = b * 4 * n_co * Hp * Wp * ct * out_rw
    elif method in ("pallas_fused", "pallas-fused"):
        steps = b * n_h * n_w * n_co * n_ci
        in_b = steps * (th + R) * (tw + R) * ci * dtype_bytes
        w_b = steps * 4 * R * R * ci * ct * dtype_bytes
        out_b = b * n_h * n_w * n_co * th * tw * 4 * ct * out_rw
    else:
        raise ValueError(f"no roofline model for method {method!r}")
    bytes_moved = in_b + w_b + out_b + epi_bytes
    return max(flops / PEAK_FLOPS, bytes_moved / PEAK_BW)


def best_fused_proxy(
    b: int, n_in: int, n_k: int, cin: int, cout: int, padding: int = 0,
    *, dtype_bytes: int = 4,
) -> tuple[float, tuple[int, int]]:
    """Best (seconds, (tile_h, tile_w)) over the fused-kernel tile variants."""
    best = None
    for th, tw in _FUSED_TILES:
        t = roofline_proxy(
            "pallas_fused", b, n_in, n_k, cin, cout, padding,
            tile_h=th, tile_w=tw, dtype_bytes=dtype_bytes,
        )
        if best is None or t < best[0]:
            best = (t, (th, tw))
    return best


def _gemm_tile_variants(
    b: int, n_in: int, n_k: int, padding: int, cin: int, cout: int,
) -> tuple:
    """Shape-feasible (tile_m, tile_n, tile_k) variants for the gemm race.

    The kernel's own default leads; the static variant list is clamped to
    the padded row count and snapped to divisors of Cout/Cin (the kernel
    rejects non-dividing channel tiles), then deduped preserving order —
    so the race order is deterministic.
    """
    from repro.kernels.transpose_conv2d_gemm import default_gemm_tiles

    m = seg.output_size(n_in, n_k, padding)
    rows_cap = -(-b * m * m // 8) * 8  # sublane-rounded GEMM rows
    out: list = []
    base = default_gemm_tiles(b, n_in, n_k, padding, cin, cout)
    for tm, tn, tk in (base,) + _GEMM_TILES:
        tm = min(tm, rows_cap)
        tn = tn if cout % tn == 0 else cout
        tk = tk if cin % tk == 0 else cin
        if (tm, tn, tk) not in out:
            out.append((tm, tn, tk))
    return tuple(out)


def gemm_roofline_proxy(
    b: int, n_in: int, n_k: int, cin: int, cout: int, padding: int = 0,
    *, tile_m: int | None = None, tile_n: int | None = None,
    tile_k: int | None = None, dtype_bytes: int = 4, epilogue=None,
    fuse_epilogue: bool = True,
) -> float:
    """Analytic seconds for the implicit-GEMM forward: max(compute, HBM).

    Models the kernel's actual grid ``(n_m, n_co, n_ci * n_tap)``:

    * compute — the dense flat GEMM over the sublane-padded ``B*M*M`` rows
      (no parity skip: ~4x the segregated MACs for even kernels) PLUS the
      one-hot gather matmul that reconstructs each ``(tile_m, tile_k)``
      slab from the resident input plane (``2*tm*S*tk`` MACs per step,
      ``S = B*N*N``) — the price of doing the irregular addressing on the
      MXU;
    * HBM — the input plane once per ``(m, cout, cin)`` block (taps are
      the fast k axis, so consecutive tap steps reuse the resident plane),
      the full dense weight once per m-tile (THE structural win: the
      phase grids re-fetch the weight stack once per batch item, here
      batch folds into the GEMM rows), and the fp32 out blocks under the
      same conservative write+read-back-per-k-step convention the other
      forward models use.
    """
    from repro.kernels.transpose_conv2d_gemm import default_gemm_tiles

    m = seg.output_size(n_in, n_k, padding)
    rows = b * m * m
    dtm, dtn, dtk = default_gemm_tiles(b, n_in, n_k, padding, cin, cout)
    tm = min(tile_m or dtm, -(-rows // 8) * 8)
    tn = tile_n or dtn
    tk = tile_k or dtk
    n_m = -(-rows // tm)
    rows_pad = n_m * tm
    n_co = -(-cout // tn)
    n_ci = -(-cin // tk)
    n_tap = n_k * n_k
    ksteps = n_tap * n_ci
    s_plane = b * n_in * n_in
    flops = 2 * rows_pad * n_tap * cin * cout          # dense flat GEMM
    flops += 2 * rows_pad * n_co * n_tap * s_plane * cin  # one-hot gather
    epi = epilib.canonical(epilogue)
    epi_bytes = 0
    if epi is not None:
        flops += (int(epi.bias) + int(epi.act != "none")) * b * m * m * cout
        if not fuse_epilogue:
            epi_bytes = epilogue_postop_bytes(b, m, cout)
    in_b = n_m * n_co * n_ci * s_plane * tk * dtype_bytes
    w_b = n_m * n_tap * cin * cout * dtype_bytes
    out_b = rows_pad * cout * (2 * ksteps - 1) * 4
    bytes_moved = in_b + w_b + out_b + epi_bytes
    return max(flops / PEAK_FLOPS, bytes_moved / PEAK_BW)


def best_gemm_proxy(
    b: int, n_in: int, n_k: int, cin: int, cout: int, padding: int = 0,
    *, dtype_bytes: int = 4,
) -> tuple[float, tuple[int, int, int]]:
    """Best (seconds, (tile_m, tile_n, tile_k)) over the gemm variants."""
    best = None
    for tm, tn, tk in _gemm_tile_variants(b, n_in, n_k, padding, cin, cout):
        t = gemm_roofline_proxy(
            b, n_in, n_k, cin, cout, padding,
            tile_m=tm, tile_n=tn, tile_k=tk, dtype_bytes=dtype_bytes,
        )
        if best is None or t < best[0]:
            best = (t, (tm, tn, tk))
    return best


def bwd_roofline_proxy(
    method: str, b: int, n_in: int, n_k: int, cin: int, cout: int,
    padding: int = 0, *, tile_h: int | None = None, tile_w: int | None = None,
    dtype_bytes: int = 4, epilogue=None,
) -> float:
    """Analytic seconds for the full backward pass (dx + dw).

    method="pallas": the segregated Pallas backward — the dx grid fetches
    one halo'd tile of the four parity planes per step (serving all four
    correlations), the dw grid fetches the forward's halo'd input tile plus
    the parity-plane tiles and carries the stacked-gradient accumulator
    across the (batch, h_tile) steps. Both accumulators are revisited only
    by *consecutive* grid steps (the reduction axes are innermost), so the
    block stays resident in VMEM and each output block is counted as ONE
    HBM write — unlike the forward model's conservative write+read-back
    convention, which only compares Pallas grids against each other.

    method="lax": the lax VJP of the segregated lax forward — same MACs on
    the dw half, but each phase's conv input-gradient over-computes into the
    ``R - 1`` zero frame (factor ``((Hp + R - 1) / Hp)^2`` on the dx half),
    and XLA materializes per-phase buffers: the parity-plane extraction
    copies of ``g``, four dx-sized partials written then re-read by the
    accumulating adds, per-phase plane and input reads, and the dw
    write/read pair.
    """
    m = seg.output_size(n_in, n_k, padding)
    R = seg.ceil_half(n_k)
    Hp = Wp = (m + 1) // 2
    macs2 = 2 * b * seg.flop_count(n_in, n_k, cin, cout, padding)
    epi = epilib.canonical(epilogue)
    g_plane = b * m * m * cout * 4  # one fp32 pass over the cotangent map
    if method in ("pallas", "pallas_bwd"):
        flops = 2 * macs2  # dx + dw, exact extents
        # dx grid (b, n_h, n_w, cin_tile, cout_tile)
        dth, dtw, dci, dco = default_bwd_tiles(n_in, n_k, padding, cin, cout)
        th = min(tile_h or dth, n_in)
        tw = min(tile_w or dtw, n_in)
        n_h, n_w = -(-n_in // th), -(-n_in // tw)
        n_ci, n_co = cin // dci, cout // dco
        steps = b * n_h * n_w * n_ci * n_co
        dx_in = steps * 4 * (th + R - 1) * (tw + R - 1) * dco * dtype_bytes
        dx_w = steps * 4 * R * R * dco * dci * dtype_bytes
        # resident accumulator: one fp32 write per (b, i, j, cin) out block
        dx_out = b * n_h * n_w * n_ci * th * tw * dci * 4
        # dw grid (cin_tile, cout_tile, batch, h_tile)
        thw = default_dw_tile(n_in, n_k, padding)
        ci_w, co_w = min(cin, 512), min(cout, 128)
        n_hw = -(-Hp // thw)
        stepsw = (cin // ci_w) * (cout // co_w) * b * n_hw
        dw_in = stepsw * (
            (thw + R) * (Wp + R) * ci_w + 4 * thw * Wp * co_w
        ) * dtype_bytes
        # resident accumulator: one fp32 write per (cin, cout) stack block
        dw_out = (cin // ci_w) * (cout // co_w) * 4 * R * R * ci_w * co_w * 4
        bytes_moved = dx_in + dx_w + dx_out + dw_in + dw_out
        if epi is not None and epi.saves_output:
            # fused gm = g * act'(y) prologue: read g + y, write gm once;
            # db rides in the dw accumulator for free
            bytes_moved += 3 * g_plane
    elif method == "lax":
        over = ((Hp + R - 1) / Hp) ** 2  # conv input-grad zero-frame waste
        flops = (1 + over) * macs2
        g_b = b * m * m * cout * 4
        plane_b = b * Hp * Wp * cout * 4
        x_b = b * n_in * n_in * cin * dtype_bytes
        dx_b = b * n_in * n_in * cin * 4
        dw_b = 4 * R * R * cin * cout * 4  # stacked extent, like the kernel
        bytes_moved = (
            2 * g_b            # parity-plane extraction copies
            + 4 * 2 * plane_b  # each phase's plane read twice (dx + dw pass)
            + 4 * 2 * dx_b     # four dx partials written + re-read to add
            + 4 * x_b          # dw re-reads the padded input per phase
            + dw_b             # per-phase sub-kernel reads (dx pass)
            + 2 * dw_b         # dw write + read-back
        )
        if epi is not None and epi.saves_output:
            # unfused epilogue grad: the act' mask is materialized (read y,
            # write mask, re-read with g, write gm) + the separate db pass
            bytes_moved += 5 * g_plane
        elif epi is not None and epi.bias:
            bytes_moved += g_plane  # separate db reduction re-reads g
    else:
        raise ValueError(f"no backward roofline model for method {method!r}")
    return max(flops / PEAK_FLOPS, bytes_moved / PEAK_BW)


def best_bwd_proxy(
    b: int, n_in: int, n_k: int, cin: int, cout: int, padding: int = 0,
    *, dtype_bytes: int = 4,
) -> tuple[float, tuple[int, int]]:
    """Best (seconds, (tile_h, tile_w)) over the dx-kernel tile variants."""
    best = None
    for th, tw in _BWD_TILES:
        t = bwd_roofline_proxy(
            "pallas", b, n_in, n_k, cin, cout, padding,
            tile_h=th, tile_w=tw, dtype_bytes=dtype_bytes,
        )
        if best is None or t < best[0]:
            best = (t, (th, tw))
    return best


def _pair_tile_variants(c0: int, c1: int, c2: int) -> tuple:
    """Shape-feasible (cin, mid, cout) channel-tile variants for the pair
    race: the kernel's own default leads, the static list is snapped to
    dividing tiles (the kernel rejects non-dividing channel tiles), deduped
    preserving order."""
    from repro.kernels.transpose_conv2d_pair import _snap, default_pair_tiles

    out = [default_pair_tiles(c0, c1, c2)]
    for tci, tmid, tco in _PAIR_TILES:
        v = (_snap(c0, tci), _snap(c1, tmid), _snap(c2, tco))
        if v not in out:
            out.append(v)
    return tuple(out)


def pair_roofline_proxy(
    b: int, n_in: int, n_k: int, c0: int, c1: int, c2: int,
    padding: int = 0, *, tile_ci: int | None = None,
    tile_mid: int | None = None, tile_co: int | None = None,
    dtype_bytes: int = 4, epilogue1=None, epilogue2=None,
) -> float:
    """Analytic seconds for the fused-pair kernel: max(compute, HBM).

    Models the pair grid ``(b, n_co, n_mid, n_ci)`` exactly: the input
    plane block is re-fetched only when its ``ci`` index changes (resident
    across the mid sweep when ``n_ci == 1``), ``w1`` blocks stream once per
    step, ``w2`` blocks once per ``(b, co, mid)`` step, and the output
    block — revisited only by consecutive steps (the reduction axes are
    innermost) — stays VMEM-resident and is written to HBM ONCE per
    ``(b, co)``. The interface activation contributes **zero** HBM bytes
    (it lives in the VMEM scratch accumulator); the price is the producer
    re-running once per consumer ``cout`` tile (the ``n_co`` compute
    factor — 1 at the default tiles for every zoo pair).
    """
    from repro.kernels.transpose_conv2d_pair import (
        default_pair_tiles, pair_geometry,
    )

    g = pair_geometry(n_in, n_k, padding)
    dci, dmid, dco = default_pair_tiles(c0, c1, c2)
    tci = tile_ci or dci
    tmid = tile_mid or dmid
    tco = tile_co or dco
    n_ci, n_mid, n_co = c0 // tci, c1 // tmid, c2 // tco
    R, np1 = g["R"], g["np1"]
    hp1, hp2 = g["hp1"], g["hp2"]
    m1, m2 = g["m1"], g["m2"]
    # producer re-runs per consumer cout tile; consumer extents are exact
    flops = 8 * b * hp1 * hp1 * c0 * c1 * n_co
    flops += 8 * b * hp2 * hp2 * c1 * c2
    epi1 = epilib.canonical(epilogue1)
    epi2 = epilib.canonical(epilogue2)
    if epi1 is not None:
        flops += ((int(epi1.bias) + int(epi1.act != "none"))
                  * b * m1 * m1 * c1 * n_co)
    if epi2 is not None:
        flops += (int(epi2.bias) + int(epi2.act != "none")) * b * m2 * m2 * c2
    x_fetches = b * (n_co * n_mid * n_ci if n_ci > 1 else 1)
    in_b = x_fetches * np1 * np1 * tci * dtype_bytes
    w1_b = b * n_co * n_mid * n_ci * 4 * R * R * tci * tmid * dtype_bytes
    w2_b = b * n_co * n_mid * 4 * R * R * tmid * tco * dtype_bytes
    out_b = b * n_co * (2 * hp2) * (2 * hp2) * tco * 4
    bytes_moved = in_b + w1_b + w2_b + out_b
    return max(flops / PEAK_FLOPS, bytes_moved / PEAK_BW)


def back_to_back_proxy(
    b: int, n_in: int, n_k: int, c0: int, c1: int, c2: int,
    padding: int = 0, *, dtype_bytes: int = 4,
    epilogue1=None, epilogue2=None,
) -> float:
    """Analytic seconds for the unfused reference: two back-to-back
    ``pallas_fused`` launches, each at its proxy-best tiles, the second
    consuming the first's fp32 output plane from HBM (the round trip the
    pair kernel eliminates)."""
    m1 = seg.output_size(n_in, n_k, padding)
    _, (th1, tw1) = best_fused_proxy(
        b, n_in, n_k, c0, c1, padding, dtype_bytes=dtype_bytes
    )
    t1 = roofline_proxy(
        "pallas_fused", b, n_in, n_k, c0, c1, padding,
        tile_h=th1, tile_w=tw1, dtype_bytes=dtype_bytes, epilogue=epilogue1,
    )
    _, (th2, tw2) = best_fused_proxy(b, m1, n_k, c1, c2, padding)
    t2 = roofline_proxy(
        "pallas_fused", b, m1, n_k, c1, c2, padding,
        tile_h=th2, tile_w=tw2, epilogue=epilogue2,
    )
    return t1 + t2


def best_pair_proxy(
    b: int, n_in: int, n_k: int, c0: int, c1: int, c2: int,
    padding: int = 0, *, dtype_bytes: int = 4,
    epilogue1=None, epilogue2=None,
) -> tuple[float, tuple[int, int, int]]:
    """Best (seconds, (tile_ci, tile_mid, tile_co)) over the pair variants."""
    best = None
    for tci, tmid, tco in _pair_tile_variants(c0, c1, c2):
        t = pair_roofline_proxy(
            b, n_in, n_k, c0, c1, c2, padding,
            tile_ci=tci, tile_mid=tmid, tile_co=tco,
            dtype_bytes=dtype_bytes, epilogue1=epilogue1,
            epilogue2=epilogue2,
        )
        if best is None or t < best[0]:
            best = (t, (tci, tmid, tco))
    return best


# ------------------------------------------------------------------- tuning

# lax-based candidates always race on wall clock
LAX_CANDIDATES = (
    "conventional", "unified_reshape", "unified_matmul", "unified_fused",
)
PALLAS_CANDIDATES = ("pallas_fused", "pallas_phase", "pallas_gemm")
DEFAULT_CANDIDATES = LAX_CANDIDATES + PALLAS_CANDIDATES
BWD_CANDIDATES = ("lax", "pallas")
# the schema-v4 pair race: one megafused launch vs two fused launches
PAIR_CANDIDATES = ("pallas_pair", "back_to_back")


def _layer_fn(padding, method, epi):
    """Whole-layer callable ``act(tconv(x, k) + b)`` for one lax method —
    the epilogue is composed (XLA fuses elementwise tails), so every
    candidate races the SAME full layer the dispatch will execute."""
    from repro.core import transpose_conv as tc

    def fn(x, k, bvec=None):
        y = tc.transpose_conv2d(x, k, padding, method=method)
        return epi.apply(y, bvec) if epi is not None else y

    return fn


def _tune_fwd(
    x, k, bvec, padding, lax_methods, pallas_methods, include_pallas,
    repeats, warmup, epi,
):
    from repro.kernels.transpose_conv2d import (
        transpose_conv2d_pallas, transpose_conv2d_pallas_phase,
    )
    from repro.kernels.transpose_conv2d_gemm import (
        gemm_vmem_bytes, transpose_conv2d_pallas_gemm,
    )

    b, n_in, _, cin = x.shape
    n_k, cout = k.shape[0], k.shape[3]
    args = (x, k) if epi is None or not epi.bias else (x, k, bvec)
    candidates: dict[str, float] = {}
    for name in lax_methods:
        fn = jax.jit(_layer_fn(padding, name, epi))
        candidates[name] = _time_fn(fn, *args, repeats=repeats, warmup=warmup)

    itemsize = jnp.dtype(x.dtype).itemsize
    fused_s, (tile_h, tile_w) = best_fused_proxy(
        b, n_in, n_k, cin, cout, padding, dtype_bytes=itemsize
    )
    _, gemm_tiles = best_gemm_proxy(
        b, n_in, n_k, cin, cout, padding, dtype_bytes=itemsize
    )
    proxy = {
        "pallas_fused": roofline_proxy(
            "pallas_fused", b, n_in, n_k, cin, cout, padding,
            tile_h=tile_h, tile_w=tile_w, dtype_bytes=itemsize,
            epilogue=epi,
        ),
        "pallas_phase": roofline_proxy(
            "pallas_phase", b, n_in, n_k, cin, cout, padding,
            dtype_bytes=itemsize, epilogue=epi,
        ),
        "pallas_gemm": gemm_roofline_proxy(
            b, n_in, n_k, cin, cout, padding,
            tile_m=gemm_tiles[0], tile_n=gemm_tiles[1],
            tile_k=gemm_tiles[2], dtype_bytes=itemsize, epilogue=epi,
        ),
    }
    if epi is not None:
        # the unfused variant pays the post-op output round trip
        proxy["pallas_fused+postops"] = roofline_proxy(
            "pallas_fused", b, n_in, n_k, cin, cout, padding,
            tile_h=tile_h, tile_w=tile_w, dtype_bytes=itemsize,
            epilogue=epi, fuse_epilogue=False,
        )
    fuse_epi = True
    if include_pallas:
        for name in pallas_methods:
            if name == "pallas_fused":
                # race the tile variants for real, not just by proxy
                times = {}
                for th, tw in _FUSED_TILES:
                    times[(th, tw)] = _time_fn(
                        jax.jit(
                            lambda *a, _th=th, _tw=tw:
                            transpose_conv2d_pallas(
                                a[0], a[1], padding, tile_h=_th, tile_w=_tw,
                                epilogue=epi,
                                bias=a[2] if len(a) > 2 else None,
                            )
                        ),
                        *args, repeats=repeats, warmup=warmup,
                    )
                (tile_h, tile_w), best = min(
                    times.items(), key=lambda kv: kv[1]
                )
                candidates[name] = best
                if epi is not None:
                    # fused-epilogue vs unfused: the bare kernel at the
                    # winning tiles + composed post-ops
                    def unfused(x, k, bvec=None, _th=tile_h, _tw=tile_w):
                        y = transpose_conv2d_pallas(
                            x, k, padding, tile_h=_th, tile_w=_tw
                        )
                        return epi.apply(y, bvec)

                    candidates["pallas_fused+postops"] = _time_fn(
                        jax.jit(unfused), *args,
                        repeats=repeats, warmup=warmup,
                    )
            elif name == "pallas_gemm":
                # race the feasible (tile_m, tile_n, tile_k) variants
                times = {}
                for tmv, tnv, tkv in _gemm_tile_variants(
                    b, n_in, n_k, padding, cin, cout
                ):
                    if not fits_vmem(gemm_vmem_bytes(
                        b, n_in, n_k, padding, cin, cout, itemsize,
                        tiles=(tmv, tnv, tkv),
                    )):
                        continue
                    times[(tmv, tnv, tkv)] = _time_fn(
                        jax.jit(
                            lambda *a, _tm=tmv, _tn=tnv, _tk=tkv:
                            transpose_conv2d_pallas_gemm(
                                a[0], a[1], padding, tile_m=_tm,
                                tile_n=_tn, tile_k=_tk, epilogue=epi,
                                bias=a[2] if len(a) > 2 else None,
                            )
                        ),
                        *args, repeats=repeats, warmup=warmup,
                    )
                gemm_tiles, best = min(times.items(), key=lambda kv: kv[1])
                candidates[name] = best
            else:
                candidates[name] = _time_fn(
                    jax.jit(
                        lambda *a: transpose_conv2d_pallas_phase(
                            a[0], a[1], padding, epilogue=epi,
                            bias=a[2] if len(a) > 2 else None,
                        )
                    ),
                    *args, repeats=repeats, warmup=warmup,
                )

    winner = min(candidates, key=candidates.get)
    if winner == "pallas_fused+postops":
        winner_method, fuse_epi = "pallas_fused", False
    else:
        winner_method = winner
    entry = {
        "method": winner_method,
        "time_s": candidates[winner],
        "source": "measured",
        "candidates": candidates,
        "proxy": proxy,
    }
    if winner_method == "pallas_fused":
        entry["tile_h"], entry["tile_w"] = tile_h, tile_w
        if epi is not None:
            entry["fuse_epilogue"] = fuse_epi
    elif winner_method == "pallas_gemm":
        entry["tile_m"], entry["tile_n"], entry["tile_k"] = gemm_tiles
    return entry, (tile_h, tile_w), gemm_tiles


def _tune_bwd(x, k, bvec, padding, include_pallas, repeats, warmup, epi):
    from repro.kernels import ops
    from repro.kernels.transpose_conv2d_bwd import transpose_conv2d_bwd_pallas

    b, n_in, _, cin = x.shape
    n_k, cout = k.shape[0], k.shape[3]
    m = seg.output_size(n_in, n_k, padding)
    rng = np.random.default_rng(1)
    g = jnp.asarray(rng.normal(size=(b, m, m, cout)), dtype=jnp.float32)
    # epilogue'd backwards consume the saved forward output y
    y = None
    if epi is not None and epi.saves_output:
        y = jax.block_until_ready(
            _layer_fn(padding, "unified_reshape", epi)(x, k, bvec)
        )

    candidates: dict[str, float] = {
        # the cached jitted closure repro.kernels.ops dispatches to (the
        # lax VJP composes the identical epilogue backward: gm from y, db)
        "lax": _time_fn(
            lambda x, k, g: ops._lax_bwd(padding, (x, k, y, bvec), g, epi),
            x, k, g, repeats=repeats, warmup=warmup,
        )
    }
    itemsize = jnp.dtype(x.dtype).itemsize
    pallas_s, (tile_h, tile_w) = best_bwd_proxy(
        b, n_in, n_k, cin, cout, padding, dtype_bytes=itemsize
    )
    proxy = {
        "pallas": bwd_roofline_proxy(
            "pallas", b, n_in, n_k, cin, cout, padding,
            tile_h=tile_h, tile_w=tile_w, dtype_bytes=itemsize, epilogue=epi,
        ),
        "lax": bwd_roofline_proxy(
            "lax", b, n_in, n_k, cin, cout, padding, dtype_bytes=itemsize,
            epilogue=epi,
        ),
    }
    if include_pallas:
        times = {}
        for th, tw in _BWD_TILES:
            times[(th, tw)] = _time_fn(
                lambda x, k, g, _th=th, _tw=tw: transpose_conv2d_bwd_pallas(
                    x, k, g, padding, tile_h=_th, tile_w=_tw,
                    epilogue=epi, y=y,
                ),
                x, k, g, repeats=repeats, warmup=warmup,
            )
        (tile_h, tile_w), best = min(times.items(), key=lambda kv: kv[1])
        candidates["pallas"] = best
        if epi is not None:
            # fused prologue + in-launch db vs the unfused variant: act'
            # masking and the db reduction as separate passes
            def unfused(x, k, g, _th=tile_h, _tw=tile_w):
                gm = g if y is None else epi.grad_from_y(g, y)
                out = transpose_conv2d_bwd_pallas(
                    x, k, gm, padding, tile_h=_th, tile_w=_tw
                )
                if epi.bias:
                    out = out + (gm.sum((0, 1, 2)),)
                return out

            candidates["pallas+postops"] = _time_fn(
                unfused, x, k, g, repeats=repeats, warmup=warmup,
            )

    # dispatch implements the fused prologue only: the winner is picked
    # among implementable candidates; "pallas+postops" stays in the record
    # as the measured unfused reference
    dispatchable = {
        n: t for n, t in candidates.items() if n in BWD_CANDIDATES
    }
    winner = min(dispatchable, key=dispatchable.get)
    entry = {
        "method": winner,
        "time_s": dispatchable[winner],
        "source": "measured",
        "candidates": candidates,
        "proxy": proxy,
    }
    if winner == "pallas":
        entry["tile_h"], entry["tile_w"] = tile_h, tile_w
    return entry


def _tune_step(
    x, k, bvec, padding, lax_methods, pallas_methods, include_pallas,
    repeats, warmup, fwd_tiles, gemm_tiles, epi,
):
    """Race the full fwd+bwd value_and_grad per forward method.

    The Pallas forwards differentiate through ``repro.kernels.ops`` with
    ``bwd="auto"``, i.e. whatever the just-recorded ``bwd`` entry selects —
    the joint tuning the training dispatch relies on. ``pallas_fused`` runs
    at the forward race's winning tiles, the exact configuration the entry
    records and train-mode dispatch will replay. Epilogue'd layers race the
    whole ``act(tconv + b)`` unit — gradients include ``db`` — and the
    fused-epilogue Pallas step races its unfused kernel-plus-post-ops
    variant (``pallas_fused+postops``, whose backward materializes the
    act' mask through plain AD instead of the fused prologue).
    """
    from repro.kernels import ops

    methods = tuple(lax_methods)
    if include_pallas:
        methods += tuple(pallas_methods)
        if epi is not None and "pallas_fused" in methods:
            methods += ("pallas_fused+postops",)
    with_bias = epi is not None and epi.bias
    args = (x, k, bvec) if with_bias else (x, k)
    argnums = (0, 1, 2) if with_bias else (0, 1)
    candidates: dict[str, float] = {}
    for name in methods:
        if name == "pallas_fused":
            th, tw = fwd_tiles

            def loss(*a, _th=th, _tw=tw):
                return ops.transpose_conv2d_pallas(
                    a[0], a[1], padding, _th, _tw, "auto", epi,
                    a[2] if len(a) > 2 else None,
                ).sum()
        elif name == "pallas_fused+postops":
            th, tw = fwd_tiles

            def loss(*a, _th=th, _tw=tw):
                y = ops.transpose_conv2d_pallas(
                    a[0], a[1], padding, _th, _tw, "auto"
                )
                return epi.apply(y, a[2] if len(a) > 2 else None).sum()
        elif name == "pallas_gemm":
            tmv, tnv, tkv = gemm_tiles

            def loss(*a, _tm=tmv, _tn=tnv, _tk=tkv):
                return ops.transpose_conv2d_pallas_gemm(
                    a[0], a[1], padding, _tm, _tn, _tk, "auto", epi,
                    a[2] if len(a) > 2 else None,
                ).sum()
        else:
            def loss(*a, _m=name):
                return _layer_fn(padding, _m, epi)(*a).sum()

        fn = jax.jit(jax.value_and_grad(loss, argnums=argnums))
        candidates[name] = _time_fn(fn, *args, repeats=repeats, warmup=warmup)

    winner = min(candidates, key=candidates.get)
    fuse_epi = True
    winner_method = winner
    if winner == "pallas_fused+postops":
        winner_method, fuse_epi = "pallas_fused", False
    entry = {
        "method": winner_method,
        "time_s": candidates[winner],
        "source": "measured",
        "candidates": candidates,
    }
    if winner_method == "pallas_fused":
        entry["tile_h"], entry["tile_w"] = fwd_tiles
        if epi is not None:
            entry["fuse_epilogue"] = fuse_epi
    elif winner_method == "pallas_gemm":
        entry["tile_m"], entry["tile_n"], entry["tile_k"] = gemm_tiles
    return entry


def tune_layer(
    b: int, n_in: int, n_k: int, cin: int, cout: int, padding: int = 0,
    *, dtype=jnp.float32, methods: tuple | None = None,
    repeats: int = 3, warmup: int = 1, persist: bool = True,
    include_pallas: bool | None = None, train: bool = False,
    epilogue=None,
) -> dict:
    """Measure candidates for one layer shape, record + return the record.

    ``methods`` filters the forward candidate set (default: every lax method
    plus both Pallas kernels). include_pallas=None (auto): Pallas kernels
    race on wall clock only on a real accelerator backend; on CPU they run
    in interpret mode (wall clock would measure the Python interpreter, not
    the operator), so there they are reported via the roofline proxy and
    never become the winner.

    ``train=True`` tunes the whole training step: the ``bwd`` direction
    (segregated Pallas backward vs the lax VJP — what ``ops``'s custom VJP
    dispatches to) and the ``step`` direction (full value_and_grad per
    forward method — what ``method="auto", train=True`` dispatches to).
    Returns the full per-direction record.

    ``epilogue`` (an :class:`~repro.kernels.epilogue.Epilogue`) makes the
    whole ``act(tconv + b)`` layer the tuned unit — its own cache
    signature (schema v3): every candidate runs the full layer, and the
    Pallas kernels additionally race their fused-epilogue variant against
    the unfused kernel-plus-post-ops spelling in every direction.
    """
    backend = jax.default_backend()
    epilogue = epilib.canonical(epilogue)
    if include_pallas is None:
        # the Pallas kernels are TPU-lowered (TPU compiler params, Element
        # indexing); everywhere else they only run interpreted
        include_pallas = backend == "tpu"
    from repro.kernels.plan import fwd_fits_vmem

    methods = tuple(methods or DEFAULT_CANDIDATES)
    lax_methods = tuple(m for m in methods if m not in PALLAS_CANDIDATES)
    # the whole-plane kernels race only where they fit VMEM
    pallas_methods = tuple(
        m for m in methods if m in PALLAS_CANDIDATES
        and fwd_fits_vmem(m, b, n_in, n_k, cin, cout, padding,
                          str(jnp.dtype(dtype)))
    )
    if not lax_methods and not include_pallas:
        raise ValueError(
            f"nothing to wall-clock: methods={methods} names only Pallas "
            f"kernels, which backend={backend!r} runs in interpret mode "
            "(pass include_pallas=True to force, or add a lax method)"
        )

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(b, n_in, n_in, cin)), dtype=dtype)
    k = jnp.asarray(
        rng.normal(size=(n_k, n_k, cin, cout)) * 0.05, dtype=dtype
    )
    bvec = None
    if epilogue is not None and epilogue.bias:
        bvec = jnp.asarray(rng.normal(size=(cout,)) * 0.1, dtype=dtype)

    key = layer_key(
        b, n_in, n_k, cin, cout, padding, str(jnp.dtype(dtype)), backend,
        epilogue=epilogue,
    )
    fwd_entry, fwd_tiles, gemm_tiles = _tune_fwd(
        x, k, bvec, padding, lax_methods, pallas_methods, include_pallas,
        repeats, warmup, epilogue,
    )
    # one disk write per tune_layer: intermediate directions stay in memory
    record(key, fwd_entry, direction="fwd", persist=persist and not train)
    obs_audit.get_trail().record_decision(
        kind="layer", key=key, direction="fwd", entry=fwd_entry,
        backend=backend, persist=persist and not train,
    )
    if not train:
        return lookup(key)

    # bwd before step: the step race differentiates the Pallas forwards
    # through bwd="auto", which consults the entry recorded here
    bwd_entry = _tune_bwd(
        x, k, bvec, padding, include_pallas, repeats, warmup, epilogue
    )
    record(key, bwd_entry, direction="bwd", persist=False)
    obs_audit.get_trail().record_decision(
        kind="layer", key=key, direction="bwd", entry=bwd_entry,
        backend=backend, persist=False,
    )
    step_entry = _tune_step(
        x, k, bvec, padding, lax_methods, pallas_methods, include_pallas,
        repeats, warmup, fwd_tiles, gemm_tiles, epilogue,
    )
    record(key, step_entry, direction="step", persist=persist)
    obs_audit.get_trail().record_decision(
        kind="layer", key=key, direction="step", entry=step_entry,
        backend=backend, persist=persist,
    )
    return lookup(key)


def tune_pair(
    b: int, n_in: int, n_k: int, c0: int, c1: int, c2: int,
    padding: int = 0, *, dtype=jnp.float32, methods: tuple | None = None,
    repeats: int = 3, warmup: int = 1, persist: bool = True,
    include_pallas: bool | None = None, epilogue1=None, epilogue2=None,
) -> dict:
    """Race the fused-pair kernel vs back-to-back launches for one pair.

    Records (and returns) the ``pair`` entry under the pair's schema-v4
    key. On a real accelerator both candidates race on wall clock — the
    pair kernel over its channel-tile variants, back-to-back as two
    ``pallas_fused`` launches at their proxy-best tiles. On CPU *neither*
    candidate is wall-clockable (both are Pallas kernels, interpret-mode
    only), so the record is written from the roofline proxies with
    ``source="proxy"`` and — by the same convention as the layer
    directions — the conservative ``back_to_back`` winner: interpret-mode
    fusion never wins dispatch, while both proxies stay in the record for
    the benchmark gate.
    """
    backend = jax.default_backend()
    epi1 = epilib.canonical(epilogue1)
    epi2 = epilib.canonical(epilogue2)
    if include_pallas is None:
        include_pallas = backend == "tpu"
    methods = tuple(methods or PAIR_CANDIDATES)
    unknown = sorted(set(methods) - set(PAIR_CANDIDATES))
    if unknown:
        raise ValueError(
            f"unknown pair method(s) {unknown}; valid: {PAIR_CANDIDATES}"
        )
    itemsize = jnp.dtype(dtype).itemsize
    pair_s, pair_tiles = best_pair_proxy(
        b, n_in, n_k, c0, c1, c2, padding, dtype_bytes=itemsize,
        epilogue1=epi1, epilogue2=epi2,
    )
    proxy = {
        "pallas_pair": pair_s,
        "back_to_back": back_to_back_proxy(
            b, n_in, n_k, c0, c1, c2, padding, dtype_bytes=itemsize,
            epilogue1=epi1, epilogue2=epi2,
        ),
    }
    key = pair_key(b, n_in, n_k, c0, c1, c2, padding,
                   str(jnp.dtype(dtype)), backend,
                   epilogue1=epi1, epilogue2=epi2)
    if not include_pallas:
        entry = {
            "method": "back_to_back",
            "time_s": proxy["back_to_back"],
            "source": "proxy",
            "candidates": {},
            "proxy": proxy,
        }
        record(key, entry, direction="pair", persist=persist)
        obs_audit.get_trail().record_decision(
            kind="pair", key=key, direction="pair", entry=entry,
            backend=backend, persist=persist,
        )
        return lookup(key)

    from repro.kernels.transpose_conv2d import transpose_conv2d_pallas
    from repro.kernels.transpose_conv2d_pair import (
        pair_vmem_bytes, transpose_conv2d_pair_pallas,
    )

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(b, n_in, n_in, c0)), dtype=dtype)
    k1 = jnp.asarray(rng.normal(size=(n_k, n_k, c0, c1)) * 0.05, dtype=dtype)
    k2 = jnp.asarray(rng.normal(size=(n_k, n_k, c1, c2)) * 0.05, dtype=dtype)
    b1 = b2 = None
    if epi1 is not None and epi1.bias:
        b1 = jnp.asarray(rng.normal(size=(c1,)) * 0.1, dtype=jnp.float32)
    if epi2 is not None and epi2.bias:
        b2 = jnp.asarray(rng.normal(size=(c2,)) * 0.1, dtype=jnp.float32)

    candidates: dict[str, float] = {}
    tiles = pair_tiles
    if "pallas_pair" in methods:
        times = {}
        for tci, tmid, tco in _pair_tile_variants(c0, c1, c2):
            if not fits_vmem(pair_vmem_bytes(
                n_in, n_k, c0, c1, c2, padding, itemsize,
                tiles=(tci, tmid, tco),
            )):
                continue
            times[(tci, tmid, tco)] = _time_fn(
                jax.jit(
                    lambda x, k1, k2, _t=(tci, tmid, tco):
                    transpose_conv2d_pair_pallas(
                        x, k1, k2, padding,
                        cin_tile=_t[0], mid_tile=_t[1], cout_tile=_t[2],
                        epilogue1=epi1, bias1=b1,
                        epilogue2=epi2, bias2=b2,
                    )
                ),
                x, k1, k2, repeats=repeats, warmup=warmup,
            )
        tiles, best = min(times.items(), key=lambda kv: kv[1])
        candidates["pallas_pair"] = best
    if "back_to_back" in methods:
        m1 = seg.output_size(n_in, n_k, padding)
        _, (th1, tw1) = best_fused_proxy(
            b, n_in, n_k, c0, c1, padding, dtype_bytes=itemsize
        )
        _, (th2, tw2) = best_fused_proxy(b, m1, n_k, c1, c2, padding)

        def b2b(x, k1, k2):
            y1 = transpose_conv2d_pallas(
                x, k1, padding, tile_h=th1, tile_w=tw1,
                epilogue=epi1, bias=b1,
            )
            return transpose_conv2d_pallas(
                y1, k2, padding, tile_h=th2, tile_w=tw2,
                epilogue=epi2, bias=b2,
            )

        candidates["back_to_back"] = _time_fn(
            jax.jit(b2b), x, k1, k2, repeats=repeats, warmup=warmup,
        )

    winner = min(candidates, key=candidates.get)
    entry = {
        "method": winner,
        "time_s": candidates[winner],
        "source": "measured",
        "candidates": {str(k): v for k, v in candidates.items()},
        "proxy": proxy,
    }
    if winner == "pallas_pair":
        entry["tile_ci"], entry["tile_mid"], entry["tile_co"] = tiles
    record(key, entry, direction="pair", persist=persist)
    obs_audit.get_trail().record_decision(
        kind="pair", key=key, direction="pair", entry=entry,
        backend=backend, persist=persist,
    )
    return lookup(key)


def tune_gan_zoo(
    *, batch: int = 1, repeats: int = 3, persist: bool = True,
    train: bool = False, epilogues: bool = True, pairs: bool = True,
    methods: tuple | None = None, include_pallas: bool | None = None,
) -> dict[str, dict]:
    """Tune every distinct Table-4 GAN layer shape; returns {key: record}.

    ``epilogues=True`` (default) tunes the signatures the generators
    actually dispatch: each layer fused with its bias+activation tail
    (relu mid-stack, tanh on the output layer —
    :func:`repro.models.gan.generator_epilogues`). ``epilogues=False``
    tunes the bare transpose-conv signatures (the pre-v3 behaviour).

    ``pairs=True`` (default, requires ``epilogues``) additionally runs the
    schema-v4 pair race on every fusion-eligible adjacent pair — the same
    greedy left-to-right pairing and VMEM-budget screen the plan pass
    (``repro.kernels.plan.fuse_pairs``) applies, so a zoo sweep warms
    exactly the keys :func:`best_pair` will consult.
    """
    from repro.kernels import transpose_conv2d_pair as pairlib
    from repro.models.gan import GAN_ZOO, generator_epilogues

    out = {}
    seen = set()
    for cfg in GAN_ZOO.values():
        epis = (
            generator_epilogues(cfg) if epilogues
            else (None,) * len(cfg.layers)
        )
        for (hw, cin, cout), epi in zip(cfg.layers, epis):
            sig = (batch, hw, cfg.kernel, cin, cout, cfg.padding)
            if (sig, epi) in seen:
                continue
            seen.add((sig, epi))
            entry = tune_layer(*sig, repeats=repeats, persist=persist,
                               train=train, epilogue=epi, methods=methods,
                               include_pallas=include_pallas)
            out[layer_key(*sig, epilogue=epi)] = entry
        if not (pairs and epilogues):
            continue
        # greedy left-to-right adjacent pairing, like fuse_pairs
        i = 0
        while i + 1 < len(cfg.layers):
            (hw1, c0, c1), (hw2, c1b, c2) = cfg.layers[i], cfg.layers[i + 1]
            legal = (
                c1b == c1
                and hw2 == seg.output_size(hw1, cfg.kernel, cfg.padding)
                and fits_vmem(pairlib.pair_vmem_bytes(
                    hw1, cfg.kernel, c0, c1, c2, cfg.padding
                ))
            )
            if not legal:
                i += 1
                continue
            sig = (batch, hw1, cfg.kernel, c0, c1, c2, cfg.padding)
            psig = (sig, epis[i], epis[i + 1])
            if psig not in seen:
                seen.add(psig)
                entry = tune_pair(*sig, repeats=repeats, persist=persist,
                                  include_pallas=include_pallas,
                                  epilogue1=epis[i], epilogue2=epis[i + 1])
                out[pair_key(*sig, epilogue1=epis[i],
                             epilogue2=epis[i + 1])] = entry
            i += 2
    return out


def main(argv=None):
    """CLI: populate (or clean) the persistent cache.

    PYTHONPATH=src python -m repro.kernels.autotune --gan-zoo
    PYTHONPATH=src python -m repro.kernels.autotune --gan-zoo --train
    PYTHONPATH=src python -m repro.kernels.autotune --layer 1 8 4 512 256 2
    PYTHONPATH=src python -m repro.kernels.autotune --layer 8 4 4 1024 512 2 \\
        --methods pallas_gemm,pallas_fused --include-pallas
    PYTHONPATH=src python -m repro.kernels.autotune --pair 1 8 4 512 256 128 2
    PYTHONPATH=src python -m repro.kernels.autotune --prune
    """
    import argparse

    ap = argparse.ArgumentParser(description=main.__doc__)
    g = ap.add_mutually_exclusive_group(required=True)
    g.add_argument("--gan-zoo", action="store_true",
                   help="tune every distinct Table-4 GAN layer shape "
                        "(fused with the generator epilogues by default) "
                        "plus the pair race on fusion-eligible adjacent "
                        "pairs")
    g.add_argument("--layer", nargs=6, type=int,
                   metavar=("B", "N", "K", "CIN", "COUT", "PAD"))
    g.add_argument("--pair", nargs=7, type=int,
                   metavar=("B", "N", "K", "CIN", "CMID", "COUT", "PAD"),
                   help="race the fused-pair kernel vs back-to-back "
                        "launches for one adjacent layer pair (relu-bias "
                        "interface + tanh-bias output epilogues unless "
                        "--no-epilogue)")
    g.add_argument("--prune", action="store_true",
                   help="drop cache entries whose layer signature no "
                        "longer parses under the current schema version")
    ap.add_argument("--train", action="store_true",
                    help="also tune the bwd + full-train-step directions")
    ap.add_argument("--no-epilogue", action="store_true",
                    help="tune bare transpose-conv signatures (no fused "
                         "bias+activation epilogues)")
    ap.add_argument("--methods",
                    help="comma-separated forward-candidate filter (race "
                         "or debug a single candidate in isolation), e.g. "
                         "--methods pallas_gemm,pallas_fused")
    ap.add_argument("--include-pallas", action="store_true",
                    help="force wall-clock racing of the Pallas kernels "
                         "even off-TPU (interpret mode is Python-speed: "
                         "debugging only, not predictive of TPU)")
    ap.add_argument("--repeats", type=int, default=3)
    args = ap.parse_args(argv)

    methods = None
    pair_methods = None
    if args.methods:
        methods = tuple(
            s.strip() for s in args.methods.split(",") if s.strip()
        )
        valid = DEFAULT_CANDIDATES + PAIR_CANDIDATES
        unknown = sorted(set(methods) - set(valid))
        if unknown:
            ap.error(
                f"unknown method(s): {', '.join(unknown)}; "
                f"valid: {', '.join(valid)}"
            )
        pair_methods = tuple(m for m in methods if m in PAIR_CANDIDATES)
        methods = tuple(m for m in methods if m in DEFAULT_CANDIDATES)
        methods = methods or None
        pair_methods = pair_methods or None
    include_pallas = True if args.include_pallas else None

    if args.prune:
        dropped = prune_cache()
        print(f"# cache: {cache_path()}")
        for k in dropped:
            print(f"pruned {k}")
        print(f"# pruned {len(dropped)} unparsable "
              f"entr{'y' if len(dropped) == 1 else 'ies'} "
              f"(schema v{_CACHE_VERSION})")
        return

    if args.gan_zoo:
        entries = tune_gan_zoo(repeats=args.repeats, train=args.train,
                               epilogues=not args.no_epilogue,
                               methods=methods,
                               include_pallas=include_pallas)
    elif args.pair:
        epi1 = epi2 = None
        if not args.no_epilogue:
            epi1 = epilib.make(True, "relu")
            epi2 = epilib.make(True, "tanh")
        entry = tune_pair(*args.pair, repeats=args.repeats,
                          methods=pair_methods,
                          include_pallas=include_pallas,
                          epilogue1=epi1, epilogue2=epi2)
        entries = {
            pair_key(*args.pair, epilogue1=epi1, epilogue2=epi2): entry
        }
    else:
        entry = tune_layer(*args.layer, repeats=args.repeats,
                           train=args.train, methods=methods,
                           include_pallas=include_pallas)
        entries = {layer_key(*args.layer): entry}
    print(f"# cache: {cache_path()}")
    for key, rec in entries.items():
        parts = []
        for d in _DIRECTIONS:
            e = rec.get(d)
            if not e:
                continue
            extra = (f"[{e['tile_h']}x{e['tile_w']}]"
                     if "tile_h" in e else "")
            if "tile_m" in e:
                extra = f"[{e['tile_m']}x{e['tile_n']}x{e['tile_k']}]"
            if "tile_ci" in e:
                extra = f"[{e['tile_ci']}x{e['tile_mid']}x{e['tile_co']}]"
            parts.append(f"{d}={e['method']}{extra} {e['time_s']:.6f}s")
        print(f"{key} -> " + "  ".join(parts))


if __name__ == "__main__":
    main()
