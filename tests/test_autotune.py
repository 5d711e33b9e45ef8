"""Autotuner: persistent cache semantics, measured selection, tuned dispatch."""
import json

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import transpose_conv as tc
from repro.kernels import autotune, ref


@pytest.fixture(autouse=True)
def tmp_cache(tmp_path, monkeypatch):
    """Every test gets its own persistent cache file."""
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path / "autotune.json"))
    autotune.clear_cache(memory_only=True)
    yield
    autotune.clear_cache(memory_only=True)


def test_cache_roundtrip_persists_to_disk():
    key = autotune.layer_key(1, 8, 4, 16, 8, 2)
    assert key.endswith("|e:none")  # v3 keys carry the epilogue signature
    # flat (v1-style) entries are accepted and become the fwd direction
    autotune.record(key, {"method": "unified_reshape", "time_s": 1e-4,
                          "source": "measured"})
    # wipe the in-memory view; lookup must reload from the JSON file
    autotune._STATE.update(mtime=-1.0, entries={})
    entry = autotune.lookup(key)
    assert entry is not None and entry["fwd"]["method"] == "unified_reshape"
    assert autotune.best_method(1, 8, 4, 16, 8, 2)["method"] == "unified_reshape"
    blob = json.loads(autotune.cache_path().read_text())
    assert blob["version"] == 4 and key in blob["entries"]


def test_v1_cache_file_migrates_on_load():
    """Existing $REPRO_AUTOTUNE_CACHE files from the forward-only schema
    keep answering for the fwd direction; bwd/step stay cold; the next save
    rewrites the file as the current schema (keys gain the e:none epilogue
    component)."""
    v1key = "cpu|b1|n8|k4|ci16|co8|p2|float32"  # pre-epilogue key spelling
    autotune.cache_path().parent.mkdir(parents=True, exist_ok=True)
    autotune.cache_path().write_text(json.dumps({
        "version": 1,
        "entries": {v1key: {"method": "unified_matmul", "time_s": 2e-4,
                            "source": "measured"}},
    }))
    assert autotune.best_method(1, 8, 4, 16, 8, 2)["method"] == "unified_matmul"
    assert autotune.best_bwd(1, 8, 4, 16, 8, 2) is None
    # recording any direction persists the migrated record
    key = autotune.layer_key(1, 8, 4, 16, 8, 2)
    autotune.record(key, {"method": "lax", "time_s": 1e-4,
                          "source": "measured"}, direction="bwd")
    blob = json.loads(autotune.cache_path().read_text())
    assert blob["version"] == 4
    assert blob["entries"][key]["fwd"]["method"] == "unified_matmul"
    assert blob["entries"][key]["bwd"]["method"] == "lax"


def test_v2_cache_file_migrates_forward_keeping_tiles():
    """v2 caches (per-direction records, no epilogue key component) load,
    answer for the e:none signature WITH their tuned tiles intact, and are
    rewritten as the current schema on the next save."""
    v2key = "cpu|b1|n8|k4|ci16|co8|p2|float32"
    autotune.cache_path().parent.mkdir(parents=True, exist_ok=True)
    autotune.cache_path().write_text(json.dumps({
        "version": 2,
        "entries": {v2key: {
            "fwd": {"method": "pallas_fused", "time_s": 2e-4,
                    "source": "measured", "tile_h": 16, "tile_w": 128},
            "bwd": {"method": "pallas", "time_s": 1e-4,
                    "source": "measured", "tile_h": 8, "tile_w": 64},
        }},
    }))
    hit = autotune.best_method(1, 8, 4, 16, 8, 2)
    assert hit["method"] == "pallas_fused"
    assert (hit["tile_h"], hit["tile_w"]) == (16, 128)
    bwd = autotune.best_bwd(1, 8, 4, 16, 8, 2)
    assert bwd["method"] == "pallas" and bwd["tile_h"] == 8
    # any write re-saves the migrated view without losing the tiles
    autotune.record(autotune.layer_key(9, 9, 9, 9, 9, 9),
                    {"method": "conventional", "time_s": 1.0, "source": "t"})
    blob = json.loads(autotune.cache_path().read_text())
    assert blob["version"] == 4
    migrated = blob["entries"][autotune.layer_key(1, 8, 4, 16, 8, 2)]
    assert migrated["fwd"]["tile_h"] == 16
    assert migrated["bwd"]["tile_w"] == 64


def test_layer_key_includes_epilogue_signature():
    from repro.kernels.epilogue import Epilogue

    k_none = autotune.layer_key(1, 8, 4, 16, 8, 2)
    k_relu = autotune.layer_key(
        1, 8, 4, 16, 8, 2, epilogue=Epilogue(bias=True, act="relu")
    )
    k_tanh = autotune.layer_key(
        1, 8, 4, 16, 8, 2, epilogue=Epilogue(bias=True, act="tanh")
    )
    assert len({k_none, k_relu, k_tanh}) == 3
    assert k_relu.endswith("|e:b+relu") and k_tanh.endswith("|e:b+tanh")
    # identity epilogues normalize to the bare signature
    assert autotune.layer_key(1, 8, 4, 16, 8, 2,
                              epilogue=Epilogue()) == k_none


def test_prune_drops_unparsable_keys_only():
    good = autotune.layer_key(1, 8, 4, 16, 8, 2)
    autotune.record(good, {"method": "unified_reshape", "time_s": 1e-4,
                           "source": "measured"})
    autotune.record("totally|not|a|layer", {"method": "x", "time_s": 0.0,
                                            "source": "t"})
    dropped = autotune.prune_cache()
    assert dropped == ["totally|not|a|layer"]
    assert autotune.lookup(good) is not None
    assert autotune.lookup("totally|not|a|layer") is None
    blob = json.loads(autotune.cache_path().read_text())
    assert "totally|not|a|layer" not in blob["entries"]
    assert autotune.prune_cache() == []  # idempotent


def test_layer_key_includes_backend_and_dtype():
    k1 = autotune.layer_key(1, 8, 4, 16, 8, 2, "float32", backend="cpu")
    k2 = autotune.layer_key(1, 8, 4, 16, 8, 2, "bfloat16", backend="cpu")
    k3 = autotune.layer_key(1, 8, 4, 16, 8, 2, "float32", backend="tpu")
    assert len({k1, k2, k3}) == 3


def test_tune_layer_records_measured_winner():
    rec = autotune.tune_layer(1, 6, 4, 4, 4, 2, repeats=2, warmup=1)
    entry = rec["fwd"]
    assert entry["method"] in entry["candidates"]
    assert entry["time_s"] == min(entry["candidates"].values()) > 0
    # on CPU the Pallas kernels compete via the roofline proxy only — the
    # whole zoo, including the implicit-GEMM forward
    assert set(entry["proxy"]) == {
        "pallas_fused", "pallas_phase", "pallas_gemm"
    }
    # forward-only tuning leaves the training directions cold
    assert "bwd" not in rec and "step" not in rec
    # and the cache now answers for this exact shape
    hit = autotune.best_method(1, 6, 4, 4, 4, 2)
    assert hit is not None and hit["method"] == entry["method"]


def test_tune_layer_train_records_bwd_and_step():
    """train=True tunes the whole training step: the bwd direction (Pallas
    backward vs lax VJP) and the full value_and_grad race per fwd method."""
    rec = autotune.tune_layer(1, 6, 4, 4, 4, 2, repeats=2, warmup=1,
                              train=True)
    bwd = rec["bwd"]
    # on CPU the Pallas backward competes via the roofline proxy only
    assert bwd["method"] == "lax" and set(bwd["proxy"]) == {"pallas", "lax"}
    assert bwd["time_s"] == min(bwd["candidates"].values()) > 0
    step = rec["step"]
    assert step["method"] in step["candidates"]
    assert step["time_s"] == min(step["candidates"].values()) > 0
    # the cache answers per direction
    assert autotune.best_bwd(1, 6, 4, 4, 4, 2)["method"] == "lax"
    assert autotune.best_entry(1, 6, 4, 4, 4, 2)["step"] == step


def test_train_dispatch_prefers_step_winner(monkeypatch):
    """method='auto', train=True dispatches to the jointly-tuned step
    winner even when the forward-only winner differs."""
    key = autotune.layer_key(1, 6, 4, 2, 3, 2)
    autotune.record(key, {
        "fwd": {"method": "conventional", "time_s": 1e-4, "source": "test"},
        "step": {"method": "unified_matmul", "time_s": 2e-4,
                 "source": "test"},
    })
    calls = []
    orig = tc.METHODS["unified_matmul"]

    def spy(*a, **kw):
        calls.append(1)
        return orig(*a, **kw)

    monkeypatch.setitem(tc.METHODS, "unified_matmul", spy)
    x = jnp.asarray(np.random.default_rng(0).normal(size=(1, 6, 6, 2)),
                    dtype=jnp.float32)
    k = jnp.asarray(np.random.default_rng(1).normal(size=(4, 4, 2, 3)),
                    dtype=jnp.float32)
    want = ref.conventional_ref(x, k, 2)
    got = tc.transpose_conv2d(x, k, 2, method="auto", train=True)
    assert calls, "train dispatch must pick the step winner"
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
    # inference dispatch still follows the fwd winner
    calls.clear()
    tc.transpose_conv2d(x, k, 2, method="auto")
    assert not calls


def test_auto_dispatch_consults_cache(monkeypatch):
    calls = []
    orig = autotune.best_entry

    def spy(*a, **kw):
        calls.append(a)
        return orig(*a, **kw)

    monkeypatch.setattr(autotune, "best_entry", spy)
    x = jnp.asarray(np.random.default_rng(0).normal(size=(1, 6, 6, 2)),
                    dtype=jnp.float32)
    k = jnp.asarray(np.random.default_rng(1).normal(size=(4, 4, 2, 3)),
                    dtype=jnp.float32)
    want = ref.conventional_ref(x, k, 2)
    got = tc.transpose_conv_auto(x, k, 2)  # cold cache -> napkin fallback
    assert calls, "transpose_conv_auto must consult the autotuner cache"
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("method", [
    "conventional", "unified_matmul", "pallas_fused", "pallas_phase",
])
def test_auto_dispatch_follows_cached_winner(method):
    x = jnp.asarray(np.random.default_rng(0).normal(size=(1, 6, 6, 2)),
                    dtype=jnp.float32)
    k = jnp.asarray(np.random.default_rng(1).normal(size=(4, 4, 2, 3)),
                    dtype=jnp.float32)
    key = autotune.layer_key(1, 6, 4, 2, 3, 2)
    entry = {"method": method, "time_s": 0.0, "source": "test"}
    if method == "pallas_fused":  # tuned tiles must reach the kernel
        entry.update(tile_h=2, tile_w=3)
    autotune.record(key, entry)
    want = ref.conventional_ref(x, k, 2)
    got = tc.transpose_conv_auto(x, k, 2)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_tune_layer_pallas_only_on_cpu_raises_clearly():
    """On CPU nothing in a Pallas-only candidate set can be wall-clocked —
    that must be a clear error, not min() over an empty dict."""
    with pytest.raises(ValueError, match="interpret mode"):
        autotune.tune_layer(1, 6, 4, 4, 4, 2, methods=("pallas_fused",))


def test_foreign_cache_version_resets_in_memory_view():
    key = autotune.layer_key(1, 8, 4, 16, 8, 2)
    autotune.record(key, {"method": "unified_reshape", "time_s": 1e-4,
                          "source": "measured"})
    # a newer tool rewrites the file with an unknown version
    autotune.cache_path().write_text(json.dumps({"version": 99, "entries": {
        key: {"method": "conventional"}
    }}))
    assert autotune.lookup(key) is None  # stale view must not be pinned


def test_foreign_cache_version_is_preserved_on_save():
    """Saving over a newer tool's cache must set it aside, not destroy it."""
    key = autotune.layer_key(1, 8, 4, 16, 8, 2)
    foreign = {"version": 99, "entries": {key: {"method": "conventional"}}}
    autotune.cache_path().parent.mkdir(parents=True, exist_ok=True)
    autotune.cache_path().write_text(json.dumps(foreign))
    autotune.record(key, {"method": "unified_reshape", "time_s": 1e-4,
                          "source": "measured"})
    blob = json.loads(autotune.cache_path().read_text())
    assert blob["version"] == 4
    bak = autotune.cache_path().with_name(
        autotune.cache_path().name + ".v99.bak"
    )
    assert json.loads(bak.read_text()) == foreign


def test_step_race_measures_pallas_fused_at_recorded_tiles(monkeypatch):
    """The step race must time pallas_fused at the SAME tiles the entry
    records (the fwd race's winner) — otherwise train-mode dispatch replays
    a configuration whose value_and_grad time was never measured."""
    from repro.kernels import ops

    seen = []
    orig = ops.transpose_conv2d_pallas

    def spy(x, k, padding=0, tile_h=None, tile_w=None, bwd="auto",
            epilogue=None, bias=None):
        seen.append((tile_h, tile_w))
        return orig(x, k, padding, tile_h, tile_w, bwd, epilogue, bias)

    monkeypatch.setattr(ops, "transpose_conv2d_pallas", spy)
    rec = autotune.tune_layer(
        1, 6, 4, 2, 2, 2, repeats=1, warmup=0, include_pallas=True,
        methods=("unified_reshape", "pallas_fused"), train=True,
    )
    step = rec["step"]
    assert "pallas_fused" in step["candidates"]
    # the step race must pin concrete raced tiles (the fwd winner), never
    # fall through to kernel defaults via (None, None)
    assert seen and all(t in autotune._FUSED_TILES for t in seen), seen
    if step["method"] == "pallas_fused":
        assert (step["tile_h"], step["tile_w"]) in seen


def test_in_process_retuning_invalidates_auto_trace(monkeypatch):
    """record() bumps the cache generation, which is part of the jit key for
    method='auto' — new winners take effect without a process restart."""
    calls = []
    orig = autotune.best_entry

    def spy(*a, **kw):
        calls.append(a)
        return orig(*a, **kw)

    monkeypatch.setattr(autotune, "best_entry", spy)
    x = jnp.asarray(np.random.default_rng(0).normal(size=(1, 6, 6, 2)),
                    dtype=jnp.float32)
    k = jnp.asarray(np.random.default_rng(1).normal(size=(4, 4, 2, 3)),
                    dtype=jnp.float32)
    want = ref.conventional_ref(x, k, 2)

    tc.transpose_conv2d(x, k, 2, method="auto")
    n1 = len(calls)
    assert n1 >= 1
    tc.transpose_conv2d(x, k, 2, method="auto")  # same generation: cached
    assert len(calls) == n1
    autotune.record(
        autotune.layer_key(1, 6, 4, 2, 3, 2),
        {"method": "unified_matmul", "time_s": 0.0, "source": "test"},
    )
    got = tc.transpose_conv2d(x, k, 2, method="auto")  # bumped: retraces
    assert len(calls) > n1
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_corrupt_cache_degrades_to_fallback():
    autotune.cache_path().parent.mkdir(parents=True, exist_ok=True)
    autotune.cache_path().write_text("{not json")
    assert autotune.best_method(1, 6, 4, 2, 3, 2) is None
    x = jnp.ones((1, 6, 6, 2), jnp.float32)
    k = jnp.ones((4, 4, 2, 3), jnp.float32)
    want = ref.conventional_ref(x, k, 2)
    np.testing.assert_allclose(
        tc.transpose_conv_auto(x, k, 2), want, rtol=1e-4, atol=1e-4
    )


def test_roofline_fused_beats_phase_on_gan_layers():
    """The fused grid moves ~4x less input traffic: the proxy must prefer it
    on every Table-4 GAN layer shape."""
    from repro.models.gan import GAN_ZOO

    for cfg in GAN_ZOO.values():
        for hw, cin, cout in cfg.layers:
            fused, _tiles = autotune.best_fused_proxy(
                1, hw, cfg.kernel, cin, cout, cfg.padding
            )
            phase = autotune.roofline_proxy(
                "pallas_phase", 1, hw, cfg.kernel, cin, cout, cfg.padding
            )
            assert fused <= phase, (cfg.name, hw, cin, cout, fused, phase)


def test_gemm_winner_recorded_and_dispatched(monkeypatch):
    """When the implicit-GEMM kernel wins the forward race, the entry must
    record its (tile_m, tile_n, tile_k) and method='auto' must execute the
    GEMM kernel at those tiles — the full tune -> cache -> dispatch loop."""
    from repro.kernels import ops

    # deterministic race: _time_fn answers from a call-order queue. Order in
    # _tune_fwd: the lax methods first, then the gemm tile variants — for
    # this shape every variant snaps to the same feasible tiling, so the
    # queue is [unified_reshape, gemm].
    times = iter([1.0, 1e-4])
    monkeypatch.setattr(
        autotune, "_time_fn", lambda fn, *a, **kw: next(times)
    )
    rec = autotune.tune_layer(
        1, 4, 4, 32, 16, 2, include_pallas=True,
        methods=("unified_reshape", "pallas_gemm"),
    )
    entry = rec["fwd"]
    assert entry["method"] == "pallas_gemm"
    assert entry["candidates"]["pallas_gemm"] == 1e-4
    tiles = (entry["tile_m"], entry["tile_n"], entry["tile_k"])
    assert tiles == (64, 16, 32)  # rows=1*8*8 cap, cout, cin

    seen = []
    orig = ops.transpose_conv2d_pallas_gemm

    def spy(x, k, padding=0, tile_m=None, tile_n=None, tile_k=None,
            bwd="auto", epilogue=None, bias=None):
        seen.append((tile_m, tile_n, tile_k))
        return orig(x, k, padding, tile_m, tile_n, tile_k, bwd,
                    epilogue, bias)

    monkeypatch.setattr(ops, "transpose_conv2d_pallas_gemm", spy)
    x = jnp.asarray(np.random.default_rng(0).normal(size=(1, 4, 4, 32)),
                    dtype=jnp.float32)
    k = jnp.asarray(np.random.default_rng(1).normal(size=(4, 4, 32, 16)),
                    dtype=jnp.float32)
    want = ref.conventional_ref(x, k, 2)
    got = tc.transpose_conv2d(x, k, 2, method="auto")
    assert seen == [tiles], "auto must dispatch the tuned gemm tiles"
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_unknown_winner_method_set_aside_not_clobbered():
    """Forward compat: a v3 cache written by a newer build may record winner
    methods this build can't dispatch. Those records must be excluded from
    lookup (cold-cache behavior, no crash) yet survive a re-save verbatim —
    never silently dropped or treated as dispatchable."""
    alien_key = autotune.layer_key(1, 4, 4, 8, 8, 2)
    good_key = autotune.layer_key(1, 8, 4, 16, 8, 2)
    alien_rec = {"fwd": {"method": "pallas_hyperwarp", "time_s": 1e-9,
                         "source": "measured", "warp_factor": 9}}
    autotune.cache_path().parent.mkdir(parents=True, exist_ok=True)
    autotune.cache_path().write_text(json.dumps({
        "version": 3,
        "entries": {
            alien_key: alien_rec,
            good_key: {"fwd": {"method": "unified_reshape", "time_s": 1e-4,
                               "source": "measured"}},
        },
    }))
    # the alien record answers as a cache miss, the native one as a hit
    assert autotune.lookup(alien_key) is None
    assert autotune.best_method(1, 4, 4, 8, 8, 2) is None
    assert autotune.best_method(1, 8, 4, 16, 8, 2)["method"] == \
        "unified_reshape"
    # dispatch on the alien shape degrades to the cold-cache napkin rule
    x = jnp.ones((1, 4, 4, 8), jnp.float32)
    k = jnp.ones((4, 4, 8, 8), jnp.float32)
    np.testing.assert_allclose(
        tc.transpose_conv_auto(x, k, 2), ref.conventional_ref(x, k, 2),
        rtol=1e-4, atol=1e-4,
    )
    # a re-save merges the set-aside record back untouched
    autotune.record(good_key, {"method": "conventional", "time_s": 2e-4,
                               "source": "measured"})
    blob = json.loads(autotune.cache_path().read_text())
    assert blob["entries"][alien_key] == alien_rec
    assert blob["entries"][good_key]["fwd"]["method"] == "conventional"


def test_retuned_key_overrides_alien_record():
    """Re-tuning a shape whose record went alien replaces it on save: the
    native result wins the merge (last-writer semantics, same as two
    concurrent tuners)."""
    key = autotune.layer_key(1, 8, 4, 16, 8, 2)
    autotune.cache_path().parent.mkdir(parents=True, exist_ok=True)
    autotune.cache_path().write_text(json.dumps({
        "version": 3,
        "entries": {key: {"fwd": {"method": "pallas_hyperwarp",
                                  "time_s": 1e-9, "source": "measured"}}},
    }))
    assert autotune.lookup(key) is None
    autotune.record(key, {"method": "unified_reshape", "time_s": 1e-4,
                          "source": "measured"})
    assert autotune.best_method(1, 8, 4, 16, 8, 2)["method"] == \
        "unified_reshape"
    blob = json.loads(autotune.cache_path().read_text())
    assert blob["entries"][key]["fwd"]["method"] == "unified_reshape"


def test_cli_methods_filter_rejects_unknown_names(capsys):
    """--methods with a typo'd candidate must fail fast, naming the valid
    set, instead of silently racing an empty/partial field."""
    with pytest.raises(SystemExit) as exc:
        autotune.main(["--layer", "1", "4", "4", "2", "2", "2",
                       "--methods", "unified_reshape,pallas_warp"])
    assert exc.value.code == 2
    err = capsys.readouterr().err
    assert "pallas_warp" in err
    for valid in autotune.DEFAULT_CANDIDATES:
        assert valid in err


def test_cli_methods_filter_accepts_known_names(capsys):
    autotune.main(["--layer", "1", "4", "4", "2", "2", "2",
                   "--methods", "unified_reshape,conventional",
                   "--repeats", "1"])
    out = capsys.readouterr().out
    assert "fwd=" in out
    entry = autotune.best_method(1, 4, 4, 2, 2, 2)
    assert entry["method"] in ("unified_reshape", "conventional")
    assert set(entry["candidates"]) == {"unified_reshape", "conventional"}


def test_bwd_roofline_pallas_beats_lax_on_gan_layers():
    """The segregated Pallas backward reads tiles once for all four phases
    and keeps its accumulators VMEM-resident; the lax VJP re-materializes
    per-phase buffers and over-computes the conv input-grad zero frame. The
    proxy must prefer the Pallas backward on every Table-4 layer shape —
    the bench's bwd_pallas >= bwd_lax gate."""
    from repro.models.gan import GAN_ZOO

    for cfg in GAN_ZOO.values():
        for hw, cin, cout in cfg.layers:
            pallas, _tiles = autotune.best_bwd_proxy(
                1, hw, cfg.kernel, cin, cout, cfg.padding
            )
            lax_s = autotune.bwd_roofline_proxy(
                "lax", 1, hw, cfg.kernel, cin, cout, cfg.padding
            )
            assert pallas <= lax_s, (cfg.name, hw, cin, cout, pallas, lax_s)


# ------------------------------------------------- pair direction (schema v4)

def _mk_epis():
    from repro.kernels.epilogue import Epilogue

    return Epilogue(bias=True, act="relu"), Epilogue(bias=True, act="tanh")


def test_pair_key_format_and_roundtrip():
    e1, e2 = _mk_epis()
    key = autotune.pair_key(1, 4, 4, 8, 6, 4, 2, epilogue1=e1, epilogue2=e2)
    assert "|pair|" in key
    assert key.endswith("|e1:b+relu|e2:b+tanh")
    assert "ci8" in key and "mid6" in key and "co4" in key
    autotune.record(key, {"method": "pallas_pair", "time_s": 1e-6,
                          "source": "measured", "tile_ci": 8, "tile_mid": 6,
                          "tile_co": 4}, direction="pair")
    autotune._STATE.update(mtime=-1.0, entries={})  # force disk reload
    rec = autotune.best_pair(1, 4, 4, 8, 6, 4, 2, epilogue1=e1, epilogue2=e2)
    assert rec["method"] == "pallas_pair" and rec["tile_ci"] == 8
    blob = json.loads(autotune.cache_path().read_text())
    assert blob["version"] == 4 and key in blob["entries"]


def test_prune_keeps_pair_keys():
    e1, e2 = _mk_epis()
    key = autotune.pair_key(1, 4, 4, 8, 6, 4, 2, epilogue1=e1, epilogue2=e2)
    autotune.record(key, {"method": "back_to_back", "time_s": 1e-6,
                          "source": "proxy"}, direction="pair")
    assert autotune.prune_cache() == []
    assert autotune.lookup(key) is not None


def test_v3_cache_loads_as_passthrough_and_rewrites_v4():
    """v3 -> v4 is purely additive: layer entries are untouched, the file is
    simply rewritten as v4 on the next save."""
    key = autotune.layer_key(1, 8, 4, 16, 8, 2)
    autotune.cache_path().parent.mkdir(parents=True, exist_ok=True)
    autotune.cache_path().write_text(json.dumps({
        "version": 3,
        "entries": {key: {"fwd": {"method": "unified_reshape",
                                  "time_s": 1e-4, "source": "measured"}}},
    }))
    assert autotune.best_method(1, 8, 4, 16, 8, 2)["method"] == \
        "unified_reshape"
    autotune.record(autotune.layer_key(9, 9, 9, 9, 9, 9),
                    {"method": "conventional", "time_s": 1.0, "source": "t"})
    blob = json.loads(autotune.cache_path().read_text())
    assert blob["version"] == 4
    assert blob["entries"][key]["fwd"]["method"] == "unified_reshape"


def test_alien_pair_winner_set_aside():
    """A pair record whose winner this build doesn't know (a newer build's
    kernel) answers as a cache miss and survives re-save verbatim — the
    same forward-compat contract as layer records."""
    e1, e2 = _mk_epis()
    key = autotune.pair_key(1, 4, 4, 8, 6, 4, 2, epilogue1=e1, epilogue2=e2)
    alien = {"pair": {"method": "pallas_trio", "time_s": 1e-9,
                      "source": "measured"}}
    autotune.cache_path().parent.mkdir(parents=True, exist_ok=True)
    autotune.cache_path().write_text(json.dumps(
        {"version": 4, "entries": {key: alien}}
    ))
    assert autotune.lookup(key) is None
    assert autotune.best_pair(1, 4, 4, 8, 6, 4, 2,
                              epilogue1=e1, epilogue2=e2) is None
    autotune.record(autotune.layer_key(9, 9, 9, 9, 9, 9),
                    {"method": "conventional", "time_s": 1.0, "source": "t"})
    blob = json.loads(autotune.cache_path().read_text())
    assert blob["entries"][key] == alien


def test_tune_pair_cpu_records_back_to_back_proxy():
    """On CPU neither pair candidate is wall-clockable (both are Pallas
    kernels), so tune_pair records the back_to_back winner from the
    roofline proxies — interpret-mode fusion must never win dispatch."""
    e1, e2 = _mk_epis()
    rec = autotune.tune_pair(
        1, 4, 4, 8, 6, 4, 2, epilogue1=e1, epilogue2=e2
    )["pair"]
    assert rec["method"] == "back_to_back"
    assert rec["source"] == "proxy"
    assert set(rec["proxy"]) == {"pallas_pair", "back_to_back"}
    hit = autotune.best_pair(1, 4, 4, 8, 6, 4, 2, epilogue1=e1, epilogue2=e2)
    assert hit["method"] == "back_to_back"


def test_pair_roofline_geomean_beats_back_to_back_on_zoo():
    """The analytic models must prefer the fused pair kernel in pooled
    geomean across the zoo's eligible pairs — the bench's
    layer_pair_fusion >= 1.2x gate, pinned here shape by shape."""
    import math

    from repro.kernels.mosaic import fits_vmem
    from repro.kernels.transpose_conv2d_pair import pair_vmem_bytes
    from repro.models.gan import GAN_ZOO, generator_epilogues

    ratios = []
    for cfg in GAN_ZOO.values():
        epis = generator_epilogues(cfg)
        i = 0
        while i + 1 < len(cfg.layers):
            (hw, c0, c1), (_, _, c2) = cfg.layers[i], cfg.layers[i + 1]
            if not fits_vmem(pair_vmem_bytes(hw, cfg.kernel, c0, c1, c2,
                                             cfg.padding)):
                i += 1
                continue
            pair_s, _ = autotune.best_pair_proxy(
                8, hw, cfg.kernel, c0, c1, c2, cfg.padding,
                epilogue1=epis[i], epilogue2=epis[i + 1],
            )
            b2b_s = autotune.back_to_back_proxy(
                8, hw, cfg.kernel, c0, c1, c2, cfg.padding,
                epilogue1=epis[i], epilogue2=epis[i + 1],
            )
            ratios.append(b2b_s / pair_s)
            i += 2
    assert len(ratios) == 8  # greedy pairing over the zoo, EB-GAN tail out
    geomean = math.exp(sum(math.log(r) for r in ratios) / len(ratios))
    assert geomean >= 1.2, (geomean, ratios)


def test_cli_methods_accepts_pair_candidates(capsys):
    with pytest.raises(SystemExit) as exc:
        autotune.main(["--pair", "1", "4", "4", "8", "6", "4", "2",
                       "--methods", "pallas_pair,back_to_warp"])
    assert exc.value.code == 2
    err = capsys.readouterr().err
    assert "back_to_warp" in err
    for valid in autotune.PAIR_CANDIDATES:
        assert valid in err


def test_cli_pair_smoke(capsys):
    autotune.main(["--pair", "1", "4", "4", "8", "6", "4", "2",
                   "--repeats", "1"])
    out = capsys.readouterr().out
    assert "pair=" in out
    e1, e2 = _mk_epis()
    assert autotune.best_pair(
        1, 4, 4, 8, 6, 4, 2, epilogue1=e1, epilogue2=e2
    ) is not None
