"""The program's own spans reach a real profiler trace on the CPU, as the
benchmark's reduction reads it, with the program's tracer off: the
profiler is their second sink and needs no ``obs.enable()``."""
import benchpath  # noqa: F401
import jax
import numpy as np
import pytest

from harness import system, trace

system.import_program()

from repro.models import gan  # noqa: E402
from repro.obs import trace as obs  # noqa: E402

TINY = gan.GANConfig("tiny", 8, ((4, 4, 4), (8, 4, 3)))
SERVE_SPANS = ("serve.schedule", "serve.pack", "serve.dispatch",
               "serve.readback", "serve.slice")
TRAIN_SPANS = ("train.step", "train.batch", "train.step_fn", "train.sync")


@pytest.fixture(autouse=True)
def hermetic(monkeypatch, tmp_path):
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path / "at.json"))


@pytest.fixture
def isolated(request):
    """An isolated tracer, on or off as the test's parameter says (off
    without one); the global tracer and flag are restored afterwards."""
    tracer = obs.Tracer()
    prev, was = obs.set_tracer(tracer), obs.enabled()
    (obs.enable if getattr(request, "param", False) else obs.disable)()
    yield tracer
    obs.set_tracer(prev)
    (obs.enable if was else obs.disable)()


def traced(fn) -> list:
    """Names of the host events on the window's thread, in order."""
    with trace.profiled() as got:
        with jax.profiler.TraceAnnotation(trace.WINDOW):
            fn()
    return [n for n, _, _ in trace._main_thread(got["trace"])]


def serve_tiny():
    from repro.serve import BucketPolicy, GanEngine, GenRequest

    params = gan.generator_init(jax.random.key(0), TINY)
    eng = GanEngine(BucketPolicy(buckets=(1, 2, 4), max_wait_s=0.0))
    eng.register(TINY, params, name="tiny")
    eng.warmup()
    rng = np.random.default_rng(0)
    reqs = [GenRequest("tiny", rng.standard_normal((n, TINY.z_dim),
                                                   np.float32))
            for n in (1, 2, 1)]
    return lambda: eng.serve(reqs)


@pytest.mark.parametrize("isolated", [False, True], indirect=True,
                         ids=["obs_off", "obs_on"])
def test_engine_spans_reach_the_profiler_trace(isolated):
    run = serve_tiny()
    names = traced(run)
    for name in SERVE_SPANS:
        assert name in names, (name, names)
    # the copy is inside the dispatch, the dispatch after its schedule
    i = names.index("serve.dispatch")
    assert names.index("serve.schedule") < i < names.index("serve.readback")
    recorded = isolated.span_names()
    if obs.enabled():
        assert all(recorded.get(n, 0) >= 1 for n in SERVE_SPANS), recorded
    else:
        assert not recorded


def test_trainer_spans_reach_the_profiler_trace(isolated):
    from repro.data import SyntheticImages
    from repro.train.gan_trainer import GanTrainer, GanTrainerConfig

    data = SyntheticImages(hw=TINY.out_hw(TINY.layers[-1][0]),
                           channels=TINY.layers[-1][2], global_batch=2)
    tr = GanTrainer(TINY, GanTrainerConfig(global_batch=2), data,
                    log_fn=lambda *a: None)
    state = tr.init_state(jax.random.key(0))
    state, _ = tr.run(state, steps=1)       # compiles outside the trace
    tr.resume = lambda s: (1, s)
    names = traced(lambda: tr.run(state, steps=3))
    for name in TRAIN_SPANS:
        assert names.count(name) == 2, (name, names)
    assert not isolated.spans


def test_span_is_the_no_op_again_once_the_profiler_stops(isolated):
    assert obs.span("x") is obs.NOOP_SPAN
    with trace.profiled():
        with jax.profiler.TraceAnnotation(trace.WINDOW):
            sp = obs.span("x", a=1)
            assert sp is not obs.NOOP_SPAN
            with sp as live:
                live.set(b=2)               # attributes are dropped
    assert obs.span("x") is obs.NOOP_SPAN
    with obs.span("x") as sp:
        sp.set(a=1)
    assert not isolated.spans and not isolated.counters
