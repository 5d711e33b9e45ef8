"""Plan-served GAN inference engine: bucket policy, metrics, FIFO fairness,
deadline flush, backpressure, pad-and-mask equivalence with unbatched
generation, and zero retraces after warmup."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import gan
from repro.serve import BucketPolicy, GanEngine, GenRequest, QueueFull
from repro.serve.batching import pow2_buckets
from repro.serve.metrics import ServeMetrics

_tiny = gan.reduced_config


class FakeClock:
    """Injectable clock for deterministic deadline / fairness tests."""

    def __init__(self, t=0.0):
        self.t = t

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


def _z(rng, n, z_dim):
    return rng.standard_normal((n, z_dim)).astype(np.float32)

def _bucket_ref(fn, params, r):
    """Reference output of one served request: the executable of the bucket
    that served it, run on the request's own latents zero-padded to that
    bucket. It is the same program as the batched dispatch, so pad rows and
    co-batched requests must not change a bit of the request's rows. (An
    unbatched program is a different one: XLA:CPU blocks its matmuls by
    batch size and rounds differently, by about 1e-7.)"""
    z = np.zeros((r.bucket, np.shape(r.z)[1]), np.float32)
    z[: r.n] = r.z
    return np.asarray(fn(params, jnp.asarray(z)))[: r.n]



@pytest.fixture(scope="module")
def tiny_dcgan():
    cfg = _tiny(gan.DCGAN)
    params = gan.generator_init(jax.random.key(0), cfg)
    return cfg, params


# ------------------------------------------------------------ bucket policy

def test_pow2_buckets():
    assert pow2_buckets(16) == (1, 2, 4, 8, 16)
    assert pow2_buckets(1) == (1,)
    with pytest.raises(ValueError):
        pow2_buckets(12)
    with pytest.raises(ValueError):
        pow2_buckets(0)


def test_bucket_for_picks_smallest_holding_bucket():
    p = BucketPolicy(buckets=(1, 2, 4, 8))
    assert [p.bucket_for(n) for n in (1, 2, 3, 4, 5, 8)] == [1, 2, 4, 4, 8, 8]
    with pytest.raises(ValueError):
        p.bucket_for(9)
    with pytest.raises(ValueError):
        p.bucket_for(0)


def test_policy_validation():
    with pytest.raises(ValueError):
        BucketPolicy(buckets=())
    with pytest.raises(ValueError):
        BucketPolicy(buckets=(4, 2, 8))          # not increasing
    with pytest.raises(ValueError):
        BucketPolicy(buckets=(2, 2, 4))          # duplicate
    with pytest.raises(ValueError):
        BucketPolicy(buckets=(1, 2), max_queue=1)  # < max bucket
    with pytest.raises(ValueError):
        BucketPolicy(max_wait_s=-1.0)


def test_pack_is_greedy_fifo_whole_requests():
    p = BucketPolicy(buckets=(1, 2, 4, 8))
    assert p.pack([]) == (0, 0)
    assert p.pack([1]) == (1, 1)
    assert p.pack([1, 3, 2, 1, 4]) == (4, 8)     # 1+3+2+1=7 -> bucket 8
    assert p.pack([8, 1]) == (1, 8)              # never split, never reorder
    assert p.pack([5, 4]) == (1, 8)              # 5+4 > 8: head only


def test_should_flush_full_and_deadline():
    p = BucketPolicy(buckets=(1, 2, 4, 8), max_wait_s=0.5)
    assert not p.should_flush([], 99.0)
    assert not p.should_flush([1, 2], 0.1)       # partial, young: wait
    assert p.should_flush([1, 2], 0.5)           # deadline hit
    assert p.should_flush([4, 4], 0.0)           # exactly full
    assert p.should_flush([4, 3, 2], 0.0)        # next req would overflow


# ---------------------------------------------------------------- metrics

def test_metrics_summary_math():
    m = ServeMetrics()
    m.record_admit(10.0)
    m.record_batch(3, 4, 0.5, now=11.0)
    m.record_batch(1, 4, 0.5, now=12.0)
    for lat in (0.1, 0.2, 0.3, 0.4):
        m.record_completion(lat)
    m.record_reject()
    s = m.summary()
    assert s["samples"] == 4 and s["batches"] == 2 and s["requests"] == 4
    assert s["pad_waste"] == pytest.approx(0.5)  # 4 of 8 rows were padding
    assert s["elapsed_s"] == pytest.approx(2.0)
    assert s["samples_per_s"] == pytest.approx(2.0)
    assert s["rejected"] == 1
    assert s["latency_s"]["p50"] == pytest.approx(0.25)
    assert s["latency_s"]["max"] == pytest.approx(0.4)
    assert "p99" in s["latency_s"]


def test_metrics_empty_summary():
    s = ServeMetrics().summary()
    assert s["pad_waste"] == 0.0 and s["samples_per_s"] == 0.0
    assert s["latency_s"]["p50"] == 0.0


# ----------------------------------------------------- engine: correctness

def test_pad_and_mask_matches_unbatched(tiny_dcgan):
    """Every admitted request's output is bitwise-equal to its bucket's
    executable run on its own latents — padding rows and co-batched
    requests must not perturb a single bit."""
    cfg, params = tiny_dcgan
    eng = GanEngine(BucketPolicy(buckets=(1, 2, 4, 8), max_queue=64))
    eng.register(cfg, params)
    eng.warmup()

    rng = np.random.default_rng(0)
    reqs = [GenRequest("dcgan", _z(rng, n, cfg.z_dim))
            for n in (1, 3, 2, 1, 4, 2, 1, 5)]
    eng.serve(reqs)
    assert all(r.done for r in reqs)
    for r in reqs:
        ref = _bucket_ref(eng._executable("dcgan", r.bucket), params, r)
        assert r.output.shape == ref.shape
        assert np.array_equal(np.asarray(r.output), ref), (
            f"request {r.rid} (n={r.n}) diverged from its bucket's program"
        )
        unbatched = gan.generator_apply(params, cfg, jnp.asarray(r.z))
        np.testing.assert_allclose(r.output, unbatched, rtol=1e-5, atol=1e-6)


def test_multi_model_registry_shares_one_engine(tiny_dcgan):
    """Two zoo generators served by the same engine, interleaved requests;
    each output still bitwise-matches its own model's bucket executable."""
    cfg_d, params_d = tiny_dcgan
    cfg_g = _tiny(gan.GPGAN)
    params_g = gan.generator_init(jax.random.key(1), cfg_g)

    eng = GanEngine(BucketPolicy(buckets=(1, 2, 4), max_queue=64))
    eng.register(cfg_d, params_d)
    eng.register(cfg_g, params_g)
    eng.warmup()

    rng = np.random.default_rng(1)
    reqs = []
    for i in range(8):
        name, cfg = (("dcgan", cfg_d), ("gpgan", cfg_g))[i % 2]
        reqs.append(GenRequest(name, _z(rng, 1 + i % 3, cfg.z_dim)))
    eng.serve(reqs)
    assert all(r.done for r in reqs)
    for r in reqs:
        params = params_d if r.model == "dcgan" else params_g
        ref = _bucket_ref(eng._executable(r.model, r.bucket), params, r)
        assert np.array_equal(np.asarray(r.output), ref)


def test_submit_validation(tiny_dcgan):
    cfg, params = tiny_dcgan
    eng = GanEngine(BucketPolicy(buckets=(1, 2), max_queue=16))
    eng.register(cfg, params)
    with pytest.raises(ValueError):                 # unknown model
        eng.submit(GenRequest("nope", np.zeros((1, cfg.z_dim), np.float32)))
    with pytest.raises(ValueError):                 # wrong z shape
        eng.submit(GenRequest("dcgan", np.zeros((3,), np.float32)))
    with pytest.raises(ValueError):                 # oversize request
        eng.submit(GenRequest("dcgan", np.zeros((3, cfg.z_dim), np.float32)))
    with pytest.raises(ValueError):                 # duplicate register
        eng.register(cfg, params)


def test_zero_row_request_rejected_at_admission(tiny_dcgan):
    """A (0, z_dim) request must be refused at submit — admitted, it would
    poison the queue head (no bucket holds 0 rows) and wedge the loop."""
    cfg, params = tiny_dcgan
    eng = GanEngine(BucketPolicy(buckets=(1, 2), max_queue=16))
    eng.register(cfg, params)
    with pytest.raises(ValueError):
        eng.submit(GenRequest("dcgan", np.zeros((0, cfg.z_dim), np.float32)))
    assert eng.queued_requests == 0
    rng = np.random.default_rng(12)                 # engine still serves
    ok = GenRequest("dcgan", _z(rng, 1, cfg.z_dim))
    eng.serve([ok])
    assert ok.done


# ------------------------------------------------------- engine: fairness

def test_fifo_order_within_model(tiny_dcgan):
    """Single model: requests complete in submission order even when batch
    formation groups them differently."""
    cfg, params = tiny_dcgan
    eng = GanEngine(BucketPolicy(buckets=(1, 2, 4), max_queue=64))
    eng.register(cfg, params)
    eng.warmup()
    rng = np.random.default_rng(2)
    reqs = [GenRequest("dcgan", _z(rng, n, cfg.z_dim))
            for n in (1, 2, 1, 3, 1, 1, 2)]
    eng.serve(reqs)
    assert [r.rid for r in eng.completed] == sorted(r.rid for r in reqs)


def test_fifo_fairness_across_models_serves_oldest_head_first(tiny_dcgan):
    """Cross-model fairness: each dispatch serves the model whose head
    request has waited longest — a busy model cannot starve a quiet one."""
    cfg_d, params_d = tiny_dcgan
    cfg_g = _tiny(gan.GPGAN)
    params_g = gan.generator_init(jax.random.key(1), cfg_g)

    clock = FakeClock()
    eng = GanEngine(
        BucketPolicy(buckets=(1, 2), max_wait_s=0.0, max_queue=64),
        clock=clock,
    )
    eng.register(cfg_d, params_d)
    eng.register(cfg_g, params_g)
    eng.warmup()

    rng = np.random.default_rng(3)
    # dcgan floods at t=0,1,2; gpgan arrives at t=0.5 — it must be served
    # right after the first dcgan batch, not after the whole flood
    a0 = GenRequest("dcgan", _z(rng, 1, cfg_d.z_dim))
    a1 = GenRequest("dcgan", _z(rng, 1, cfg_d.z_dim))
    a2 = GenRequest("dcgan", _z(rng, 1, cfg_d.z_dim))
    b0 = GenRequest("gpgan", _z(rng, 1, cfg_g.z_dim))
    for t, r in [(0.0, a0), (0.0, a1), (0.5, b0), (2.0, a2)]:
        clock.t = t
        eng.submit(r)
    while eng.step(drain=True):
        pass
    assert [r.rid for r in eng.completed] == [a0.rid, a1.rid, b0.rid, a2.rid]


# ------------------------------------------------ engine: deadline flush

def test_deadline_flushes_partial_batch(tiny_dcgan):
    """A lone small request does not wait for a full bucket: the step loop
    refuses to dispatch before max_wait_s and flushes right after it."""
    cfg, params = tiny_dcgan
    clock = FakeClock()
    eng = GanEngine(
        BucketPolicy(buckets=(1, 2, 4, 8), max_wait_s=0.25, max_queue=64),
        clock=clock,
    )
    eng.register(cfg, params)
    eng.warmup()

    rng = np.random.default_rng(4)
    r = GenRequest("dcgan", _z(rng, 2, cfg.z_dim))
    eng.submit(r)
    assert not eng.step()          # young partial batch: hold
    clock.advance(0.1)
    assert not eng.step()          # still under the deadline
    clock.advance(0.2)             # 0.3s waited > 0.25s max_wait
    assert eng.step()
    assert r.done and eng.metrics.batches == 1
    # padded into the smallest holding bucket, not the largest
    assert eng.metrics.padded == 2 and eng.metrics.samples == 2


def test_full_bucket_flushes_immediately(tiny_dcgan):
    cfg, params = tiny_dcgan
    clock = FakeClock()
    eng = GanEngine(
        BucketPolicy(buckets=(1, 2, 4), max_wait_s=999.0, max_queue=64),
        clock=clock,
    )
    eng.register(cfg, params)
    eng.warmup()
    rng = np.random.default_rng(5)
    for n in (2, 2):               # fills the max bucket exactly
        eng.submit(GenRequest("dcgan", _z(rng, n, cfg.z_dim)))
    assert eng.step()              # no deadline needed
    assert eng.metrics.samples == 4 and eng.metrics.pad_waste == 0.0


# ------------------------------------------------- engine: backpressure

def test_backpressure_rejects_above_queue_bound(tiny_dcgan):
    cfg, params = tiny_dcgan
    clock = FakeClock()
    eng = GanEngine(
        BucketPolicy(buckets=(1, 2, 4), max_wait_s=999.0, max_queue=6),
        clock=clock,
    )
    eng.register(cfg, params)
    rng = np.random.default_rng(6)
    eng.submit(GenRequest("dcgan", _z(rng, 4, cfg.z_dim)))
    eng.submit(GenRequest("dcgan", _z(rng, 2, cfg.z_dim)))
    overflow = GenRequest("dcgan", _z(rng, 1, cfg.z_dim))
    with pytest.raises(QueueFull):
        eng.submit(overflow)
    assert overflow.rid == -1 and eng.queued_requests == 2
    assert eng.metrics.rejected == 1
    # draining frees the queue: the same request is admissible again
    while eng.step(drain=True):
        pass
    eng.submit(overflow)
    assert eng.queued_requests == 1


# --------------------------------------------- engine: zero retraces

def test_zero_retraces_after_warmup(tiny_dcgan, tconv_trace_counter):
    """The tentpole invariant: after warmup, a mixed-size request stream
    causes ZERO new layer traces (every bucket's plan traced exactly once)
    and the engine's trace-time recompile counter stays frozen."""
    cfg, params = tiny_dcgan
    eng = GanEngine(BucketPolicy(buckets=(1, 2, 4, 8), max_queue=256))
    eng.register(cfg, params)
    eng.warmup()

    # warmup traced each (bucket, layer) plan exactly once
    assert eng.warmup_recompiles == 4              # one executable per bucket
    assert len(tconv_trace_counter) == 4 * len(cfg.layers)
    assert all(c == 1 for c in tconv_trace_counter.values())
    warm = dict(tconv_trace_counter)

    rng = np.random.default_rng(7)
    for _ in range(3):             # several waves of mixed-size traffic
        reqs = [GenRequest("dcgan", _z(rng, 1 + int(n), cfg.z_dim))
                for n in rng.integers(0, 8, size=9)]
        eng.serve(reqs)
        assert all(r.done for r in reqs)

    assert tconv_trace_counter == warm, "steady-state serving retraced"
    assert eng.metrics.recompiles == eng.warmup_recompiles


def test_unwarmed_engine_compiles_inline_and_counts_it(tiny_dcgan):
    """Skipping warmup still serves correctly — the recompile counter is
    how the metrics surface the inline compile cost."""
    cfg, params = tiny_dcgan
    eng = GanEngine(BucketPolicy(buckets=(1, 2), max_queue=16))
    eng.register(cfg, params)
    assert eng.metrics.recompiles == 0
    rng = np.random.default_rng(8)
    reqs = [GenRequest("dcgan", _z(rng, 2, cfg.z_dim))]
    eng.serve(reqs)
    assert reqs[0].done
    assert eng.metrics.recompiles == 1             # paid inline, visible
    eng.serve([GenRequest("dcgan", _z(rng, 2, cfg.z_dim))])
    assert eng.metrics.recompiles == 1             # second hit: cached


# ---------------------------------------------------------- replay mode

def test_replay_serves_trace_to_completion(tiny_dcgan):
    cfg, params = tiny_dcgan
    eng = GanEngine(
        BucketPolicy(buckets=(1, 2, 4), max_wait_s=0.002, max_queue=64)
    )
    eng.register(cfg, params)
    eng.warmup()
    rng = np.random.default_rng(9)
    reqs = [GenRequest("dcgan", _z(rng, 1 + i % 2, cfg.z_dim))
            for i in range(6)]
    arrivals = [i * 1e-3 for i in range(6)]
    eng.replay(reqs, arrivals)
    assert all(r.done for r in reqs)
    assert eng.metrics.requests == 6


def test_replay_sheds_load_under_backpressure(tiny_dcgan):
    """QueueFull during replay drops the one rejected request (counted in
    metrics) and keeps serving the rest of the trace — a hot burst must not
    abort the whole replay."""
    cfg, params = tiny_dcgan
    eng = GanEngine(
        BucketPolicy(buckets=(1, 2), max_wait_s=999.0, max_queue=2)
    )
    eng.register(cfg, params)
    eng.warmup()
    rng = np.random.default_rng(11)
    reqs = [GenRequest("dcgan", _z(rng, 1, cfg.z_dim)) for _ in range(6)]
    eng.replay(reqs, [0.0] * 6)        # burst into a 2-sample queue bound
    served = [r for r in reqs if r.done]
    assert eng.metrics.rejected == 6 - len(served) > 0
    assert eng.metrics.requests == len(served)


def test_replay_rejects_unsorted_arrivals(tiny_dcgan):
    cfg, params = tiny_dcgan
    eng = GanEngine(BucketPolicy(buckets=(1, 2), max_queue=16))
    eng.register(cfg, params)
    rng = np.random.default_rng(10)
    reqs = [GenRequest("dcgan", _z(rng, 1, cfg.z_dim)) for _ in range(2)]
    with pytest.raises(ValueError):
        eng.replay(reqs, [0.2, 0.1])


# ------------------------------------------------- engine: request deadlines

def test_expired_request_rejected_not_served_stale(tiny_dcgan):
    """A queued request whose deadline passes is dropped and counted —
    never dispatched late as if nothing happened."""
    cfg, params = tiny_dcgan
    clock = FakeClock()
    eng = GanEngine(
        BucketPolicy(buckets=(1, 2, 4), max_wait_s=999.0, max_queue=64),
        clock=clock,
    )
    eng.register(cfg, params)
    eng.warmup()
    rng = np.random.default_rng(11)
    impatient = GenRequest("dcgan", _z(rng, 1, cfg.z_dim), deadline_s=0.05)
    patient = GenRequest("dcgan", _z(rng, 1, cfg.z_dim))
    eng.submit(impatient)
    eng.submit(patient)
    clock.advance(0.2)             # past the impatient deadline
    assert eng.step(drain=True)    # dispatches what's still valid
    assert impatient.expired and not impatient.done
    assert impatient.output is None
    assert patient.done and not patient.expired
    assert eng.metrics.expired == 1
    assert eng.metrics.requests == 1   # only the served one completed
    # the dispatched batch never contained the expired rows
    assert eng.metrics.samples == 1


def test_expired_mid_queue_behind_patient_head(tiny_dcgan):
    """Deadlines are per-request: a short-deadline request can expire
    BEHIND a patient head without disturbing FIFO order for the rest."""
    cfg, params = tiny_dcgan
    clock = FakeClock()
    eng = GanEngine(
        BucketPolicy(buckets=(1, 2, 4), max_wait_s=999.0, max_queue=64),
        clock=clock,
    )
    eng.register(cfg, params)
    eng.warmup()
    rng = np.random.default_rng(12)
    head = GenRequest("dcgan", _z(rng, 1, cfg.z_dim))
    mid = GenRequest("dcgan", _z(rng, 1, cfg.z_dim), deadline_s=0.01)
    tail = GenRequest("dcgan", _z(rng, 1, cfg.z_dim))
    for r in (head, mid, tail):
        eng.submit(r)
    clock.advance(0.1)
    assert eng.step(drain=True)
    assert mid.expired and not mid.done
    assert head.done and tail.done
    assert [r.rid for r in eng.completed] == [head.rid, tail.rid]
    assert eng.metrics.expired == 1


def test_serve_all_expired_drains_cleanly(tiny_dcgan):
    """step() must terminate (not spin) when everything queued expires."""
    cfg, params = tiny_dcgan
    clock = FakeClock()
    eng = GanEngine(
        BucketPolicy(buckets=(1, 2), max_wait_s=999.0, max_queue=64),
        clock=clock,
    )
    eng.register(cfg, params)
    eng.warmup()
    rng = np.random.default_rng(13)
    reqs = [GenRequest("dcgan", _z(rng, 1, cfg.z_dim), deadline_s=0.01)
            for _ in range(3)]
    for r in reqs:
        eng.submit(r)
    clock.advance(1.0)
    assert not eng.step(drain=True)   # purge drains the queue, nothing runs
    assert eng.queued_requests == 0
    assert all(r.expired and not r.done for r in reqs)
    assert eng.metrics.expired == 3 and eng.metrics.batches == 0


def test_deadline_validation(tiny_dcgan):
    cfg, params = tiny_dcgan
    eng = GanEngine(BucketPolicy(buckets=(1, 2), max_queue=64))
    eng.register(cfg, params)
    rng = np.random.default_rng(14)
    with pytest.raises(ValueError):
        eng.submit(GenRequest("dcgan", _z(rng, 1, cfg.z_dim), deadline_s=0.0))
    with pytest.raises(ValueError):
        eng.submit(GenRequest("dcgan", _z(rng, 1, cfg.z_dim),
                              deadline_s=-1.0))


# ------------------------------------- engine: terminal-state accounting

def test_expired_request_stamps_t_done_and_residence(tiny_dcgan):
    """Expiry is a terminal resolution like any other: ``t_done`` is
    stamped at purge so ``latency_s`` (queue residence) is measurable, and
    the residence lands in ``metrics.expired_residence_s``."""
    cfg, params = tiny_dcgan
    clock = FakeClock()
    eng = GanEngine(
        BucketPolicy(buckets=(1, 2), max_wait_s=999.0, max_queue=64),
        clock=clock,
    )
    eng.register(cfg, params)
    eng.warmup()
    rng = np.random.default_rng(15)
    req = GenRequest("dcgan", _z(rng, 1, cfg.z_dim), deadline_s=0.05)
    eng.submit(req)
    t_submit = req.t_submit
    clock.advance(0.3)
    eng.step(drain=True)
    assert req.expired and req.terminal_state == "expired"
    assert req.t_done == clock.t                  # stamped at purge
    assert req.latency_s == pytest.approx(clock.t - t_submit)
    assert np.isfinite(req.latency_s)
    assert eng.metrics.expired_residence_s == [pytest.approx(0.3)]
    assert eng.metrics.summary()["expired_residence_s"]["p50"] == (
        pytest.approx(0.3)
    )


def test_replay_malformed_request_failed_not_abort(tiny_dcgan):
    """A live trace must keep serving through a bad request: a malformed
    submit (unknown model / wrong latent shape) is terminally failed and
    counted, and the rest of the trace is served — the replay never
    aborts with the queue half-full."""
    cfg, params = tiny_dcgan
    eng = GanEngine(
        BucketPolicy(buckets=(1, 2), max_wait_s=0.001, max_queue=64)
    )
    eng.register(cfg, params)
    eng.warmup()
    rng = np.random.default_rng(16)
    good_a = GenRequest("dcgan", _z(rng, 1, cfg.z_dim))
    unknown = GenRequest("nope", _z(rng, 1, cfg.z_dim))
    bad_shape = GenRequest("dcgan", _z(rng, 1, cfg.z_dim + 3))
    good_b = GenRequest("dcgan", _z(rng, 2, cfg.z_dim))
    reqs = [good_a, unknown, bad_shape, good_b]
    eng.replay(reqs, [0.0, 0.001, 0.002, 0.003])
    assert good_a.done and good_b.done
    assert unknown.failed and not unknown.done
    assert bad_shape.failed and not bad_shape.done
    assert unknown.terminal_state == "failed"
    assert np.isfinite(unknown.latency_s)         # t_done stamped
    assert eng.metrics.malformed == 2
    assert eng.metrics.requests == 2
    ledger = eng.conservation()
    assert ledger["ok"] and ledger["admitted"] == 2


def test_conservation_ledger_plain_engine(tiny_dcgan):
    """The conservation ledger on the base engine: done + expired +
    rejected splits exactly, mid-run the still-queued term balances."""
    cfg, params = tiny_dcgan
    clock = FakeClock()
    eng = GanEngine(
        BucketPolicy(buckets=(1, 2), max_wait_s=999.0, max_queue=2),
        clock=clock,
    )
    eng.register(cfg, params)
    eng.warmup()
    rng = np.random.default_rng(17)
    served = GenRequest("dcgan", _z(rng, 1, cfg.z_dim))
    doomed = GenRequest("dcgan", _z(rng, 1, cfg.z_dim), deadline_s=0.01)
    eng.submit(served)
    eng.submit(doomed)
    with pytest.raises(QueueFull):
        eng.submit(GenRequest("dcgan", _z(rng, 1, cfg.z_dim)))
    mid = eng.conservation()
    assert mid["ok"] and mid["queued"] == 2 and mid["resolved"] == 0
    clock.advance(0.1)
    while eng.step(drain=True):
        pass
    end = eng.conservation()
    assert end["ok"] and end["queued"] == 0
    assert end["done"] == 1 and end["expired"] == 1 and end["rejected"] == 1
    assert end["admitted"] == end["resolved"] == 2
