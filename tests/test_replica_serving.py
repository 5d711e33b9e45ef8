"""Resilient multi-replica serving: Replica executables, supervisor
routing, the health state machine, timeout/retry/backoff, output guard,
graceful degradation, per-model metrics, and the conservation invariant.

The chaos-flavored twins (deterministic fault injection through the
replica dispatch seam) live in ``tests/test_serve_fault_injection.py``.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import gan
from repro.serve import (
    BucketPolicy,
    GenRequest,
    Replica,
    ReplicaState,
    ReplicaSupervisor,
)
from repro.serve.fault_injection import (
    ReplicaCrash,
    ServeFaultInjector,
    ServeFaultPlan,
    TransientDispatchError,
)

_tiny = gan.reduced_config


class FakeClock:
    def __init__(self, t=0.0):
        self.t = t

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


def _z(rng, n, z_dim):
    return rng.standard_normal((n, z_dim)).astype(np.float32)


@pytest.fixture(scope="module")
def tiny_dcgan():
    cfg = _tiny(gan.DCGAN)
    params = gan.generator_init(jax.random.key(0), cfg)
    return cfg, params


def make_supervisor(cfg, params, *, n_replicas=2, plan=None, clock=None,
                    buckets=(1, 2, 4), max_wait_s=0.0, max_queue=64,
                    **kwargs):
    """Two warmed replicas (optionally fault-injected) under one
    supervisor with a fake clock and an explicit dispatch timeout."""
    clock = clock or FakeClock()
    inj = ServeFaultInjector(plan, clock=clock) if plan is not None else None
    hook = inj.hook if inj is not None else None
    replicas = [Replica(f"r{i}", dispatch_hook=hook)
                for i in range(n_replicas)]
    kwargs.setdefault("timeout_s", 1.0)
    sup = ReplicaSupervisor(
        replicas,
        BucketPolicy(buckets=buckets, max_wait_s=max_wait_s,
                     max_queue=max_queue),
        clock=clock, **kwargs,
    )
    sup.register(cfg, params)
    sup.warmup()
    return sup, inj, clock

def _served_ref(sup, params, r):
    """Reference output of one served request: the executable that served
    it (its replica's, or the supervisor's own inline one) at its bucket,
    run on the request's own latents zero-padded to that bucket. The same
    program as the batched dispatch, so the comparison is bitwise. (An
    unbatched program is a different one: XLA:CPU blocks its matmuls by
    batch size and rounds differently, by about 1e-7.)"""
    owner = sup if r.replica == "inline" else sup.rslots[r.replica].replica
    z = np.zeros((r.bucket, np.shape(r.z)[1]), np.float32)
    z[: r.n] = r.z
    out = owner._executable(r.model, r.bucket)(params, jnp.asarray(z))
    return np.asarray(out)[: r.n]



# --------------------------------------------------------------- replica

def test_replica_outputs_bitwise_equal_unbatched(tiny_dcgan):
    cfg, params = tiny_dcgan
    rep = Replica("r0")
    rep.register(cfg, params)
    rep.warmup([1, 2])
    rng = np.random.default_rng(0)
    z = _z(rng, 2, cfg.z_dim)
    out = rep.execute("dcgan", z, 2)
    ref = np.asarray(gan.generator_apply(params, cfg, jnp.asarray(z)))
    assert np.array_equal(out, ref)


def test_replica_warmup_measures_baselines_and_compiles_once(tiny_dcgan):
    cfg, params = tiny_dcgan
    rep = Replica("r0")
    rep.register(cfg, params)
    rep.warmup([1, 2, 4])
    assert rep.recompiles == 3                    # one trace per bucket
    assert set(rep.baseline_s) == {("dcgan", 1), ("dcgan", 2), ("dcgan", 4)}
    assert all(v > 0 for v in rep.baseline_s.values())
    rng = np.random.default_rng(1)
    for n in (1, 2, 4, 1, 2):                     # steady state: no retraces
        rep.execute("dcgan", _z(rng, n, cfg.z_dim), n)
    assert rep.recompiles == 3


def test_replica_dispatch_seam_sees_every_dispatch(tiny_dcgan):
    cfg, params = tiny_dcgan
    seen = []

    def hook(replica, index, name, bucket, probe=False):
        seen.append((replica.replica_id, index, name, bucket, probe))
        return None

    rep = Replica("r7", dispatch_hook=hook)
    rep.register(cfg, params)
    rep.warmup([1])
    rng = np.random.default_rng(2)
    rep.execute("dcgan", _z(rng, 1, cfg.z_dim), 1)
    rep.execute("dcgan", _z(rng, 1, cfg.z_dim), 1)
    assert rep.probe() is True
    assert seen == [
        ("r7", 1, "dcgan", 1, False),
        ("r7", 2, "dcgan", 1, False),
        ("r7", 1, "dcgan", 1, True),   # probes count separately
    ]


def test_replica_hook_transform_poisons_only_this_output(tiny_dcgan):
    cfg, params = tiny_dcgan

    def hook(replica, index, name, bucket, probe=False):
        if not probe and index == 1:
            def poison(out):
                out = np.array(out, copy=True)
                out[0] = np.nan
                return out
            return poison
        return None

    rep = Replica("r0", dispatch_hook=hook)
    rep.register(cfg, params)
    rep.warmup([1])
    rng = np.random.default_rng(3)
    z = _z(rng, 1, cfg.z_dim)
    bad = rep.execute("dcgan", z, 1)
    good = rep.execute("dcgan", z, 1)
    assert np.isnan(bad).any()
    assert np.isfinite(good).all()


def test_replica_duplicate_register_rejected(tiny_dcgan):
    cfg, params = tiny_dcgan
    rep = Replica("r0")
    rep.register(cfg, params)
    with pytest.raises(ValueError):
        rep.register(cfg, params)


# ---------------------------------------------------- supervisor: routing

def test_supervisor_outputs_bitwise_equal_across_replicas(tiny_dcgan):
    """Both replicas serve mixed traffic; every output bitwise-matches its
    bucket's program — replicas run the same compiled plans."""
    cfg, params = tiny_dcgan
    sup, _, _ = make_supervisor(cfg, params)
    rng = np.random.default_rng(4)
    reqs = [GenRequest("dcgan", _z(rng, 1 + i % 3, cfg.z_dim))
            for i in range(8)]
    sup.serve(reqs)
    assert all(r.done for r in reqs)
    for r in reqs:
        assert np.array_equal(np.asarray(r.output), _served_ref(sup, params, r))
        # the other replica's program at the same bucket is bitwise-equal
        other = "r1" if r.replica == "r0" else "r0"
        assert np.array_equal(
            _served_ref(sup, params, dataclasses.replace(r, replica=other)),
            np.asarray(r.output),
        )
    # both replicas actually took traffic (round-robin balance)
    by_replica = {r.replica for r in reqs}
    assert by_replica == {"r0", "r1"}


def test_supervisor_round_robin_balances_dispatches(tiny_dcgan):
    cfg, params = tiny_dcgan
    sup, _, _ = make_supervisor(cfg, params)
    rng = np.random.default_rng(5)
    sup.serve([GenRequest("dcgan", _z(rng, 1, cfg.z_dim))
               for _ in range(10)])
    d0 = sup.rslots["r0"].replica.dispatches
    d1 = sup.rslots["r1"].replica.dispatches
    assert d0 + d1 == sup.metrics.batches
    assert abs(d0 - d1) <= 1


def test_supervisor_single_replica_works(tiny_dcgan):
    cfg, params = tiny_dcgan
    sup, _, _ = make_supervisor(cfg, params, n_replicas=1)
    rng = np.random.default_rng(6)
    reqs = [GenRequest("dcgan", _z(rng, 2, cfg.z_dim)) for _ in range(3)]
    sup.serve(reqs)
    assert all(r.done and r.replica == "r0" for r in reqs)


def test_supervisor_validation(tiny_dcgan):
    with pytest.raises(ValueError):
        ReplicaSupervisor([])                                  # no replicas
    with pytest.raises(ValueError):
        ReplicaSupervisor([Replica("a"), Replica("a")])        # dup ids
    with pytest.raises(ValueError):
        ReplicaSupervisor([Replica("a", dtype="bfloat16")])    # dtype clash
    with pytest.raises(ValueError):
        ReplicaSupervisor([Replica("a")], degraded_mode="explode")
    with pytest.raises(ValueError):
        ReplicaSupervisor([Replica("a")], retry_budget=-1)


def test_supervisor_inherits_engine_invariants(tiny_dcgan):
    """FIFO order, deadline expiry, and backpressure all still hold under
    the supervisor — it reuses the engine's admission half unchanged."""
    cfg, params = tiny_dcgan
    clock = FakeClock()
    sup, _, _ = make_supervisor(cfg, params, clock=clock, max_queue=4,
                                buckets=(1, 2))
    rng = np.random.default_rng(7)
    a = GenRequest("dcgan", _z(rng, 2, cfg.z_dim))
    b = GenRequest("dcgan", _z(rng, 2, cfg.z_dim), deadline_s=0.01)
    sup.submit(a)
    sup.submit(b)
    from repro.serve import QueueFull
    with pytest.raises(QueueFull):
        sup.submit(GenRequest("dcgan", _z(rng, 1, cfg.z_dim)))
    clock.advance(0.1)                 # b expires while queued
    while sup.step(drain=True):
        pass
    assert a.done and b.expired and not b.done
    assert sup.metrics.expired == 1 and sup.metrics.rejected == 1
    assert sup.conservation()["ok"]


# --------------------------------------------- supervisor: health machine

def test_crash_requeues_batch_onto_surviving_replica(tiny_dcgan):
    cfg, params = tiny_dcgan
    plan = ServeFaultPlan(crash_at=(("r0", 2),))
    sup, inj, _ = make_supervisor(cfg, params, plan=plan)
    rng = np.random.default_rng(8)
    reqs = [GenRequest("dcgan", _z(rng, 1, cfg.z_dim)) for _ in range(6)]
    for r in reqs:   # one batch per serve so r0 reaches dispatch index 2
        sup.serve([r])
    assert inj.fired and inj.fired[0][0] == "crash"
    assert all(r.done for r in reqs)
    assert sup.metrics.requeues >= 1 and sup.metrics.retries >= 1
    # the retried batch landed somewhere that was not the crashed replica
    retried = [r for r in reqs if r.retries > 0]
    assert retried and all(r.replica != "r0" for r in retried)
    for r in reqs:
        ref = np.asarray(gan.generator_apply(params, cfg, jnp.asarray(r.z)))
        assert np.array_equal(np.asarray(r.output), ref)
    assert sup.conservation()["ok"]


def test_failure_transitions_healthy_suspect_dead(tiny_dcgan):
    """Two strikes: first failure HEALTHY->SUSPECT, second (when the
    suspect replica is routed again or probed) -> DEAD."""
    cfg, params = tiny_dcgan
    plan = ServeFaultPlan(crash_at=(("r0", 1), ("r1", 1)))
    sup, _, _ = make_supervisor(cfg, params, plan=plan, retry_budget=10)
    rng = np.random.default_rng(9)
    reqs = [GenRequest("dcgan", _z(rng, 1, cfg.z_dim)) for _ in range(2)]
    sup.serve(reqs)
    tc = sup.metrics.transition_counts
    assert tc.get("HEALTHY->SUSPECT", 0) == 2
    assert tc.get("SUSPECT->DEAD", 0) == 2
    assert sup.replica_states() == {"r0": "DEAD", "r1": "DEAD"}
    # degraded inline kept serving
    assert all(r.done and r.replica == "inline" for r in reqs)
    assert sup.metrics.degraded_batches >= 1
    assert sup.conservation()["ok"]


def test_transient_error_bounces_suspect_then_healthy(tiny_dcgan):
    cfg, params = tiny_dcgan
    plan = ServeFaultPlan(transient_at=(("r0", 2),))
    sup, inj, _ = make_supervisor(cfg, params, n_replicas=1, plan=plan)
    rng = np.random.default_rng(10)
    reqs = [GenRequest("dcgan", _z(rng, 1, cfg.z_dim)) for _ in range(4)]
    for r in reqs:   # one batch per serve so dispatch 2 hits the fault
        sup.serve([r])
    assert ("transient", "r0", 2) in inj.fired
    assert all(r.done for r in reqs)
    tc = sup.metrics.transition_counts
    assert tc.get("HEALTHY->SUSPECT", 0) == 1
    assert tc.get("SUSPECT->HEALTHY", 0) == 1
    assert sup.replica_states()["r0"] == "HEALTHY"
    assert sup.conservation()["ok"]


def test_timeout_marks_suspect_and_requeues(tiny_dcgan):
    """A dispatch stalling past the deadline is a straggler: its (late)
    result is discarded, the replica goes SUSPECT, the batch requeues and
    completes elsewhere."""
    cfg, params = tiny_dcgan
    plan = ServeFaultPlan(hang_at=(("r1", 1, 5.0),))
    sup, inj, _ = make_supervisor(cfg, params, plan=plan, timeout_s=1.0)
    rng = np.random.default_rng(11)
    reqs = [GenRequest("dcgan", _z(rng, 2, cfg.z_dim)) for _ in range(4)]
    sup.serve(reqs)   # two bucket-4 batches: round-robin hits r1 second
    assert any(f[0] == "hang" for f in inj.fired)
    assert sup.metrics.timeouts == 1
    assert sup.metrics.requeues >= 1
    assert all(r.done for r in reqs)
    assert "HEALTHY->SUSPECT" in sup.metrics.transition_counts
    assert sup.conservation()["ok"]


def test_timeout_derived_from_warmup_baselines(tiny_dcgan):
    cfg, params = tiny_dcgan
    clock = FakeClock()
    replicas = [Replica("r0")]
    sup = ReplicaSupervisor(
        replicas, BucketPolicy(buckets=(1, 2), max_wait_s=0.0, max_queue=16),
        timeout_factor=8.0, min_timeout_s=0.05, clock=clock,
    )
    sup.register(cfg, params)
    sup.warmup()
    base = sup._baseline_s[("dcgan", 1)]
    assert base > 0
    assert sup.timeout_for("dcgan", 1) == max(0.05, 8.0 * base)
    # unknown (model, bucket) signature floors at min_timeout_s
    assert sup.timeout_for("dcgan", 999) == 0.05


def test_nonfinite_output_never_served(tiny_dcgan):
    """A poisoned output plane is retried, never handed to a client."""
    cfg, params = tiny_dcgan
    plan = ServeFaultPlan(nan_at=(("r0", 1),))
    sup, inj, _ = make_supervisor(cfg, params, plan=plan)
    rng = np.random.default_rng(12)
    reqs = [GenRequest("dcgan", _z(rng, 1, cfg.z_dim)) for _ in range(4)]
    sup.serve(reqs)
    assert any(f[0] == "nan" for f in inj.fired)
    assert sup.metrics.nonfinite == 1
    assert all(r.done for r in reqs)
    for r in reqs:
        assert np.isfinite(np.asarray(r.output)).all()
        assert np.array_equal(np.asarray(r.output), _served_ref(sup, params, r))
    assert sup.conservation()["ok"]


# ---------------------------------------- supervisor: retry budget / shed

def test_retry_budget_exhaustion_fails_terminally(tiny_dcgan):
    """Every dispatch fails everywhere and degradation is shedding: the
    requests must terminally fail (bounded) — not spin forever."""
    cfg, params = tiny_dcgan
    plan = ServeFaultPlan(crash_at=(("r0", 1), ("r1", 1)))
    sup, _, _ = make_supervisor(cfg, params, plan=plan, retry_budget=2,
                                degraded_mode="shed")
    rng = np.random.default_rng(13)
    reqs = [GenRequest("dcgan", _z(rng, 1, cfg.z_dim)) for _ in range(3)]
    sup.serve(reqs)
    assert all(r.failed and not r.done for r in reqs)
    assert all(r.terminal_state == "failed" for r in reqs)
    assert all(r.retries >= 1 for r in reqs)
    assert sup.metrics.failed == 3
    assert sup.queued_requests == 0
    assert sup.conservation()["ok"]


def test_all_dead_shed_mode_bounded_shedding(tiny_dcgan):
    cfg, params = tiny_dcgan
    plan = ServeFaultPlan(crash_at=(("r0", 1), ("r1", 1)))
    sup, _, _ = make_supervisor(cfg, params, plan=plan, retry_budget=10,
                                degraded_mode="shed")
    rng = np.random.default_rng(14)
    reqs = [GenRequest("dcgan", _z(rng, 1, cfg.z_dim)) for _ in range(4)]
    sup.serve(reqs)
    assert all(r.terminal_state == "failed" for r in reqs)
    assert sup.metrics.shed == 4
    assert sup.conservation()["ok"]


def test_all_dead_inline_fallback_serves_bitwise_equal(tiny_dcgan):
    """Graceful degradation: every replica dead -> the supervisor's own
    inline executables serve the batch (lazily compiled, visible in the
    recompile counter), outputs still bitwise-equal."""
    cfg, params = tiny_dcgan
    plan = ServeFaultPlan(crash_at=(("r0", 1), ("r1", 1)))
    sup, _, _ = make_supervisor(cfg, params, plan=plan, retry_budget=10,
                                degraded_mode="inline")
    rng = np.random.default_rng(15)
    reqs = [GenRequest("dcgan", _z(rng, 1, cfg.z_dim)) for _ in range(4)]
    assert sup.metrics.recompiles == 0       # inline executables are cold
    sup.serve(reqs)
    assert all(r.done and r.replica == "inline" for r in reqs)
    assert sup.metrics.degraded_batches >= 1
    assert sup.metrics.recompiles >= 1       # the inline compile is visible
    for r in reqs:
        assert np.array_equal(np.asarray(r.output), _served_ref(sup, params, r))
        # the replicas' program at the same bucket is bitwise-equal too
        as_r0 = dataclasses.replace(r, replica="r0")
        assert np.array_equal(
            np.asarray(r.output), _served_ref(sup, params, as_r0)
        )
    assert sup.conservation()["ok"]


# ------------------------------------------- supervisor: circuit breaker

def test_circuit_breaker_backoff_doubles_and_revives(tiny_dcgan):
    """DEAD replicas are probed on an exponential backoff; a reviving
    probe moves them RECOVERING, and one successful dispatch re-earns
    HEALTHY — the full DEAD -> RECOVERING -> HEALTHY arc."""
    cfg, params = tiny_dcgan
    plan = ServeFaultPlan(crash_at=(("r0", 1),),
                          revive_after_probes=(("r0", 3),))
    sup, inj, clock = make_supervisor(cfg, params, plan=plan,
                                      probe_backoff_s=0.1,
                                      probe_backoff_max_s=10.0)
    rng = np.random.default_rng(16)
    sup.serve([GenRequest("dcgan", _z(rng, 1, cfg.z_dim))
               for _ in range(3)])
    # keep traffic flowing while time passes so due probes fire
    for _ in range(40):
        clock.advance(0.1)
        sup.serve([GenRequest("dcgan", _z(rng, 1, cfg.z_dim))])
        if sup.replica_states()["r0"] == "HEALTHY":
            break
    assert ("revive", "r0", 3) in inj.fired
    tc = sup.metrics.transition_counts
    assert tc.get("SUSPECT->DEAD", 0) == 1
    assert tc.get("DEAD->RECOVERING", 0) == 1
    assert tc.get("RECOVERING->HEALTHY", 0) == 1
    assert sup.replica_states()["r0"] == "HEALTHY"
    assert sup.metrics.probes >= 3
    assert sup.metrics.probe_failures >= 2
    # revived replica takes real traffic again (round-robin: 4 separate
    # batches guarantee r0 lands at least one)
    d0_before = sup.rslots["r0"].replica.dispatches
    for _ in range(4):
        sup.serve([GenRequest("dcgan", _z(rng, 1, cfg.z_dim))])
    assert sup.rslots["r0"].replica.dispatches > d0_before
    assert sup.conservation()["ok"]


def test_unhealthy_replica_not_probed_before_backoff(tiny_dcgan):
    cfg, params = tiny_dcgan
    plan = ServeFaultPlan(crash_at=(("r0", 1),))
    sup, _, clock = make_supervisor(cfg, params, plan=plan,
                                    probe_backoff_s=100.0)
    rng = np.random.default_rng(17)
    sup.serve([GenRequest("dcgan", _z(rng, 1, cfg.z_dim))
               for _ in range(4)])
    assert sup.replica_states()["r0"] in ("SUSPECT", "DEAD")
    probes_before = sup.metrics.probes
    clock.advance(1.0)                       # far inside the backoff
    sup.serve([GenRequest("dcgan", _z(rng, 1, cfg.z_dim))])
    assert sup.metrics.probes == probes_before


# -------------------------------------- zero steady-state recompiles

def test_per_replica_zero_steady_state_recompiles_under_faults(tiny_dcgan):
    """The engine invariant, now per replica: after warmup, mixed traffic
    WITH injected faults (crash + NaN retries) adds zero traces on any
    replica — a retried bucket re-runs a warmed executable."""
    cfg, params = tiny_dcgan
    plan = ServeFaultPlan(crash_at=(("r0", 3),), nan_at=(("r1", 2),))
    sup, _, _ = make_supervisor(cfg, params, plan=plan, retry_budget=10)
    warm = dict(sup.replica_recompiles)
    assert all(v == len(sup.policy.buckets) for v in warm.values())
    rng = np.random.default_rng(18)
    for _ in range(3):
        reqs = [GenRequest("dcgan", _z(rng, 1 + int(n), cfg.z_dim))
                for n in rng.integers(0, 4, size=6)]
        sup.serve(reqs)
        assert all(r.done for r in reqs)
    assert sup.replica_recompiles == warm, "steady-state serving retraced"
    assert sup.metrics.recompiles == 0       # inline fallback never engaged
    assert sup.conservation()["ok"]


# ------------------------------------------------- per-model metrics

def test_per_model_metrics_attribute_degradation(tiny_dcgan):
    """Two models through one supervisor; faults only hit batches of one
    of them — the per-model labels must attribute retries/latency to the
    right model."""
    cfg_d, params_d = tiny_dcgan
    cfg_g = _tiny(gan.GPGAN)
    params_g = gan.generator_init(jax.random.key(1), cfg_g)

    clock = FakeClock()
    inj = ServeFaultInjector(
        ServeFaultPlan(transient_at=(("r0", 1),)), clock=clock
    )
    replicas = [Replica("r0", dispatch_hook=inj.hook)]
    sup = ReplicaSupervisor(
        replicas,
        BucketPolicy(buckets=(1, 2), max_wait_s=0.0, max_queue=64),
        timeout_s=1.0, clock=clock,
    )
    sup.register(cfg_d, params_d)
    sup.register(cfg_g, params_g)
    sup.warmup()
    rng = np.random.default_rng(19)
    # dcgan is submitted first -> its batch hits the transient fault
    d_reqs = [GenRequest("dcgan", _z(rng, 1, cfg_d.z_dim))
              for _ in range(2)]
    g_reqs = [GenRequest("gpgan", _z(rng, 1, cfg_g.z_dim))
              for _ in range(2)]
    for r in d_reqs:
        sup.submit(r)
        clock.advance(1e-3)
    for r in g_reqs:
        sup.submit(r)
        clock.advance(1e-3)
    while sup.step(drain=True):
        pass
    assert all(r.done for r in d_reqs + g_reqs)
    pm = sup.metrics.summary()["per_model"]
    assert set(pm) == {"dcgan", "gpgan"}
    assert pm["dcgan"]["retries"] >= 1
    assert pm["gpgan"]["retries"] == 0
    assert pm["dcgan"]["requests"] == 2 and pm["gpgan"]["requests"] == 2
    text = sup.metrics.describe()
    assert "[dcgan]" in text and "[gpgan]" in text
    assert sup.conservation()["ok"]


# ---------------------------------------------- conservation (randomized)

def test_conservation_under_randomized_interleaving(tiny_dcgan):
    """Deterministic randomized sweep (the in-container stand-in for the
    hypothesis property in test_property.py): arbitrary interleavings of
    submit / step / clock advance / expiry with injected crash+NaN+hang
    faults end with every admitted request in exactly one terminal state
    and the ledger balanced."""
    cfg, params = tiny_dcgan
    for seed in range(4):
        rng = np.random.default_rng(100 + seed)
        plan = ServeFaultPlan(
            crash_at=(("r0", int(rng.integers(1, 6))),),
            nan_at=(("r1", int(rng.integers(1, 6))),),
            hang_at=(("r1", int(rng.integers(6, 10)), 5.0),),
            revive_after_probes=(("r0", 2),),
        )
        sup, _, clock = make_supervisor(
            cfg, params, plan=plan, max_queue=8,
            degraded_mode=("inline", "shed")[seed % 2],
        )
        from repro.serve import QueueFull

        all_reqs = []
        for _ in range(40):
            op = rng.integers(0, 4)
            if op == 0:
                deadline = (None if rng.integers(0, 2)
                            else float(rng.uniform(0.01, 0.2)))
                r = GenRequest("dcgan",
                               _z(rng, int(rng.integers(1, 4)), cfg.z_dim),
                               deadline_s=deadline)
                all_reqs.append(r)
                try:
                    sup.submit(r)
                except QueueFull:
                    pass
            elif op == 1:
                sup.step()
            elif op == 2:
                clock.advance(float(rng.uniform(0.0, 0.15)))
            else:
                sup.step(drain=True)
        while sup.step(drain=True):
            pass
        sup._purge_expired(sup.clock())

        states = [r.terminal_state for r in all_reqs]
        assert all(s is not None for s in states), (
            f"seed {seed}: unresolved requests {states}"
        )
        from collections import Counter
        c = Counter(states)
        assert len(all_reqs) == (
            c["done"] + c["expired"] + c["rejected"] + c["failed"]
        )
        ledger = sup.conservation()
        assert ledger["ok"], f"seed {seed}: {ledger}"
        assert sup.queued_requests == 0
        # nothing non-finite was ever served
        for r in all_reqs:
            if r.done:
                assert np.isfinite(np.asarray(r.output)).all()
