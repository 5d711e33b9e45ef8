"""Real-mesh sharding parity for plan-served generators.

The tier-1 suite runs on a single host device, where
:func:`repro.distributed.sharding.shard_plan_apply` degrades to the
unsharded path and ``shard_map`` never actually partitions anything. This
file is the real thing: run under
``XLA_FLAGS=--xla_force_host_platform_device_count=4`` (the CI ``mesh``
job does), it builds a genuine 2x2 ``(pod, data)`` device mesh, shards
the batch across all four shards, and checks parity with the unsharded
plan — for per-layer plans AND for plans the megafusion pass rewrote into
:class:`~repro.kernels.plan.FusedPairPlan` entries. Without 4 devices
every test skips (so a plain local ``pytest`` run stays green).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.distributed import sharding as sh
from repro.kernels.plan import FusedPairPlan
from repro.models import gan

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 4,
    reason="needs 4 devices: XLA_FLAGS=--xla_force_host_platform_device_count=4",
)

BATCH = 4


def _mesh22():
    devs = np.array(jax.devices()[:4]).reshape(2, 2)
    return jax.sharding.Mesh(devs, ("pod", "data"))


def _setup(fuse):
    cfg = gan.reduced_config(gan.DCGAN, scale=16)
    params = gan.generator_init(jax.random.key(0), cfg)
    plan = gan.generator_plan(cfg, BATCH, fuse=fuse)
    z = jax.random.normal(jax.random.key(1), (BATCH, cfg.z_dim))

    def apply_fn(p, zz, pl):
        return gan.generator_apply(p, cfg, zz, plan=pl)

    return params, plan, z, apply_fn


def test_mesh_is_really_2x2():
    mesh = _mesh22()
    assert sh.mesh_axis_sizes(mesh) == {"pod": 2, "data": 2}


def test_sharded_parity_per_layer_plan():
    params, plan, z, apply_fn = _setup(fuse="off")
    assert not any(isinstance(e, FusedPairPlan) for e in plan.entries)
    ref = apply_fn(params, z, plan)
    out = sh.shard_plan_apply(apply_fn, params, z, plan, mesh=_mesh22())
    assert out.shape == ref.shape
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=0, atol=1e-6)


def test_sharded_parity_fused_plan():
    params, plan, z, apply_fn = _setup(fuse="force")
    assert any(isinstance(e, FusedPairPlan) for e in plan.entries)
    ref = apply_fn(params, z, plan)
    out = sh.shard_plan_apply(apply_fn, params, z, plan, mesh=_mesh22())
    assert out.shape == ref.shape
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=0, atol=1e-6)


def test_batch_is_actually_partitioned():
    # the output must come back batch-sharded over BOTH data-parallel axes
    # — proof the 2x2 mesh really split the work rather than degrading to
    # the unsharded path
    params, plan, z, apply_fn = _setup(fuse="force")
    mesh = _mesh22()
    out = sh.shard_plan_apply(apply_fn, params, z, plan, mesh=mesh)
    sharding = out.sharding
    assert isinstance(sharding, jax.sharding.NamedSharding)
    spec0 = sharding.spec[0]
    assert spec0 in (("pod", "data"), ["pod", "data"], "pod")
    assert len(out.addressable_shards) == 4
    assert out.addressable_shards[0].data.shape[0] == BATCH // 4


def test_active_mesh_is_picked_up():
    # mesh=None + an active jax.set_mesh context: shard_plan_apply must find
    # the ambient mesh instead of degrading
    params, plan, z, apply_fn = _setup(fuse="off")
    ref = apply_fn(params, z, plan)
    with jax.set_mesh(_mesh22()):
        out = sh.shard_plan_apply(apply_fn, params, z, plan)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=0, atol=1e-6)


def test_nondivisible_batch_degrades_unsharded():
    params, plan, _, apply_fn = _setup(fuse="off")
    z3 = jax.random.normal(jax.random.key(2), (3, 100))
    out = sh.shard_plan_apply(apply_fn, params, z3, plan, mesh=_mesh22())
    assert out.shape[0] == 3  # ran, unsharded (3 % 4 != 0)


def test_sharded_matches_jnp_reference_composition():
    # end-to-end sanity: the sharded fused plan agrees with the plain
    # unfused plan too (different summation order -> tolerance, not bitwise)
    params, plan_f, z, apply_fn = _setup(fuse="force")
    _, plan_u, _, _ = _setup(fuse="off")
    out_f = sh.shard_plan_apply(apply_fn, params, z, plan_f, mesh=_mesh22())
    out_u = apply_fn(params, z, plan_u)
    np.testing.assert_allclose(np.asarray(out_f), np.asarray(out_u),
                               rtol=1e-5, atol=1e-5)
