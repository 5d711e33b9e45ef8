"""Parallelism-mode switch (tp vs fsdp/ZeRO-3) and attribution tooling."""
import jax
from jax.sharding import PartitionSpec as P
import pytest

from repro.configs import get_config
from repro.distributed import sharding as sh
from repro.models.lm import build_model


@pytest.fixture
def fake_mesh(monkeypatch):
    mesh = jax.sharding.AbstractMesh((16, 16), ("data", "model"))
    monkeypatch.setattr(
        jax.sharding, "get_abstract_mesh", lambda: mesh, raising=False
    )
    yield mesh
    sh.set_parallelism("tp")


def test_fsdp_mode_param_specs(fake_mesh):
    sh.set_parallelism("fsdp")
    try:
        cfg = get_config("llama3-8b")
        params = build_model(cfg).abstract_params()
        specs = sh.param_specs(params, False)
        # every big matrix sharded over (data, model); no TP axis anywhere
        assert specs["embed"]["w"] == P(("data", "model"), None)
        l0 = specs["layers"][0]
        assert l0["mixer"]["attn"]["wq"]["w"] == P(
            None, ("data", "model"), None
        )
        flat = jax.tree_util.tree_leaves(
            specs, is_leaf=lambda s: isinstance(s, P)
        )
        for s in flat:
            assert "model" not in [e for e in s if isinstance(e, str)], s
    finally:
        sh.set_parallelism("tp")


def test_fsdp_mode_widens_batch_and_drops_tp(fake_mesh):
    sh.set_parallelism("fsdp")
    try:
        # BATCH entries widen to include model; bare MODEL entries drop
        spec = sh._filter(P(sh.BATCH, None, sh.MODEL), (256, 4, 64))
        assert spec == P(("data", "model"), None, None)
    finally:
        sh.set_parallelism("tp")


def test_tp_mode_default(fake_mesh):
    assert sh.get_parallelism() == "tp"
    spec = sh._filter(P(sh.BATCH, None, sh.MODEL), (256, 4, 64))
    assert spec == P(("data",), None, "model")


def test_attribution_parses_collectives():
    from repro.launch.attribution import collective_items

    hlo = '''
HloModule m

ENTRY %main (a: f32[16,16]) -> f32[16,16] {
  %a = f32[16,16]{1,0} parameter(0)
  ROOT %ar = f32[16,16]{1,0} all-reduce(%a), replica_groups={}, metadata={op_name="jit(f)/psum"}
}
'''
    items = collective_items(hlo)
    assert len(items) == 1
    bytes_, op, _, mult, name = items[0]
    assert op == "all-reduce" and bytes_ == 16 * 16 * 4 * 2 and mult == 1
    assert "psum" in name
