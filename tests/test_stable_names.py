"""Names the program chooses for what a profile shows: each Pallas
transpose-conv kernel carries a ``tconv_`` name (the device operation's
name in a trace), and the engine's whole-generator executable lowers to a
module called ``jit_run``."""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.extend import core as jcore

from repro.kernels import transpose_conv2d as fwd
from repro.kernels import transpose_conv2d_bwd as bwd
from repro.kernels import transpose_conv2d_gemm as gemm
from repro.kernels import transpose_conv2d_pair as pair
from repro.kernels.epilogue import Epilogue
from repro.models import gan
from repro.serve import BucketPolicy, GanEngine

RELU = Epilogue(bias=True, act="relu")
TANH = Epilogue(bias=True, act="tanh")
B, N, CIN, COUT, C2 = 2, 4, 8, 8, 4
M = 2 * N


def s(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.float32)


X, K, BIAS, G = s(B, N, N, CIN), s(4, 4, CIN, COUT), s(COUT), s(B, M, M, COUT)
KERNELS = {
    "tconv_fused": (lambda x, k, b: fwd.transpose_conv2d_pallas(
        x, k, 2, interpret=True, epilogue=RELU, bias=b), (X, K, BIAS)),
    "tconv_phase": (lambda x, k, b: fwd.transpose_conv2d_pallas_phase(
        x, k, 2, interpret=True, epilogue=RELU, bias=b), (X, K, BIAS)),
    "tconv_gemm": (lambda x, k, b: gemm.transpose_conv2d_pallas_gemm(
        x, k, 2, interpret=True, epilogue=RELU, bias=b), (X, K, BIAS)),
    "tconv_dx": (lambda g, k: bwd.transpose_conv2d_dx_pallas(
        g, k, N, 2, interpret=True), (G, K)),
    "tconv_dw": (lambda x, g: bwd.transpose_conv2d_dw_pallas(
        x, g, 4, 2, interpret=True, with_db=True), (X, G)),
    "tconv_epilogue_grad": (lambda g, y: bwd.epilogue_grad_pallas(
        g, y, RELU, interpret=True), (G, G)),
    "tconv_pair": (lambda x, k1, k2, b1, b2: pair.transpose_conv2d_pair_pallas(
        x, k1, k2, 2, interpret=True, epilogue1=RELU, bias1=b1,
        epilogue2=TANH, bias2=b2), (X, K, s(4, 4, COUT, C2), BIAS, s(C2))),
}


def pallas_names(jaxpr) -> list:
    """The names of every ``pallas_call`` in ``jaxpr`` and the jaxprs it
    calls."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append(eqn.params["name"])
            continue
        for v in eqn.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                if isinstance(sub, jcore.ClosedJaxpr):
                    out += pallas_names(sub.jaxpr)
                elif isinstance(sub, jcore.Jaxpr):
                    out += pallas_names(sub)
    return out


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_each_tconv_kernel_carries_its_name(name):
    fn, shapes = KERNELS[name]
    assert pallas_names(jax.make_jaxpr(fn)(*shapes).jaxpr) == [name]


def test_engine_executable_lowers_to_jit_run(monkeypatch, tmp_path):
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path / "at.json"))
    cfg = gan.GANConfig("tiny", 8, ((4, 4, 4), (8, 4, 3)))
    eng = GanEngine(BucketPolicy(buckets=(2,)))
    eng.register(cfg, gan.generator_init(jax.random.key(0), cfg))
    text = eng._executable("tiny", 2).lower(
        eng.registry["tiny"].params, jnp.zeros((2, cfg.z_dim))).as_text()
    module = re.search(r"module @(\S+)", text).group(1)
    assert module.startswith("jit_run"), module
