"""Compile every dispatchable Pallas kernel for a TPU v5e at real widths.

Interpret mode (every other kernel test) cannot see what the TPU's kernel
compiler refuses: misaligned blocks and offsets, unsupported primitives,
more VMEM than a kernel may use. Here each kernel is lowered with
``interpret=False`` for one chip of a described (not attached) ``v5e:2x2``
and compiled; the compiled module must hold the kernel as a
``tpu_custom_call``. Shapes are the Table-4 zoo layers at batch 8, fp32,
with the generator's bias+relu epilogue.

The whole-plane kernels (phase, implicit GEMM, layer pair) grow with the
spatial plane; where their VMEM estimate exceeds the budget they are no
dispatch or autotune candidate, which the screen tests pin. The largest
layer each screen admits is among the compiled cases.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and it holds it until it exits.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import plan as planlib
from repro.kernels import transpose_conv2d as fwd
from repro.kernels import transpose_conv2d_bwd as bwd
from repro.kernels import transpose_conv2d_gemm as gemm
from repro.kernels import transpose_conv2d_pair as pair
from repro.kernels.epilogue import Epilogue
from repro.models import gan

B = 8
RELU = Epilogue(bias=True, act="relu")
TANH = Epilogue(bias=True, act="tanh")
ZOO = {"dcgan": gan.DCGAN, "ebgan": gan.EBGAN}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described chip, with the persistent compilation cache off: an
    entry compiled for a described chip cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _layer(name, i):
    hw, cin, cout = ZOO[name].layers[i]
    return hw, cin, cout, 2 * hw  # 4x4 kernels, padding 2: M = 2N


def _case(kind, name, i, chip):
    """(fn, argument shapes) of one kernel call at zoo layer ``i``."""
    def s(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=chip)

    hw, cin, cout, m = _layer(name, i)
    x, k, b = s(B, hw, hw, cin), s(4, 4, cin, cout), s(cout)
    g = s(B, m, m, cout)
    if kind == "fused":
        return (lambda x, k, b: fwd.transpose_conv2d_pallas(
            x, k, 2, interpret=False, epilogue=RELU, bias=b)), (x, k, b)
    if kind == "phase":
        return (lambda x, k, b: fwd.transpose_conv2d_pallas_phase(
            x, k, 2, interpret=False, epilogue=RELU, bias=b)), (x, k, b)
    if kind == "gemm":
        return (lambda x, k, b: gemm.transpose_conv2d_pallas_gemm(
            x, k, 2, interpret=False, epilogue=RELU, bias=b)), (x, k, b)
    if kind == "dx":
        return (lambda g, k: bwd.transpose_conv2d_dx_pallas(
            g, k, hw, 2, interpret=False)), (g, k)
    if kind == "dw":
        return (lambda x, g: bwd.transpose_conv2d_dw_pallas(
            x, g, 4, 2, interpret=False, with_db=True)), (x, g)
    if kind == "epilogue_grad":
        return (lambda g, y: bwd.epilogue_grad_pallas(
            g, y, RELU, interpret=False)), (g, g)
    if kind == "pair":
        _, _, c2, _ = _layer(name, i + 1)
        return (lambda x, k1, k2, b1, b2: pair.transpose_conv2d_pair_pallas(
            x, k1, k2, 2, interpret=False, epilogue1=RELU, bias1=b1,
            epilogue2=TANH, bias2=b2,
        )), (x, k, s(4, 4, cout, c2), b, s(c2))
    raise ValueError(kind)


@pytest.mark.parametrize("kind,name,layer", [
    ("fused", "dcgan", 0), ("fused", "ebgan", 0), ("fused", "ebgan", 5),
    ("phase", "dcgan", 0), ("phase", "ebgan", 0),
    ("phase", "ebgan", 4),   # the largest layer the VMEM screen admits
    ("gemm", "dcgan", 0), ("gemm", "ebgan", 0),
    ("gemm", "dcgan", 3),    # the largest layer the VMEM screen admits
    ("dx", "dcgan", 0), ("dx", "dcgan", 3),
    ("dw", "dcgan", 0), ("dw", "dcgan", 3),
    ("epilogue_grad", "dcgan", 0), ("epilogue_grad", "dcgan", 3),
    ("pair", "dcgan", 0),
    ("pair", "ebgan", 2),    # the largest pair the VMEM screen admits
])
def test_kernel_compiles_for_v5e(one_chip, kind, name, layer):
    fn, shapes = _case(kind, name, layer, one_chip)
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("method,name,layer,fits", [
    ("pallas_phase", "ebgan", 4, True), ("pallas_phase", "ebgan", 5, False),
    ("pallas_gemm", "dcgan", 3, True), ("pallas_gemm", "ebgan", 3, False),
    ("pallas_gemm", "ebgan", 5, False),
    ("pallas_fused", "ebgan", 5, True),  # spatially tiled: never screened
])
def test_vmem_screen_of_whole_plane_kernels(method, name, layer, fits):
    hw, cin, cout, _ = _layer(name, layer)
    assert planlib.fwd_fits_vmem(method, B, hw, 4, cin, cout, 2) is fits
    if not fits:  # not dispatchable: pinning it is refused at plan time
        with pytest.raises(ValueError, match="VMEM"):
            planlib.plan_layer(B, hw, 4, cin, cout, 2, method=method)


@pytest.mark.parametrize("name,first,legal", [
    ("dcgan", 0, True), ("ebgan", 2, True),
    ("ebgan", 3, False), ("ebgan", 4, False),
])
def test_vmem_screen_of_layer_pairs(name, first, legal):
    cfg = ZOO[name]
    lps = [
        planlib.plan_layer(B, hw, 4, cin, cout, 2, epilogue=RELU)
        for hw, cin, cout in cfg.layers[first:first + 2]
    ]
    ok, why = planlib.pair_legal(*lps)
    assert ok is legal, why
