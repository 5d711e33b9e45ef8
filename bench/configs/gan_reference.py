"""Plain reference of the GAN configurations in this directory.

It follows the paper's Algorithm 1 (arXiv:2502.20493): each stride-2
transpose convolution upsamples its input bed-of-nails style (zeros
between rows and columns), pads it by ``padding`` and runs one dense
``kernel x kernel`` correlation, so a ``N x N`` map becomes
``2N - kernel + 2 * padding``. The generator projects ``z`` to the first
map, then applies every layer with its bias, ``relu`` in the middle and
``tanh`` at the end. The discriminator is three 4x4 stride-2 convolutions
(padding 1, leaky relu 0.2) and a linear head; training is the
non-saturating GAN loss with AdamW (global-norm clipping per network),
the discriminator stepping first and the generator against the updated
discriminator.

It imports nothing of the system under test. Weights are made here, from
the seed, in the tree layout the system takes. Everything runs in float32
at ``highest`` matmul precision unless asked otherwise: the generator also
at ``default`` precision (each product of bfloat16-rounded inputs, summed
in float32), and in bfloat16, with the last layer's output in float32
when asked (the controls); training also at ``default`` precision and in
mixed precision (float32 weights, bfloat16 passes).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST
_DN = ("NHWC", "HWIO", "NHWC")


# ---------------------------------------------------------------- weights

def init_generator(key, cfg: dict) -> dict:
    """Projection and transpose-conv weights, fan-in scaled, with small
    random biases so that a dropped bias shows."""
    layers = cfg["layers"]
    h0, c0, _ = layers[0]
    k = cfg["kernel"]
    ks = jax.random.split(key, 2 * len(layers) + 1)
    params = {"proj": {"w": 0.02 * jax.random.normal(
        ks[0], (cfg["z_dim"], h0 * h0 * c0))}}
    for i, (_, cin, cout) in enumerate(layers):
        params[f"tconv{i}"] = {
            "w": jax.random.normal(ks[2 * i + 1], (k, k, cin, cout))
            * (k * k * cin) ** -0.5,
            "b": 0.02 * jax.random.normal(ks[2 * i + 2], (cout,)),
        }
    return params


def init_discriminator(key, cfg: dict) -> dict:
    hw = 2 * cfg["layers"][-1][0] - cfg["kernel"] + 2 * cfg["padding"]
    w = cfg["discriminator_width"]
    chans = [cfg["layers"][-1][2], w, 2 * w, 4 * w]
    ks = jax.random.split(key, 4)
    params = {f"conv{i}": {"w": jax.random.normal(
        ks[i], (4, 4, chans[i], chans[i + 1])) * (16 * chans[i]) ** -0.5}
        for i in range(3)}
    params["head"] = {"w": 0.02 * jax.random.normal(
        ks[3], ((hw // 8) ** 2 * chans[3], 1))}
    return params


# ---------------------------------------------------------------- forward

def _precision(x, precision=HIGHEST):
    return precision if x.dtype == jnp.float32 else None


def transpose_conv(x, w, b, padding: int, precision=HIGHEST,
                   out_dtype=None):
    """Algorithm 1: bed-of-nails upsample (one zero between neighbours),
    pad by ``padding``, one dense correlation. ``out_dtype`` sums the
    products in that type and adds the bias there."""
    zero = jnp.zeros((), x.dtype)
    up = lax.pad(x, zero, [(0, 0, 0), (padding, padding, 1),
                           (padding, padding, 1), (0, 0, 0)])
    y = lax.conv_general_dilated(up, w, (1, 1), "VALID",
                                 dimension_numbers=_DN,
                                 precision=_precision(x, precision),
                                 preferred_element_type=out_dtype)
    return y + b.astype(y.dtype)


def generate(params: dict, cfg: dict, z, precision=HIGHEST,
             out_dtype=None):
    """The generator in the type of ``z`` and ``params``; ``out_dtype``
    finishes the last layer (sum, bias, ``tanh``) in that type."""
    h0, c0, _ = cfg["layers"][0]
    x = jnp.dot(z, params["proj"]["w"], precision=_precision(z, precision))
    x = jax.nn.relu(x.reshape(z.shape[0], h0, h0, c0))
    last = len(cfg["layers"]) - 1
    for i in range(len(cfg["layers"])):
        p = params[f"tconv{i}"]
        x = transpose_conv(x, p["w"], p["b"], cfg["padding"], precision,
                           out_dtype if i == last else None)
        x = jnp.tanh(x) if i == last else jax.nn.relu(x)
    return x


def discriminate(params: dict, x, precision=HIGHEST):
    for i in range(3):
        x = lax.conv_general_dilated(x, params[f"conv{i}"]["w"], (2, 2),
                                     [(1, 1), (1, 1)], dimension_numbers=_DN,
                                     precision=_precision(x, precision))
        x = jax.nn.leaky_relu(x, 0.2)
    x = x.reshape(x.shape[0], -1)
    return jnp.dot(x, params["head"]["w"],
                   precision=_precision(x, precision))[:, 0]


# ---------------------------------------------------------------- training

def _cast(tree, dtype):
    return jax.tree_util.tree_map(lambda a: a.astype(dtype), tree)


def _logits(dp, x, precision, compute):
    """D's logits: in the type of ``x`` and the weights, or computed in
    the type ``compute`` and returned in float32."""
    if compute is None:
        return discriminate(dp, x, precision)
    return discriminate(_cast(dp, compute), x.astype(compute),
                        precision).astype(jnp.float32)


def _fake(gp, cfg, z, precision, compute):
    if compute is not None:
        gp, z = _cast(gp, compute), z.astype(compute)
    return generate(gp, cfg, z, precision)


def _d_loss(dp, gp, cfg, real, z, precision, compute):
    fake = _fake(gp, cfg, z, precision, compute)
    return (jnp.mean(jax.nn.softplus(-_logits(dp, real, precision, compute)))
            + jnp.mean(jax.nn.softplus(_logits(dp, fake, precision,
                                               compute))))


def _g_loss(gp, dp, cfg, z, precision, compute):
    fake = _fake(gp, cfg, z, precision, compute)
    return jnp.mean(jax.nn.softplus(-_logits(dp, fake, precision, compute)))


def _adamw(params, grads, m, v, count, opt):
    """One AdamW step of one network: clip by the global norm, update the
    moments in float32, step each leaf. Returns the clipped grads too."""
    leaves = jax.tree_util.tree_leaves(grads)
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                         for g in leaves))
    scale = jnp.minimum(1.0, opt["clip_norm"] / jnp.maximum(gnorm, 1e-9))
    b1, b2 = opt["b1"], opt["b2"]
    bc1, bc2 = 1 - b1 ** count, 1 - b2 ** count
    tm = jax.tree_util.tree_map
    g = tm(lambda g: g.astype(jnp.float32) * scale, grads)
    m = tm(lambda m, g: b1 * m + (1 - b1) * g, m, g)
    v = tm(lambda v, g: b2 * v + (1 - b2) * g * g, v, g)

    def step(p, m, v):
        upd = (m / bc1) / (jnp.sqrt(v / bc2) + opt["eps"])
        if p.ndim >= 2:
            upd = upd + opt["weight_decay"] * p.astype(jnp.float32)
        return (p.astype(jnp.float32) - opt["lr"] * upd).astype(p.dtype)

    return tm(step, params, m, v), m, v, g


def train(gp, dp, cfg: dict, opt: dict, batches, dtype=jnp.float32,
          precision=HIGHEST, compute=None):
    """Run one step per ``(real, z)`` in ``batches`` from ``(gp, dp)``.

    Parameters, moments and updates are kept in ``dtype``; the forward
    and backward passes run in ``compute`` where given (a mixed-precision
    step: ``dtype`` float32, ``compute`` bfloat16, losses in float32).

    Returns ``(losses, first_grads, gp, dp)``: per step ``(g_loss,
    d_loss)``, the clipped gradients of the first step as the optimizer
    takes them (``{"g": ..., "d": ...}``), and the parameters after the
    last step."""
    tm = jax.tree_util.tree_map
    cast = lambda t: tm(lambda a: a.astype(dtype), t)  # noqa: E731
    gp, dp = cast(gp), cast(dp)
    zeros = lambda t: tm(lambda a: jnp.zeros(a.shape, jnp.float32), t)  # noqa
    gm, gv, dm, dv = zeros(gp), zeros(gp), zeros(dp), zeros(dp)

    @jax.jit
    def step(gp, dp, gm, gv, dm, dv, count, real, z):
        real, z = real.astype(dtype), z.astype(dtype)
        dl, dg = jax.value_and_grad(_d_loss)(dp, gp, cfg, real, z,
                                             precision, compute)
        dp, dm, dv, dg = _adamw(dp, dg, dm, dv, count, opt)
        gl, gg = jax.value_and_grad(_g_loss)(gp, dp, cfg, z, precision,
                                             compute)
        gp, gm, gv, gg = _adamw(gp, gg, gm, gv, count, opt)
        return gp, dp, gm, gv, dm, dv, gl, dl, gg, dg

    losses, first = [], None
    for t, (real, z) in enumerate(batches, start=1):
        gp, dp, gm, gv, dm, dv, gl, dl, gg, dg = step(
            gp, dp, gm, gv, dm, dv, jnp.float32(t), real, z)
        losses.append((float(gl), float(dl)))
        if first is None:
            first = {"g": gg, "d": dg}
    return losses, first, gp, dp
