"""The work a generator or a training step requires, from shapes alone.

Counts are the algorithm's, not any kernel's: a stride-2 transpose conv
does the segregated MACs (each output element meets only its phase's
sub-kernel), whatever kernel a plan picks, so a change of kernel cannot
move them. Bytes are the least a layer must move: its input, weights and
output, once each.
"""
from __future__ import annotations

import math


def output_size(n_in: int, k: int, padding: int) -> int:
    return 2 * n_in - k + 2 * padding


def tconv_macs(n_in: int, k: int, cin: int, cout: int, padding: int) -> int:
    """Segregated multiply-accumulates of one stride-2 transpose conv, per
    sample: every output element of parity ``(pr, pc)`` meets the
    ``ceil/floor(k/2)``-sized sub-kernel its parity selects."""
    m = output_size(n_in, k, padding)
    total = 0
    for pr in (0, 1):
        for pc in (0, 1):
            kr, kc = (pr + padding) % 2, (pc + padding) % 2
            rows_k = (k + 1) // 2 if kr == 0 else k // 2
            cols_k = (k + 1) // 2 if kc == 0 else k // 2
            rows = (m - pr + 1) // 2
            cols = (m - pc + 1) // 2
            total += rows * cols * rows_k * cols_k * cin * cout
    return total


def generator_layers(cfg: dict):
    """Per layer ``(macs, in_elems, weight_elems, out_elems)`` per sample,
    the projection first."""
    h0, c0, _ = cfg["layers"][0]
    out = [(cfg["z_dim"] * h0 * h0 * c0, cfg["z_dim"],
            cfg["z_dim"] * h0 * h0 * c0, h0 * h0 * c0)]
    for n_in, cin, cout in cfg["layers"]:
        k, p = cfg["kernel"], cfg["padding"]
        m = output_size(n_in, k, p)
        out.append((tconv_macs(n_in, k, cin, cout, p), n_in * n_in * cin,
                    k * k * cin * cout + cout, m * m * cout))
    return out


def generator_flops(cfg: dict) -> int:
    """FLOPs one sample of the generator requires (2 per MAC)."""
    return 2 * sum(layer[0] for layer in generator_layers(cfg))


def generator_least_s(cfg: dict, batch: int, peaks: dict,
                      dtype_bytes: int = 4) -> float:
    """The least time one generator call of ``batch`` samples can take:
    per layer the larger of its FLOPs over the FLOP peak and its bytes
    (input and output of every sample, weights once) over HBM bandwidth."""
    total = 0.0
    for macs, n_in, n_w, n_out in generator_layers(cfg):
        flops = 2 * macs * batch
        nbytes = dtype_bytes * (batch * (n_in + n_out) + n_w)
        total += max(flops / peaks["flops_per_s"],
                     nbytes / peaks["hbm_bytes_per_s"])
    return total


def discriminator_macs(cfg: dict) -> list:
    """Per-sample MACs of each discriminator layer: three 4x4 stride-2
    convs (widths w, 2w, 4w) and the linear head."""
    hw = output_size(cfg["layers"][-1][0], cfg["kernel"], cfg["padding"])
    w = cfg["discriminator_width"]
    chans = [cfg["layers"][-1][2], w, 2 * w, 4 * w]
    macs = []
    for i in range(3):
        hw //= 2
        macs.append(hw * hw * 16 * chans[i] * chans[i + 1])
    macs.append(hw * hw * chans[3])
    return macs


def train_step_flops(cfg: dict, batch: int) -> int:
    """FLOPs one G+D step requires at ``batch``, with no recomputation.

    D phase: G forward; D forward on real and fake; D backward on both
    (weight grads everywhere, input grads below the first layer).
    G phase: G forward; D forward on fake; D input grads everywhere; G
    weight grads everywhere and input grads below the projection."""
    g = [layer[0] for layer in generator_layers(cfg)]
    d = discriminator_macs(cfg)
    g_all, d_all = sum(g), sum(d)
    macs = (4 * g_all - g[0]) + (8 * d_all - 2 * d[0])
    return 2 * macs * batch


def percent(part: float, whole: float) -> float | None:
    if not whole or not math.isfinite(part) or not math.isfinite(whole):
        return None
    return 100.0 * part / whole
