"""Traffic made from a mix's parameters and ``--seed``.

One general generator reads every mix file (``bench/traffic/<name>.json``):

* ``"kind": "open"`` — independent users: Poisson arrivals at ``rate_rps``
  over the window, request sizes geometric with ``size_p``, capped at
  ``max_size``. Every seed gets the same multiset of gaps and sizes, drawn
  from the mix's own ``base_seed``, in another order, so two seeds offer
  the same work and differ only in when it comes.
* ``"kind": "closed"`` — ``clients`` callers that each wait for their
  reply before sending the next request of ``size`` samples.

Latents are drawn from the seed as well. The program sees only the
generated requests.
"""
from __future__ import annotations

import numpy as np


def stream(seed: int, purpose: int) -> np.random.Generator:
    """An independent generator per (seed, purpose): schedule, latents and
    the correctness sample never share draws."""
    return np.random.default_rng([int(seed), int(purpose)])


def jax_seed(seed: int) -> int:
    """A 32-bit seed for ``jax.random.key`` from any whole-number seed."""
    return int(np.random.SeedSequence(int(seed)).generate_state(1)[0])


def open_schedule(mix: dict, seed: int, seconds: float):
    """``(due_s, sizes)``: arrival offsets in ``(0, seconds]``, ascending,
    and each request's number of samples."""
    n = max(1, round(mix["rate_rps"] * seconds))
    base = np.random.default_rng(mix["base_seed"])
    gaps = base.exponential(1.0, n)
    sizes = np.minimum(base.geometric(mix["size_p"], n), mix["max_size"])
    rng = stream(seed, 0)
    gaps = rng.permutation(gaps)
    sizes = rng.permutation(sizes).astype(np.int64)
    due = np.cumsum(gaps) * (seconds / gaps.sum())
    return due, sizes


def latents(seed: int, sizes, z_dim: int) -> list:
    """One ``(n, z_dim)`` float32 array per request."""
    sizes = np.asarray(sizes, np.int64)
    z = stream(seed, 1).standard_normal((int(sizes.sum()), z_dim),
                                        dtype=np.float32)
    return np.split(z, np.cumsum(sizes)[:-1])


def open_sample(mix: dict, seed: int, sizes) -> np.ndarray:
    """Indices of the requests whose answers are compared: ``check_random``
    drawn from the seed plus the ``check_largest`` largest requests."""
    n = len(sizes)
    rng = stream(seed, 2)
    pick = set(rng.choice(n, min(n, mix["check_random"]), replace=False)
               .tolist())
    order = np.argsort(-np.asarray(sizes), kind="stable")
    pick.update(order[: mix["check_largest"]].tolist())
    return np.array(sorted(pick), np.int64)


def closed_sample(mix: dict, seed: int) -> set:
    """Sequence numbers of the closed-loop requests whose answers are
    compared: the first, and ``check_random`` of the next
    ``check_within`` drawn from the seed."""
    rng = stream(seed, 2)
    rest = rng.choice(np.arange(1, mix["check_within"]),
                      mix["check_random"], replace=False)
    return {0, *rest.tolist()}


class ClosedLatents:
    """Latents for closed-loop requests, drawn in sequence from the seed."""

    def __init__(self, seed: int, size: int, z_dim: int):
        self._rng = stream(seed, 1)
        self.shape = (size, z_dim)

    def next(self) -> np.ndarray:
        return self._rng.standard_normal(self.shape, dtype=np.float32)


def lateness_summary(late_s) -> dict:
    """p50, p99 and max of how late requests were sent, in ms."""
    late = np.asarray(late_s, np.float64) * 1e3
    if late.size == 0:
        return {}
    return {"p50_ms": float(np.percentile(late, 50)),
            "p99_ms": float(np.percentile(late, 99)),
            "max_ms": float(late.max())}
