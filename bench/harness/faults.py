"""Faults planted under the timed path, to show that ``correct`` catches
them. Only the control script and the tests use these; a benchmark run
never does."""
from __future__ import annotations

import contextlib

import numpy as np

from harness import system


@contextlib.contextmanager
def _patched_finalize(alter):
    system.import_program()
    from repro.serve.gan_engine import GanEngine

    orig = GanEngine._finalize

    def finalize(self, name, reqs, out, n_real, bucket, t0, **kw):
        out = np.array(out)
        alter(out, n_real)
        return orig(self, name, reqs, out, n_real, bucket, t0, **kw)

    GanEngine._finalize = finalize
    try:
        yield
    finally:
        GanEngine._finalize = orig


def serve_altered():
    """One answer altered where it is produced: the first sample of every
    dispatch comes out 10% smaller."""
    def alter(out, n_real):
        out[0] *= 0.9
    return _patched_finalize(alter)


def serve_half():
    """Half of every batch left out: its later half of real rows is never
    computed (zeros)."""
    def alter(out, n_real):
        out[n_real // 2:n_real] = 0.0
    return _patched_finalize(alter)


def train_half(trainer) -> None:
    """Half of the batch left out, the mean taken over the rest: the
    step's second half repeats its first half."""
    import jax.numpy as jnp

    orig = trainer._batches

    def batches(step):
        reals, zs = orig(step)
        h = reals.shape[1] // 2
        return (jnp.concatenate([reals[:, :h], reals[:, :h]], axis=1),
                jnp.concatenate([zs[:, :h], zs[:, :h]], axis=1))

    trainer._batches = batches


def train_unchanged(trainer) -> None:
    """A step that returns its state unchanged (and reports zero losses)."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def step(state, reals, zs):
        zero = jnp.zeros((), jnp.float32)
        return state, {"g_loss": zero, "d_loss": zero, "g_gnorm": zero,
                       "d_gnorm": zero, "skipped": jnp.zeros((), jnp.int32)}

    trainer._step_fn = step
