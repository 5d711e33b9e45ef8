"""Share of the window the host spent copying device arrays to NumPy
(``np.asarray`` of a ``jax.Array``): the serving engine's output copy."""
from harness import trace


def read(run):
    if run.trace is None:
        return None
    s = trace.host_seconds(run.trace, "np.asarray(jax.Array)")
    return None if s is None else 100.0 * s / run.trace.window_s
