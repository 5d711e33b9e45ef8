"""Pad rows over all rows the engine dispatched, from ``ServeMetrics``."""


def read(run):
    if not run.rows_padded:
        return None
    return 100.0 * (run.rows_padded - run.rows_real) / run.rows_padded
