"""99th percentile of how late the load generator submitted requests
after their due time: the engine's host loop seen from outside."""
import numpy as np


def read(run):
    late = run.lateness_s
    if late is None or len(late) == 0:
        return None
    return float(np.percentile(late, 99)) * 1e3
