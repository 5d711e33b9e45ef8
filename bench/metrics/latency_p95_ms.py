"""95th percentile (nearest rank) of every request due in the window,
timed from its due time; a request with no answer counts with its whole
wait."""
import math

import numpy as np


def read(run):
    lat = run.latencies_s
    if lat is None or len(lat) == 0:
        return None
    lat = np.sort(np.asarray(lat))
    return float(lat[math.ceil(0.95 * len(lat)) - 1]) * 1e3
