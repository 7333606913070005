"""90th percentile of the seconds from ``submit()`` to the certified
result, over every request completed in the window (linear
interpolation between order statistics)."""
import numpy as np


def read(r):
    return float(np.percentile(r.latencies, 90)) if r.latencies else None
