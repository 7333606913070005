"""Median seconds from ``submit()`` to the certified result, over every
request completed in the window."""
import numpy as np


def read(r):
    return float(np.percentile(r.latencies, 50)) if r.latencies else None
