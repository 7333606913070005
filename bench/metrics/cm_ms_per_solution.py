"""Device milliseconds of the coordinate-minimisation burst
(``cm_burst_batch_pallas``) per certified solution completed in the
traced window."""
from bench import trace


def read(r):
    if r.trace is None or not r.solutions:
        return None
    ev = trace.calls(r.trace.all_ops(), "cm_burst_batch_pallas")
    return sum(e.dur for e in ev) / 1e6 / r.solutions if ev else None
