"""Device milliseconds of the column fetch (``fetch_columns_pallas``: the
columns ADD recruits, read from the row-major design into the engine's
active blocks) per certified solution completed in the traced window."""
from bench import trace


def read(r):
    if r.trace is None or not r.solutions:
        return None
    ev = trace.calls(r.trace.all_ops(), "fetch_columns_pallas")
    return sum(e.dur for e in ev) / 1e6 / r.solutions if ev else None
