"""Device-to-host reads per certified solution: the ``repro.sync.*``
spans (one around each read of the serving path) that start in the
traced window."""
from bench import spans


def read(r):
    if not spans.readable(r) or not r.solutions:
        return None
    t = r.trace
    n = sum(1 for e in t.host if e.name.startswith(spans.SYNC)
            and t.lo <= e.start < t.hi)
    return n / r.solutions
