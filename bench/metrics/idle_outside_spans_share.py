"""Share of the traced window in which the device is idle and no
``repro.*`` span is open on any thread: idle time no layer of the
program accounts for, averaged over chips."""
from bench import spans


def read(r):
    t = r.trace
    return spans.idle_share(
        r, lambda iv: spans.complement(iv, t.lo, t.hi))
