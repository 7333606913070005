"""The program's own host spans (``repro.*``, written by
``src/repro/runtime/spans.py``) in a reduced trace: cut to the window,
merged, and set against the device's idle gaps.

Every function returns None where there is nothing to read: no trace, no
device plane (a CPU run), or no span of the program in the window (a
program that writes none).
"""
from __future__ import annotations

from typing import Callable, List, Optional, Sequence, Tuple

from bench import trace

PREFIX = "repro."
SYNC = "repro.sync."

Intervals = List[Tuple[float, float]]


def readable(r) -> bool:
    return (r.trace is not None and bool(r.trace.n_devices)
            and any(e.name.startswith(PREFIX) for e in r.trace.host))


def spans(r, keep: Callable[[str], bool]) -> Optional[Intervals]:
    """Merged intervals of the program's spans whose name ``keep``s,
    clipped to the window; None where nothing can be read."""
    if not readable(r):
        return None
    t = r.trace
    evs = [e for e in t.host if e.name.startswith(PREFIX) and keep(e.name)]
    return trace.union(trace.clip(evs, t.lo, t.hi))


def overlap_ns(a: Sequence[Tuple[float, float]],
               b: Sequence[Tuple[float, float]]) -> float:
    """Length of the intersection of two merged interval lists."""
    out, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        out += max(hi - lo, 0.0)
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def ms_per_solution(r, name: str) -> Optional[float]:
    """Window milliseconds inside the span ``name``, per solution."""
    iv = spans(r, lambda n: n == name)
    if not iv or not r.solutions:
        return None
    return sum(t - s for s, t in iv) / 1e6 / r.solutions


def idle_share(r, where: Callable[[Intervals], Intervals]
               ) -> Optional[float]:
    """Percent of the window in which the device is idle and inside
    ``where(program spans)``, averaged over the devices."""
    iv = spans(r, lambda n: True)
    if iv is None:
        return None
    t = r.trace
    inside = where(iv)
    idle = [overlap_ns(trace.gaps(ops, t.lo, t.hi), inside)
            for ops in t.ops.values()]
    return 100.0 * sum(idle) / len(idle) / t.window_ns


def complement(iv: Intervals, lo: float, hi: float) -> Intervals:
    """[lo, hi) less the merged intervals ``iv``."""
    out, cur = [], lo
    for s, t in iv:
        if s > cur:
            out.append((cur, s))
        cur = max(cur, t)
    if cur < hi:
        out.append((cur, hi))
    return out
