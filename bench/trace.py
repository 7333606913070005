"""Reduction of a profiler trace to device busy time, kernel time and the
breakdown of where the window went.

A trace is read with ``jax.profiler.ProfileData`` into plain events
(``Ev``): plane, line, name, start and duration in nanoseconds. Everything
below works on those tuples, so it is tested on hand-made traces as well
as on one recorded on the CPU.

On a TPU the device plane is ``/device:TPU:<i>`` and its line ``XLA Ops``
holds the operations, nested: a ``while`` or ``conditional`` spans the
operations it runs. Busy time is the union of their intervals; an
operation's self time is its duration less that of the operations nested
in it on the same line.
"""
from __future__ import annotations

import collections
from typing import Dict, Iterable, List, NamedTuple, Optional, Sequence, \
    Tuple

import numpy as np

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
WINDOW_SPAN = "bench.window"
# python frames that only wait; a gap is named after what else runs
_WAITING = ("wait", "acquire", "sleep", "result", "join")


class Ev(NamedTuple):
    plane: str
    line: str
    name: str
    start: float           # ns
    dur: float             # ns

    @property
    def end(self) -> float:
        return self.start + self.dur


def load(path: str) -> List[Ev]:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    out = []
    for plane in pd.planes:
        for line in plane.lines:
            for e in line.events:
                out.append(Ev(plane.name, line.name, e.name,
                              float(e.start_ns), float(e.duration_ns)))
    return out


def device_ops(events: Iterable[Ev], plane_prefix: str = DEVICE_PLANE,
               line: str = OPS_LINE) -> Dict[str, List[Ev]]:
    """Operations per device plane (lines whose name starts with
    ``line``), sorted by start."""
    out: Dict[str, List[Ev]] = collections.defaultdict(list)
    for e in events:
        if e.plane.startswith(plane_prefix) and e.line.startswith(line):
            out[e.plane].append(e)
    for evs in out.values():
        evs.sort(key=lambda e: (e.start, -e.dur))
    return dict(out)


def window(events: Iterable[Ev], span: str = WINDOW_SPAN
           ) -> Optional[Tuple[float, float]]:
    """(start, end) of the benchmark's window span, in trace time."""
    for e in events:
        if e.name == span:
            return e.start, e.end
    return None


def clip(evs: Iterable[Ev], lo: float, hi: float) -> List[Ev]:
    """Events overlapping [lo, hi], cut to it."""
    out = []
    for e in evs:
        s, t = max(e.start, lo), min(e.end, hi)
        if t > s:
            out.append(e._replace(start=s, dur=t - s))
    return out


def union(evs: Iterable[Ev]) -> List[Tuple[float, float]]:
    """Merged busy intervals."""
    out: List[List[float]] = []
    for s, t in sorted((e.start, e.end) for e in evs):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], t)
        else:
            out.append([s, t])
    return [(s, t) for s, t in out]


def busy_ns(evs: Iterable[Ev]) -> float:
    return sum(t - s for s, t in union(evs))


def gaps(evs: Iterable[Ev], lo: float, hi: float
         ) -> List[Tuple[float, float]]:
    """Idle intervals of [lo, hi] between the busy ones."""
    out, cur = [], lo
    for s, t in union(evs):
        if s > cur:
            out.append((cur, min(s, hi)))
        cur = max(cur, t)
    if cur < hi:
        out.append((cur, hi))
    return [(s, t) for s, t in out if t > s]


def self_ns(evs: Sequence[Ev]) -> List[Tuple[Ev, float]]:
    """Each event with its self time: its duration less the time of the
    events nested directly in it (events of one line nest or are
    disjoint)."""
    evs = sorted(evs, key=lambda e: (e.start, -e.dur))
    own = [e.dur for e in evs]
    stack: List[int] = []
    for i, e in enumerate(evs):
        while stack and evs[stack[-1]].end <= e.start:
            stack.pop()
        if stack:
            own[stack[-1]] -= e.dur
        stack.append(i)
    return list(zip(evs, own))


def op_label(name: str, width: int = 100) -> str:
    """An operation's name as the breakdown lists it: the HLO instruction
    and its result type, cut to ``width`` characters."""
    return " ".join(name.split())[:width]


def kernel_base(name: str) -> str:
    """``%screen_fused_batch_pallas.1 = (...)`` -> ``screen_fused_batch_
    pallas``: the instruction name without its number."""
    head = name.split(" = ", 1)[0].strip().lstrip("%")
    base, _, num = head.rpartition(".")
    return base if base and num.isdigit() else head


def calls(evs: Iterable[Ev], kernel: str) -> List[Ev]:
    return [e for e in evs if kernel_base(e.name) == kernel]


def top_ops(evs: Sequence[Ev], k: int = 10) -> List[List]:
    """The ``k`` operations with the most self time, summed by label."""
    tot: Dict[str, float] = collections.defaultdict(float)
    for e, own in self_ns(evs):
        tot[op_label(e.name)] += own
    return [[name, ns / 1e9] for name, ns in
            sorted(tot.items(), key=lambda kv: -kv[1])[:k]]


# idle gaps shorter than this are summed under one label and not named
SHORT_GAP_NS = 100e3


class HostIndex:
    """Host events as arrays, to name what the host did at an instant."""

    def __init__(self, host: Sequence[Ev]):
        evs = [e for e in host if e.name != WINDOW_SPAN]
        self.names = [" ".join(e.name.split())[:100] for e in evs]
        self.start = np.array([e.start for e in evs], float)
        self.end = np.array([e.end for e in evs], float)
        self.waiting = np.array([e.name.rstrip().endswith(_WAITING)
                                 for e in evs], bool)

    def at(self, t: float) -> str:
        """The innermost host event covering ``t`` that is not merely
        waiting, else the innermost waiting one."""
        cover = (self.start <= t) & (t < self.end)
        if not cover.any():
            return "no host event"
        pick = cover & ~self.waiting
        if not pick.any():
            pick = cover
        i = np.flatnonzero(pick)
        return self.names[int(i[np.argmin((self.end - self.start)[i])])]


def idle_breakdown(gaps_: Sequence[Tuple[float, float]],
                   host: Sequence[Ev], k: int = 10) -> List[List]:
    """Idle seconds summed by what the host was doing in the middle of
    each gap; the ``k`` largest. Gaps under ``SHORT_GAP_NS`` go under
    one label."""
    idx = HostIndex(host)
    tot: Dict[str, float] = collections.defaultdict(float)
    for s, t in gaps_:
        label = (idx.at(0.5 * (s + t)) if t - s >= SHORT_GAP_NS
                 else f"gaps under {SHORT_GAP_NS / 1e3:g} us")
        tot[label] += t - s
    return [[name, ns / 1e9] for name, ns in
            sorted(tot.items(), key=lambda kv: -kv[1])[:k]]


class Reduced(NamedTuple):
    """One traced window [lo, hi), reduced: per device plane its
    operations inside the window, and the host events."""
    lo: float
    hi: float
    ops: Dict[str, List[Ev]]
    host: List[Ev]

    @property
    def window_ns(self) -> float:
        return self.hi - self.lo

    @property
    def n_devices(self) -> int:
        return len(self.ops)

    def busy_s(self) -> float:
        """Busy seconds, averaged over the devices."""
        if not self.ops:
            return 0.0
        return sum(busy_ns(v) for v in self.ops.values()) / 1e9 / len(
            self.ops)

    def all_ops(self) -> List[Ev]:
        return [e for v in self.ops.values() for e in v]

    def breakdown(self, k: int = 10) -> dict:
        """The operations with the most self time, and the idle time of
        the first device by what the host was doing."""
        ops = self.ops[min(self.ops)] if self.ops else []
        return {"device_ops": top_ops(self.all_ops(), k),
                "idle_gaps": idle_breakdown(gaps(ops, self.lo, self.hi),
                                            self.host, k)}


def reduce(events: Sequence[Ev], plane_prefix: str = DEVICE_PLANE,
           line: str = OPS_LINE) -> Optional[Reduced]:
    """Cut the trace to the benchmark's window span; None without one."""
    w = window(events)
    if w is None:
        return None
    lo, hi = w
    ops = {k: clip(v, lo, hi)
           for k, v in device_ops(events, plane_prefix, line).items()}
    host = [e for e in events if e.plane.startswith(HOST_PLANE)
            and e.end > lo and e.start < hi]
    return Reduced(lo=lo, hi=hi, ops=ops, host=host)
