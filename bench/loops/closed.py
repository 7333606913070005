"""Closed-loop clients: each client thread sends its next submission when
the last one came back, through ``submit()`` -> ``ServingFuture.result()``,
until ``seconds`` have passed. The window ends when the last request sent
by then completes, so every request in it is whole.
"""
from __future__ import annotations

import dataclasses
import threading
import time
from typing import Any, Callable, Iterator, List, Optional

# a request that has not come back this long after the window closed
# never comes: it counts as failed
LATE_S = 60.0


@dataclasses.dataclass
class Record:
    client: int
    sub: Any                          # traffic.Submission
    t_submit: float
    t_done: float
    result: Any = None
    error: Optional[BaseException] = None


@dataclasses.dataclass
class Window:
    records: List[Record]
    stuck: int                        # clients still waiting at the end
    t0: float
    t1: float


def drive(mix: dict, seed: int, server, streams: List[Iterator[Any]],
          seconds: float, opened: Callable[[], None],
          span: Callable[[str], Any]) -> Window:
    """``streams``: one submission sequence per client (the mix's and
    the seed's; a closed loop takes nothing more from them). ``opened()``
    runs just before the clients start (the profiler, the counters);
    ``span(name)`` is the benchmark's host span."""
    records: List[Record] = []
    lock = threading.Lock()
    go = threading.Barrier(len(streams) + 1)
    clock = {"stop": 0.0}

    def client(c: int) -> None:
        reqs = streams[c]
        go.wait()
        while time.perf_counter() < clock["stop"]:
            sub = next(reqs)
            rec = Record(c, sub, time.perf_counter(), 0.0)
            with span("bench.request"):
                try:
                    rec.result = server.submit(sub.problem, sub.request
                                               ).result(
                        timeout=clock["stop"] + LATE_S - time.perf_counter())
                except Exception as e:  # noqa: BLE001 - counted as failed
                    rec.error = e
            rec.t_done = time.perf_counter()
            with lock:
                records.append(rec)

    threads = [threading.Thread(target=client, args=(c,),
                                name=f"bench-client-{c}", daemon=True)
               for c in range(len(streams))]
    for t in threads:
        t.start()
    opened()
    with span("bench.window"):
        t0 = time.perf_counter()
        clock["stop"] = t0 + seconds
        go.wait()
        for t in threads:
            t.join(seconds + 2 * LATE_S)
        t1 = max([rec.t_done for rec in records] or [time.perf_counter()])
    return Window(records, sum(t.is_alive() for t in threads), t0, t1)
