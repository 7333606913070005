"""Host spans of the serving path, on the profiler's own clock.

``span(name, **ids)`` is a ``jax.profiler.TraceAnnotation``: with the
profiler on, its event lands in the trace beside the device operations,
named ``name`` with the ids as event stats; off, it costs about a
microsecond. It keeps its elapsed seconds. ``read(what, fn, x)`` names
one device-to-host read. Importing this module loads no JAX.
"""
from __future__ import annotations

import time

import numpy as np

# every span the program writes; a repro.sync.* span wraps one read
SPANS = (
    "repro.server.submit", "repro.server.coalesce_wait",
    "repro.server.dispatch", "repro.server.resolve",
    "repro.serving.solve", "repro.serving.certify",
    "repro.session.prepare", "repro.engine.run",
    "repro.sync.path_stats", "repro.sync.fleet_stats", "repro.sync.lams",
    "repro.sync.overflow", "repro.sync.beta", "repro.sync.gap",
    "repro.sync.overflowed", "repro.sync.n_outer", "repro.sync.responses",
    "repro.sync.certificate", "repro.sync.result", "repro.sync.digest",
    "repro.sync.warm_start",
)

_annotation = None      # jax.profiler.TraceAnnotation, once imported


class span:
    """``with span("repro.engine.run", b=8) as s: ...; s.elapsed_s``"""
    __slots__ = ("_ann", "_t0", "elapsed_s")

    def __init__(self, name: str, **ids):
        global _annotation
        if _annotation is None:
            from jax.profiler import TraceAnnotation
            _annotation = TraceAnnotation
        self._ann = _annotation(name, **ids)
        self.elapsed_s = 0.0

    def __enter__(self) -> "span":
        self._t0 = time.perf_counter()
        self._ann.__enter__()
        return self

    def __exit__(self, *exc) -> bool:
        self._ann.__exit__(*exc)
        self.elapsed_s = time.perf_counter() - self._t0
        return False


def read(what: str, fn, x):
    """``fn(x)``, inside ``repro.sync.<what>`` when ``x`` is on the device."""
    if isinstance(x, (np.ndarray, np.generic, float, int)):
        return fn(x)
    with span("repro.sync." + what):
        return fn(x)
