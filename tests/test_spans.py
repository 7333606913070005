"""Spans and counters of the serving path (repro.runtime.spans).

A CPU profiler trace of ``open_server`` at a tiny size -- one Scalar from
one client, then a coalesced fleet of two -- must show every span the
path reaches, nested as the layers call each other; each request's
``submit`` linked to its ``dispatch`` by id; and every host read of a
device array on the dispatch thread inside a ``repro.sync.*`` span.
"""
import contextlib
import glob
import threading
import time
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.api import Problem, Scalar
from repro.core.saif import SaifConfig
from repro.core.server import open_server
from repro.runtime.spans import SPANS, span

from conftest import make_regression

N, P = 40, 64
WAIT_MS = 150.0
VALUE_FRAME = "_value"      # jax.Array._value: the Python frame of a read


class Ev:
    def __init__(self, line, e):
        self.line = line
        self.name = e.name
        self.start = float(e.start_ns)
        self.end = self.start + float(e.duration_ns)
        self.stats = dict(e.stats)

    def within(self, o) -> bool:
        return o.start <= self.start and self.end <= o.end


@contextlib.contextmanager
def _reads_through_value():
    """On the TPU numpy reads a jax array through ``jax.Array.__array__``
    and so through ``_value``, a frame the Python tracer records; on the
    CPU numpy takes the buffer protocol and no frame shows. Route numpy's
    reads of jax arrays through ``__array__`` here as on the TPU."""
    real = {"asarray": np.asarray, "array": np.array}

    def via_value(fn):
        def read(a, *args, **kw):
            if isinstance(a, jax.Array):
                a = a.__array__()
            return fn(a, *args, **kw)
        return read

    for name, fn in real.items():
        setattr(np, name, via_value(fn))
    try:
        yield
    finally:
        for name, fn in real.items():
            setattr(np, name, fn)


def _serve(problems, lams, max_wait_ms):
    """One Scalar from one client, then two Scalars that coalesce."""
    server = open_server(solver=SaifConfig(), max_batch=2,
                         max_wait_ms=max_wait_ms)
    try:
        one = server.submit(problems[0], Scalar(lams[0])).result(
            timeout=120)
        futs = [server.submit(pr, Scalar(lam))
                for pr, lam in zip(problems[1:], lams[1:])]
        two = [f.result(timeout=120) for f in futs]
        return [one] + two, server.stats()
    finally:
        server.close()


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    rng = np.random.default_rng(7)
    X, y, _ = make_regression(rng, n=N, p=P)
    X = jnp.asarray(X)
    ys = [y] + [y + 0.1 * rng.standard_normal(N) for _ in range(2)]
    problems = [Problem(X=X, y=jnp.asarray(yy)) for yy in ys]
    lam_max = float(jnp.max(jnp.abs(X.T @ jnp.asarray(y))))
    lams = [f * lam_max for f in (0.5, 0.5, 0.3)]
    _serve(problems, lams, WAIT_MS)     # compile every program once
    d = str(tmp_path_factory.mktemp("trace"))
    with _reads_through_value():
        jax.profiler.start_trace(d)
        try:
            results, stats = _serve(problems, lams, WAIT_MS)
        finally:
            jax.profiler.stop_trace()
    from jax.profiler import ProfileData
    path = glob.glob(f"{d}/**/*.xplane.pb", recursive=True)[0]
    with warnings.catch_warnings():      # the stats' pybind type warns
        warnings.simplefilter("ignore", DeprecationWarning)
        # one line per thread; Python threads' lines share a name
        evs = [Ev((i, j), e)
               for i, plane in enumerate(ProfileData.from_file(path).planes)
               if plane.name.startswith("/host")
               for j, line in enumerate(plane.lines)
               for e in line.events]
    return results, stats, evs


def _named(evs, name):
    return [e for e in evs if e.name == name]


def _inside(e, outer, evs):
    return any(e.line == o.line and e.within(o) for o in _named(evs, outer))


def test_every_name_reached_is_listed_and_appears(traced):
    _, _, evs = traced
    seen = {e.name for e in evs if e.name.startswith("repro.")}
    assert seen <= set(SPANS)
    assert {"repro.server.submit", "repro.server.coalesce_wait",
            "repro.server.dispatch", "repro.server.resolve",
            "repro.serving.solve", "repro.serving.certify",
            "repro.session.prepare", "repro.engine.run",
            "repro.sync.path_stats", "repro.sync.fleet_stats",
            "repro.sync.lams", "repro.sync.overflow", "repro.sync.beta",
            "repro.sync.certificate", "repro.sync.result"} <= seen


def test_spans_nest_as_the_layers_call(traced):
    _, _, evs = traced
    dispatch = _named(evs, "repro.server.dispatch")
    assert len(dispatch) == 2
    assert [int(d.stats["b"]) for d in dispatch] == [1, 2]
    for name, outer in (("repro.serving.solve", "repro.server.dispatch"),
                        ("repro.server.resolve", "repro.server.dispatch"),
                        ("repro.session.prepare", "repro.server.dispatch"),
                        ("repro.engine.run", "repro.serving.solve"),
                        ("repro.serving.certify", "repro.serving.solve"),
                        ("repro.sync.overflow", "repro.engine.run"),
                        ("repro.sync.certificate", "repro.serving.certify")):
        inner = _named(evs, name)
        assert inner, name
        assert all(_inside(e, outer, evs) for e in inner), (name, outer)
    # every dispatch runs one solve, one engine run and one certificate
    for d in dispatch:
        for name in ("repro.serving.solve", "repro.engine.run",
                     "repro.serving.certify", "repro.server.resolve"):
            assert sum(e.line == d.line and e.within(d)
                       for e in _named(evs, name)) == 1, name


def test_submit_ids_link_to_their_dispatch(traced):
    _, _, evs = traced
    submits = _named(evs, "repro.server.submit")
    assert len(submits) == 3
    riders = [set(str(d.stats["reqs"]).split())
              for d in _named(evs, "repro.server.dispatch")]
    assert [len(r) for r in riders] == [1, 2]
    for s in submits:
        assert sum(str(s.stats["req"]) in r for r in riders) == 1
    # the submits run on the client thread, the dispatches on the server's
    assert {s.line for s in submits}.isdisjoint(
        {d.line for d in _named(evs, "repro.server.dispatch")})


def test_every_host_read_on_the_dispatch_thread_is_named(traced):
    _, _, evs = traced
    dispatch = _named(evs, "repro.server.dispatch")
    reads = [e for e in evs
             if e.name.endswith(" " + VALUE_FRAME) and "array.py" in e.name
             and any(e.line == d.line and e.within(d) for d in dispatch)]
    # the check is not vacuous: the fleet's reads alone are a dozen
    assert len(reads) >= 12
    syncs = [e for e in evs if e.name.startswith("repro.sync.")]
    unnamed = [r for r in reads
               if not any(r.line == s.line and r.within(s) for s in syncs)]
    assert not unnamed, [(r.name, r.start) for r in unnamed]


def test_server_counts_dispatched_requests_and_their_wait(traced):
    _, stats, _ = traced
    assert stats.submitted == stats.dispatched == stats.served == 3
    # the lone Scalar held the coalescing window open for its whole length
    assert WAIT_MS / 1e3 <= stats.queue_wait_s < 60.0


def test_kkt_check_ms_is_the_certify_span(traced):
    results, _, evs = traced
    certify = _named(evs, "repro.serving.certify")
    assert len(certify) == 2
    got = [results[0].verdict.kkt_check_ms, results[1].verdict.kkt_check_ms]
    assert results[1].verdict.kkt_check_ms == \
        results[2].verdict.kkt_check_ms        # riders share the fleet's
    for ms, ev in zip(got, certify):
        # one interval on two clocks: the profiler's and perf_counter's
        span_ms = (ev.end - ev.start) / 1e6
        assert ms == pytest.approx(span_ms, rel=0.01, abs=0.05)


def test_span_keeps_its_elapsed_time_and_costs_little_when_off():
    with span("repro.engine.run", b=1) as s:
        threading.Event().wait(0.01)
    assert s.elapsed_s >= 0.01
    t0 = time.perf_counter()
    for _ in range(1000):
        with span("repro.server.submit", req=7):
            pass
    assert (time.perf_counter() - t0) / 1000 < 50e-6
