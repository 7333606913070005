"""The readers of the program's spans and counters (bench/spans.py and
the metrics that use it), on hand-made reduced traces."""
import sys
import pathlib
import types

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

import bench  # noqa: E402
from bench import spans, trace  # noqa: E402
from bench.trace import Ev  # noqa: E402

US = 1e3    # ns per microsecond
DEV, OPS, HOST = "/device:TPU:0", "XLA Ops", "/host:CPU"
TRACED = ("prep_ms_per_solution", "engine_ms_per_solution",
          "certify_ms_per_solution", "host_syncs_per_solution",
          "idle_in_sync_share", "idle_outside_spans_share")


def _ev(name, s, t, plane=HOST, line="python"):
    return Ev(plane, line, name, s * US, (t - s) * US)


def _hand(extra_devices=0):
    """Window [0, 1000) us. Device busy [100, 300), [400, 500),
    [700, 900). Program spans: prepare [-50, 80) (cut at the window's
    start), dispatch [50, 950) holding engine.run [90, 520) with its
    overflow read [480, 520) and certify [550, 950) with two reads;
    resolve [955, 1100) and a result read [960, 1100) (cut at the end);
    a lams read [-20, 10) that starts before the window; a submit on
    another thread; a read after the window."""
    ops = {DEV: [_ev("%while.1 = (s32[2])", 100, 300, DEV, OPS),
                 _ev("%copy.7 = f32[8,8]", 400, 500, DEV, OPS),
                 _ev("%fusion.3 = f32[8]", 700, 900, DEV, OPS)]}
    for i in range(extra_devices):           # a chip busy all the window
        d = f"/device:TPU:{i + 1}"
        ops[d] = [_ev("%while.1 = (s32[2])", 0, 1000, d, OPS)]
    host = [_ev("$server.py:580 _dispatch", 0, 1000),
            _ev("repro.session.prepare", -50, 80),
            _ev("repro.sync.lams", -20, 10),
            _ev("repro.server.dispatch", 50, 950),
            _ev("repro.engine.run", 90, 520),
            _ev("repro.sync.overflow", 480, 520),
            _ev("repro.serving.certify", 550, 950),
            _ev("repro.sync.beta", 560, 600),
            _ev("repro.sync.certificate", 650, 710),
            _ev("repro.server.resolve", 955, 1100),
            _ev("repro.sync.result", 960, 1100),
            _ev("repro.server.submit", 980, 990, line="bench-client-0"),
            _ev("repro.sync.result", 1200, 1300)]
    return trace.Reduced(lo=0.0, hi=1000 * US, ops=ops, host=host)


def _reading(tr, solutions=2, server=None):
    return types.SimpleNamespace(trace=tr, solutions=solutions,
                                 server=server or {})


def _read(name, r):
    return bench.find("metrics", name).read(r)


def test_span_times_per_solution_are_clipped_to_the_window():
    r = _reading(_hand())
    # prepare [0, 80), engine [90, 520), certify [550, 950); 2 solutions
    assert _read("prep_ms_per_solution", r) == pytest.approx(0.040)
    assert _read("engine_ms_per_solution", r) == pytest.approx(0.215)
    assert _read("certify_ms_per_solution", r) == pytest.approx(0.200)


def test_host_syncs_count_reads_that_start_in_the_window():
    # overflow, beta, certificate and the first result read; not the lams
    # read (starts before the window) nor the late result read
    r = _reading(_hand())
    assert _read("host_syncs_per_solution", r) == pytest.approx(2.0)


@pytest.mark.parametrize("extra,share", [(0, 16.0), (1, 8.0)])
def test_idle_while_reading_back(extra, share):
    """Idle [0, 100), [300, 400), [500, 700), [900, 1000); reads open
    over [0, 10), [480, 520), [560, 600), [650, 710), [960, 1000): idle
    and reading 10 + 20 + 40 + 50 + 40 = 160 us of the chip's window. A
    second chip busy throughout halves the average."""
    r = _reading(_hand(extra))
    assert _read("idle_in_sync_share", r) == pytest.approx(share)


def test_idle_outside_every_span():
    """Program spans cover [0, 950) and [955, 1000): the device is idle
    and no span open over [950, 955) alone, 0.5% of the window; the
    frame of Python that is no span of the program does not count."""
    r = _reading(_hand())
    assert _read("idle_outside_spans_share", r) == pytest.approx(0.5)
    assert _read("device_idle_share", r) == pytest.approx(50.0)


def test_queue_wait_is_the_mean_claim_wait():
    r = _reading(None, server={"dispatched": 4, "queue_wait_s": 0.02})
    assert _read("queue_wait_ms", r) == pytest.approx(5.0)


@pytest.mark.parametrize("server", [{}, {"dispatched": 0,
                                         "queue_wait_s": 0.0},
                                    {"served": 3}])
def test_queue_wait_absent_without_the_counters(server):
    assert _read("queue_wait_ms", _reading(None, server=server)) is None


@pytest.mark.parametrize("name", TRACED)
def test_nothing_to_read_gives_none(name):
    hand = _hand()
    no_device = hand._replace(ops={})
    no_spans = hand._replace(host=[e for e in hand.host
                                   if not e.name.startswith("repro.")])
    for tr in (None, no_device, no_spans):
        assert _read(name, _reading(tr)) is None, tr
    if name not in ("idle_in_sync_share", "idle_outside_spans_share"):
        assert _read(name, _reading(hand, solutions=0)) is None


def test_overlap_and_complement():
    assert spans.overlap_ns([(0, 10), (20, 30)], [(5, 25)]) == 10
    assert spans.overlap_ns([], [(0, 1)]) == 0
    assert spans.complement([(2, 4), (3, 6)], 0, 10) == [(0, 2), (6, 10)]
