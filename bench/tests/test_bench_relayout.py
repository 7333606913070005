"""The readers of the design's re-layout copies and of the column fetch
(bench/metrics/x_relayout_ms_per_solution.py,
column_fetch_ms_per_solution.py), on hand-made reduced traces."""
import pathlib
import sys
import types

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

import bench  # noqa: E402
from bench import trace  # noqa: E402
from bench.trace import Ev  # noqa: E402

US = 1e3    # ns per microsecond
DEV, OPS = "/device:TPU:0", "XLA Ops"
# instruction texts as a v5e trace names them, cut short
SCREEN = ("%screen_fused_batch_pallas.1 = (f32[1,1,1048576]{2,1,0:T(1,128)}"
          ", f32[1,1,1048576]{2,1,0:T(1,128)S(1)}) custom-call(f32[1]{0} "
          "%r, f32[1,1024,1]{2,1,0:T(8,128)} %t, f32[1024,1048576]{1,0:T("
          "8,128)} %X)")
TO_ROWS = ("%copy.26 = f32[1024,1048576]{1,0:T(8,128)} copy(f32[1024,1048576]"
           "{0,1:T(8,128)} %get-tuple-element.727)")
TO_COLS = ("%copy = f32[1024,1048576]{0,1:T(8,128)} copy(f32[1024,1048576]"
           "{1,0:T(8,128)} %X)")
BLOCK = ("%copy.219 = f32[8,1024,1024]{2,1,0:T(8,128)} copy(f32[8,1024,1024]"
         "{1,2,0:T(8,128)} %block)")
FUSED = "%fusion.6 = f32[1024,1048576]{1,0:T(8,128)} fusion(%X)"
FETCH = ("%fetch_columns_pallas.3 = f32[32,1,1024]{2,1,0:T(1,128)} "
         "custom-call(s32[32]{0} %b, s32[32]{0} %l, f32[1024,1048576]{1,0:"
         "T(8,128)} %X)")


def _ev(name, s, t):
    return Ev(DEV, OPS, name, s * US, (t - s) * US)


def _reading(ops, solutions=4):
    tr = trace.Reduced(lo=0.0, hi=1000 * US, ops={DEV: ops}, host=[])
    return types.SimpleNamespace(trace=tr, solutions=solutions, server={})


def _read(name, r):
    return bench.find("metrics", name).read(r)


def test_relayout_sums_the_design_shaped_copies_per_solution():
    r = _reading([_ev(SCREEN, 0, 300), _ev(TO_ROWS, 300, 500),
                  _ev(TO_COLS, 500, 540), _ev(BLOCK, 540, 600),
                  _ev(FUSED, 600, 700), _ev(FETCH, 700, 710)])
    # copy.26 200 us + copy 40 us over 4 solutions: 0.06 ms
    assert _read("x_relayout_ms_per_solution", r) == pytest.approx(0.06)
    assert _read("column_fetch_ms_per_solution", r) == pytest.approx(
        0.0025)


def test_relayout_reads_zero_once_the_copies_are_gone():
    r = _reading([_ev(SCREEN, 0, 300), _ev(BLOCK, 300, 400)])
    assert _read("x_relayout_ms_per_solution", r) == 0.0


@pytest.mark.parametrize("name", ["x_relayout_ms_per_solution",
                                  "column_fetch_ms_per_solution"])
def test_nothing_to_read_gives_none(name):
    # no trace; no solution; no screening call (so no design shape) and
    # no fetch, as a program without the fetch kernel traces
    assert _read(name, types.SimpleNamespace(trace=None, solutions=3)) \
        is None
    assert _read(name, _reading([_ev(SCREEN, 0, 9), _ev(FETCH, 9, 10)],
                                solutions=0)) is None
    assert _read(name, _reading([_ev(TO_ROWS, 0, 10)])) is None
