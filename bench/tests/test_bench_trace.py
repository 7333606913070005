"""Trace reduction, roofline arithmetic and the peaks table
(bench/trace.py, bench/roofline.py)."""
import glob
import sys
import time
import pathlib

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import roofline, trace  # noqa: E402
from bench.trace import Ev  # noqa: E402

US = 1e3    # ns per microsecond

# the HLO text of one fleet screening call, as a v5e trace names it
SCREEN_OP = (
    "%screen_fused_batch_pallas.1 = (f32[2,1,1048576]{2,1,0:T(1,128)}, "
    "f32[2,1,1048576]{2,1,0:T(1,128)S(1)}, f32[2,1,1048576]{2,1,0:T(1,128)}"
    ", f32[2,2048,1,16]{3,2,1,0:T(1,128)S(1)}, s32[2,2048,1,16]{3,2,1,0:T("
    "1,128)S(1)}, /*index=5*/f32[2,2048,1,1]{3,2,1,0:T(1,128)S(1)}) "
    "custom-call(f32[2]{0:T(128)S(1)} %copy-done.53, f32[2,1024,1]{2,1,0:"
    "T(8,128)S(1)} %copy.72, f32[1024,1048576]{1,0:T(8,128)} %copy.77, "
    "f32[2,1,1048576]{2,1,0:T(1,128)S(1)} %copy-done.12, f32[2,1,1048576]"
    "{2,1,0:T(1,128)S(1)} %copy-done.11), custom_call_target=\"tpu_custom_"
    "call\"")


def _hand_trace():
    """A window of 1000 us. Device: a while loop [100, 600) holding A
    [120, 220) and B [300, 450); C [700, 800); D [850, 900). Host: a
    dispatch frame over everything, a wait [600, 700) with a verify frame
    [620, 690) inside it, a digest [900, 1050)."""
    dev, ops = "/device:TPU:0", "XLA Ops"
    host = "/host:CPU"
    e = [Ev(dev, ops, "%while.1 = (s32[2])", 100 * US, 500 * US),
         Ev(dev, ops, "%screen_fused_batch_pallas.1 = (f32[1,1,8])",
            120 * US, 100 * US),
         Ev(dev, ops, "%cm_burst_batch_pallas.3 = (f32[1,1,8])",
            300 * US, 150 * US),
         Ev(dev, ops, "%copy.7 = f32[8,8]", 700 * US, 100 * US),
         Ev(dev, ops, "%screen_fused_batch_pallas.1 = (f32[1,1,8])",
            850 * US, 50 * US),
         Ev(dev, "Async XLA Ops", "%copy-start.1", 0.0, 2000 * US),
         Ev(host, "python3", "bench.window", 50 * US, 1000 * US),
         Ev(host, "python3", "$server.py:551 _dispatch", 40 * US,
            1060 * US),
         Ev(host, "", "$threading.py:323 wait", 600 * US, 100 * US),
         Ev(host, "python3", "$serving.py:794 _verify", 620 * US, 70 * US),
         Ev(host, "python3", "$server.py:167 _problem_digest", 900 * US,
            150 * US)]
    return e


def test_busy_idle_and_gaps_by_hand():
    r = trace.reduce(_hand_trace())
    assert r.window_ns == 1000 * US
    assert r.n_devices == 1
    # busy: [100, 600) + [700, 800) + [850, 900) = 650 us
    assert r.busy_s() == pytest.approx(650e-6)
    lo = 50 * US
    assert trace.gaps(r.ops["/device:TPU:0"], lo, lo + r.window_ns) == [
        (50 * US, 100 * US), (600 * US, 700 * US), (800 * US, 850 * US),
        (900 * US, 1050 * US)]


def test_self_time_and_kernel_sums_by_hand():
    r = trace.reduce(_hand_trace())
    ops = r.all_ops()
    own = {e.name.split(" ")[0] + f"@{e.start / US:g}": s / US
           for e, s in trace.self_ns(ops)}
    assert own == {"%while.1@100": 250.0,
                   "%screen_fused_batch_pallas.1@120": 100.0,
                   "%cm_burst_batch_pallas.3@300": 150.0,
                   "%copy.7@700": 100.0,
                   "%screen_fused_batch_pallas.1@850": 50.0}
    scr = trace.calls(ops, "screen_fused_batch_pallas")
    assert sum(e.dur for e in scr) == 150 * US
    assert trace.kernel_base("%cm_burst_batch_pallas.3 = (f32[1])") == \
        "cm_burst_batch_pallas"
    top = dict(trace.top_ops(ops))
    assert top["%while.1 = (s32[2])"] == pytest.approx(250e-6)
    assert top["%screen_fused_batch_pallas.1 = (f32[1,1,8])"] == \
        pytest.approx(150e-6)


def test_idle_gaps_named_by_host_activity():
    bd = dict(trace.reduce(_hand_trace()).breakdown()["idle_gaps"])
    # [600, 700) falls in a wait with _verify inside it; [900, 1050) in
    # the digest; the two 50 us gaps are summed unnamed
    assert bd == pytest.approx({"$server.py:167 _problem_digest": 150e-6,
                                "$serving.py:794 _verify": 100e-6,
                                "gaps under 100 us": 100e-6})


def test_no_window_span_reduces_to_nothing():
    ev = [e for e in _hand_trace() if e.name != "bench.window"]
    assert trace.reduce(ev) is None


def test_recorded_cpu_trace_sums(tmp_path):
    """A trace recorded here: three calls of one jitted product, 20 ms
    apart, inside the window span. The reduction's busy time is the sum
    of the product's events, and the rest of the window is idle."""
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: x @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            f(x).block_until_ready()
            time.sleep(0.02)
    jax.profiler.stop_trace()
    path = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)[0]
    r = trace.reduce(trace.load(path), plane_prefix="/host:CPU",
                     line="tf_XLAPjRtCpuClient")
    dots = [e for e in r.all_ops()
            if trace.kernel_base(e.name).startswith("dot")]
    assert len(dots) == 3
    # the three calls are 20 ms apart, so their events do not overlap
    spans = sorted((e.start, e.end) for e in dots)
    assert all(a[1] + 1e7 <= b[0] for a, b in zip(spans, spans[1:]))
    r = r._replace(ops={"cpu": dots})
    busy = sum(e.dur for e in dots) / 1e9
    assert r.busy_s() == pytest.approx(busy)
    window_s = r.window_ns / 1e9
    assert window_s >= 0.06
    idle = sum(s for _, s in r.breakdown(k=100)["idle_gaps"])
    assert idle == pytest.approx(window_s - busy)
    assert dict(trace.top_ops(dots)) == pytest.approx(
        {trace.op_label(dots[0].name): busy})


def test_screen_roofline_arithmetic():
    assert roofline.screen_call_shape(SCREEN_OP) == (2, 1024, 1048576, 4)
    ops, nbytes = roofline.screen_scan(2, 1024, 1048576)
    assert nbytes == 4 * (1024 * 1048576 + 2 * 1024 + 5 * 2 * 1048576)
    assert ops == 2 * 2 * 1024 * 1048576 + 6 * 2 * 1048576
    peak = roofline.peaks("TPU v5 lite")
    # bytes bound: 4.337 GB at 819 GB/s
    assert roofline.least_seconds(ops, nbytes, peak) == pytest.approx(
        nbytes / 819e9)


def test_peaks_refuse_an_unknown_device():
    with pytest.raises(KeyError, match="no peaks"):
        roofline.peaks("TPU v9 imaginary")
