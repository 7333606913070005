"""Share of the screening scan's roofline: the least time of every
``screen_fused_batch_pallas`` call in the window (``roofline.screen_scan``
from the shapes of the call, against the device's peaks) over the time
the trace gives those calls."""
from bench import roofline, trace


def read(r):
    if r.trace is None or r.peak is None:
        return None
    least = actual = 0.0
    for e in trace.calls(r.trace.all_ops(), "screen_fused_batch_pallas"):
        shape = roofline.screen_call_shape(e.name)
        if shape is None:
            return None
        b, n, p, itemsize = shape
        ops, nbytes = roofline.screen_scan(b, n, p, itemsize)
        least += roofline.least_seconds(ops, nbytes, r.peak)
        actual += e.dur / 1e9
    return 100.0 * least / actual if actual > 0 else None
