"""Share of the traced window in which no operation ran on the device:
1 - (union of the ``XLA Ops`` intervals) / window, averaged over chips."""


def read(r):
    if r.trace is None or not r.trace.n_devices:
        return None
    return 100.0 * (1.0 - r.trace.busy_s() / (r.trace.window_ns / 1e9))
