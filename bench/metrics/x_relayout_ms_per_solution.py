"""Device milliseconds, per certified solution, of the copies whose result
has the design's shape (n, p): re-layouts of X between the row-major
order the screening scan streams and any other order. The design's shape
is read from the ``screen_fused_batch_pallas`` calls of the window; none
found, nothing is read."""
from bench import roofline, trace


def design_shape(ops):
    """(n, p) of the design the window's screening calls scan."""
    for e in trace.calls(ops, "screen_fused_batch_pallas"):
        shape = roofline.screen_call_shape(e.name)
        if shape is not None:
            return shape[1], shape[2]
    return None


def is_relayout(name: str, n: int, p: int) -> bool:
    if trace.kernel_base(name) != "copy":
        return False
    sh = roofline.shapes(name.partition(" = ")[2])
    return bool(sh) and sh[0][1] == (n, p)


def read(r):
    if r.trace is None or not r.solutions:
        return None
    ops = r.trace.all_ops()
    np_ = design_shape(ops)
    if np_ is None:
        return None
    ns = sum(e.dur for e in ops if is_relayout(e.name, *np_))
    return ns / 1e6 / r.solutions
