"""Share of the traced window in which the device is idle while the host
is inside a ``repro.sync.*`` span (reading a result back), averaged over
chips."""
from bench import spans


def read(r):
    sync = spans.spans(r, lambda n: n.startswith(spans.SYNC))
    if sync is None:
        return None
    return spans.idle_share(r, lambda _: sync)
