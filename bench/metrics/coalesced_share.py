"""Share of the requests served in the window that rode a coalesced
microbatch of two or more (``ServerStats.coalesced_requests / served``)."""


def read(r):
    served = r.server.get("served", 0)
    if not served:
        return None
    return 100.0 * r.server.get("coalesced_requests", 0) / served
