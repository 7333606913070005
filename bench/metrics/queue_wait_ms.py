"""Mean milliseconds a request waited in the server's queues, from
``submit()`` to its claim by the dispatcher (the coalescing window
included): ``1e3 * ServerStats.queue_wait_s / dispatched`` over the
window."""


def read(r):
    n = r.server.get("dispatched")
    if not n or "queue_wait_s" not in r.server:
        return None
    return 1e3 * r.server["queue_wait_s"] / n
