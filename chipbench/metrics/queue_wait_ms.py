"""queue_wait_ms: mean time per request from admission to the coalescer
taking it into a flush (the ``max_delay_s`` hold and any wait behind the
coalescer), from the service's counters ``queue_wait_s`` / ``taken_requests``
over the window. A program without those counters reads nothing."""


def read(record):
    svc = record["service"]
    if not svc.get("taken_requests"):
        return None
    return 1e3 * svc["queue_wait_s"] / svc["taken_requests"]
