"""launch_ms: mean time per flush of the engine's executable calls (the
cache lookup and the asynchronous enqueue, not the device time), from the
service's counters ``launch_s`` / ``flushes`` over the window. A program
without those counters reads nothing."""


def read(record):
    svc = record["service"]
    if "launch_s" not in svc or not svc.get("flushes"):
        return None
    return 1e3 * svc["launch_s"] / svc["flushes"]
