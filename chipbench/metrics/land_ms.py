"""land_ms: mean time per flush from the engine's dispatch returning to the
flush's answers resolved on the completer thread (the wait behind earlier
device work, the device time, the transfer back and the demux), from the
service's counters ``land_s`` / ``landed_flushes`` over the window. A
program without those counters reads nothing."""


def read(record):
    svc = record["service"]
    if not svc.get("landed_flushes"):
        return None
    return 1e3 * svc["land_s"] / svc["landed_flushes"]
