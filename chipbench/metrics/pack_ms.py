"""pack_ms: mean time per flush of the engine's validation, slicing, padding
and packing (the packed tables' transfer to the device included), from the
service's counters ``pack_s`` / ``flushes`` over the window. A program
without those counters reads nothing."""


def read(record):
    svc = record["service"]
    if "pack_s" not in svc or not svc.get("flushes"):
        return None
    return 1e3 * svc["pack_s"] / svc["flushes"]
