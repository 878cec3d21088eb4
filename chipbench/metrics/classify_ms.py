"""classify_ms: mean time per flush of the engine's regime classification
(``select_algorithm_batch`` inside ``SweepEngine.dispatch``), from the
service's counters ``classify_s`` / ``flushes`` over the window. A program
without those counters reads nothing."""


def read(record):
    svc = record["service"]
    if "classify_s" not in svc or not svc.get("flushes"):
        return None
    return 1e3 * svc["classify_s"] / svc["flushes"]
