"""dispatch_ms: mean time per flush inside ``SweepEngine.dispatch`` (regime
split, padding, packing, launch), on the benchmark's own clock around each
call from the service's coalescer."""

from chipbench import stats


def read(record):
    value = stats.mean([end - start for start, end in record["dispatch_spans"]])
    return None if value is None else 1e3 * value
