"""submit_ms: mean time per request inside ``SchedulerService.submit``
(validation, bucket key, ``ProblemBatch`` packing), on the benchmark's own
clock around each call."""

from chipbench import stats


def read(record):
    spans = [r["submitted"] - r["sent"] for r in record["requests"] if "submitted" in r]
    value = stats.mean(spans)
    return None if value is None else 1e3 * value
