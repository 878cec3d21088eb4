"""plan_p95_ms: 95th percentile of the latency over every request due in the
window, from its due time to its answer; a failed request counts as
missing."""

from chipbench import stats


def read(record):
    return 1e3 * stats.percentile(stats.latencies_s(record["requests"], record["give_up_s"]), 95)
