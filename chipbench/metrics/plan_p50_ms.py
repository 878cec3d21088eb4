"""plan_p50_ms: median latency over every request due in the window, from
its due time to its answer; a failed request counts as missing."""

from chipbench import stats


def read(record):
    return 1e3 * stats.percentile(stats.latencies_s(record["requests"], record["give_up_s"]), 50)
