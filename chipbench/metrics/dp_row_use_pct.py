"""dp_row_use_pct: share of the rows the DP's class scan ran that were real
clients with work to place, from the service's counters over the window:
``dp_live_rows`` over ``dp_rows`` (``Bb * nb`` per flush). The rest is
padding of the batch and of the client axis to their pow2 buckets. A program
without those counters, or a window with no DP flush, reads nothing."""


def read(record):
    svc = record["service"]
    if not svc.get("dp_rows") or "dp_live_rows" not in svc:
        return None
    return 100.0 * svc["dp_live_rows"] / svc["dp_rows"]
