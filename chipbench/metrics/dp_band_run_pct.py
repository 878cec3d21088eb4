"""dp_band_run_pct: share of the cells the DP executables computed over
their buckets that the min-plus band loops ran, from the service's counters
over the window: ``dp_run_cells`` (``(Tb + 1)`` times each row's band
steps; on the Pallas TPU kernel a row's loop ends at its band ``U' + 1``,
rounded up to the kernel's unroll of 32) over ``dp_computed_cells``
(``Bb * nb * (Tb + 1) * Wb`` per flush). A program without those counters,
or a window with no DP flush, reads nothing."""


def read(record):
    svc = record["service"]
    if not svc.get("dp_computed_cells") or "dp_run_cells" not in svc:
        return None
    return 100.0 * svc["dp_run_cells"] / svc["dp_computed_cells"]
