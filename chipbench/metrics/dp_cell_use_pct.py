"""dp_cell_use_pct: share of the cells the DP executables computed that the
instances needed, from the service's counters over the window:
``dp_band_cells`` (``(T' + 1) * (U_i - L_i + 1)`` over real rows and
clients) over ``dp_computed_cells`` (``Bb * nb * (Tb + 1) * Wb`` per
flush). The rest is padding to the pow2 buckets. A program without those
counters, or a window with no DP flush, reads nothing."""


def read(record):
    svc = record["service"]
    if not svc.get("dp_computed_cells"):
        return None
    return 100.0 * svc["dp_band_cells"] / svc["dp_computed_cells"]
