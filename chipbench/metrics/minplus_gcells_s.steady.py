"""minplus_gcells_s.steady: band cells the DP needs per second of the
min-plus kernel's device time, in Gcell/s.

The cells are counted from each answered instance's own shape,
``(T' + 1) * (U_i - L_i + 1)`` summed over its clients (``T'`` with the lower
limits shifted out), not from the padded bucket it ran in; so the same work
reads the same whatever computes it, and padding counts against the kernel.
The kernel's time is the summed device time of its operations in the trace.
"""

# the kernel's custom call is named after its jitted wrapper, as
# ``minplus_pallas_batch.<n>`` in the compiled program
KERNEL = "minplus_pallas_batch"


def read(record):
    tr = record.get("trace")
    if not tr:
        return None
    seconds = sum(v for name, v in tr["op_s"].items() if KERNEL in name)
    if seconds <= 0:
        return None
    cells = sum(c for c, r in zip(record["band_cells"], record["requests"]) if r.get("ok"))
    return cells / seconds / 1e9
