"""Reference ``uplink_dp``: the (MC)^2MKP dynamic program of arXiv:2209.06210
(Algorithm 1 with the Section 5.2 lower-limit removal) in exact integer
arithmetic, blocked to fit a request of thousands of clients.

It computes what :func:`chipbench.reference.dp_schedule` computes, and gives
the same schedule: the same recurrence over clients in order, the first
minimum over ascending ``j`` kept. It differs in what it holds. That one keeps
``K`` and the argmins as ``(n, T'+1)`` float64 and int64, 3.2 GB at 3,550
clients and ``T'`` of 57k; this one keeps two rows of ``K`` and the argmins as
uint8 (band offsets below 256), about 200 MB there. Each row is computed only
over the workloads that can still reach ``T'``: at most the capacity of the
clients so far, at least ``T'`` less the capacity of the clients after. Every
other entry is infinite in the plain DP or lies on no path to ``T'``.

Tables are integer mJ and sums are int64, so the optimum is exact. ``dtype``
runs the same recurrence in a floating type instead (the control).
It imports nothing of the program.
"""

from __future__ import annotations

import numpy as np


def solve(T: int, lower, upper, tables, dtype=np.int64):
    """``(x, objective)``: an optimal schedule and its cost, the fixed cost
    of the lower limits included, both in ``dtype`` arithmetic."""
    dtype = np.dtype(dtype)
    exact = np.issubdtype(dtype, np.integer)
    lower = np.asarray(lower, np.int64)
    upper = np.asarray(upper, np.int64)
    n = len(tables)
    Tp = int(T) - int(lower.sum())
    width = upper - lower
    if Tp < 0 or int(width.sum()) < Tp:
        raise ValueError("infeasible instance")
    if int(width.max(initial=0)) > 255:
        raise ValueError("band offsets above 255 do not fit the uint8 argmins")
    shifted = []
    for t, lo, u in zip(tables, lower, upper):
        c = np.asarray(t, np.float64)[int(lo) : int(u) + 1]
        c = c - c[0]
        if exact:
            if not np.array_equal(c, np.rint(c)):
                raise ValueError("the exact reference needs integer cost tables")
            c = np.rint(c).astype(np.int64)
        shifted.append(c.astype(dtype))
    inf = np.iinfo(np.int64).max // 4 if exact else dtype.type(np.inf)
    # the workloads row i can hold that still reach T': [lo_i, hi_i]
    before = np.concatenate([[0], np.cumsum(width)])
    hi = np.minimum(Tp, before[1:])
    lo = np.maximum(0, Tp - (before[-1] - before[1:]))
    # entries above a row's window are never written, so they stay infinite
    K = np.full(Tp + 1, inf, dtype=dtype)
    K[0] = 0
    new = np.full_like(K, inf)
    I = np.zeros((n, Tp + 1), dtype=np.uint8)
    for i, c in enumerate(shifted):
        a0, b = int(lo[i]), int(hi[i])
        new[a0 : b + 1] = inf
        row = I[i]
        for j, cj in enumerate(c):  # ascending j, strict improvement: first minimum
            a = max(a0, j)
            if a > b:
                break
            cand = K[a - j : b - j + 1] + cj
            better = cand < new[a : b + 1]
            np.copyto(new[a : b + 1], cand, where=better)
            np.copyto(row[a : b + 1], np.uint8(j), where=better)
        K, new = new, K
    if not K[Tp] < inf:
        raise ValueError("infeasible instance")
    x = np.zeros(n, dtype=np.int64)
    t = Tp
    for i in range(n - 1, -1, -1):
        x[i] = I[i, t]
        t -= int(x[i])
    fixed = sum(np.asarray(tb, np.float64)[int(lo_)] for tb, lo_ in zip(tables, lower))
    if exact:
        return x + lower, float(int(K[Tp]) + fixed)
    return x + lower, float(K[Tp] + dtype.type(fixed))
