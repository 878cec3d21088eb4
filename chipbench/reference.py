"""Plain references the benchmark judges answers against.

Frozen copies, kept with the benchmark so that a change to the program
cannot change the yardstick. They import nothing of the program and take
nothing it made: an instance is ``(T, lower, upper, tables)`` in plain numpy.

* :func:`dp_schedule` is the (MC)^2MKP dynamic program of arXiv:2209.06210
  (Algorithm 1 with the Section 5.2 lower-limit removal), copied from the
  serial numpy DP the program started from. ``dtype`` lets the control run
  the same recurrence in a lower precision.
* :func:`marin_schedule` is the paper's MarIn (Algorithm 2): a min-heap over
  next marginal costs, exact for non-decreasing marginals.
* :func:`total_cost` is the float64 cost of a schedule.
* :func:`regime` is Definition 3's marginal-cost classification.

A configuration names its reference in ``check.reference``; :func:`solver`
finds it here or, for a reference added later, in ``references/<name>.py``.
"""

from __future__ import annotations

import heapq

import numpy as np


def total_cost(tables, x) -> float:
    """Float64 cost ``sum_i C_i(x_i)``."""
    return float(sum(float(t[int(v)]) for t, v in zip(tables, x)))


def feasible(T: int, lower, upper, x) -> bool:
    """``x`` assigns exactly ``T`` tasks within every ``[L_i, U_i]``."""
    x = np.asarray(x)
    return (
        x.shape == np.shape(lower)
        and int(x.sum()) == int(T)
        and bool(np.all(x >= lower))
        and bool(np.all(x <= upper))
    )


def dp_schedule(T: int, lower, upper, tables, dtype=np.float64):
    """Optimal schedule and its DP objective, by Algorithm 1.

    Lower limits are shifted out first (``T' = T - sum L``, ``C'_i(j) =
    C_i(j + L_i) - C_i(L_i)``); the DP fills ``K[i, t]``, the least cost of
    ``t`` tasks over classes ``0..i``, with the first minimum over ascending
    ``j`` kept, and the schedule is read back from the argmin rows. Returns
    ``(x, objective)`` where the objective is ``K[n-1, T']`` plus the fixed
    cost ``sum_i C_i(L_i)``, both in ``dtype`` arithmetic.
    """
    lower = np.asarray(lower, np.int64)
    upper = np.asarray(upper, np.int64)
    n = len(tables)
    Tp = int(T) - int(lower.sum())
    shifted = [
        (np.asarray(t, np.float64)[int(lo) : int(u) + 1] - float(t[int(lo)])).astype(dtype)
        for t, lo, u in zip(tables, lower, upper)
    ]
    inf = dtype(np.inf)
    K = np.full((n, Tp + 1), inf, dtype=dtype)
    I = np.full((n, Tp + 1), -1, dtype=np.int64)
    c0 = shifted[0]
    for j in range(min(len(c0), Tp + 1)):
        if c0[j] < K[0, j]:
            K[0, j] = c0[j]
            I[0, j] = j
    for i in range(1, n):
        ci = shifted[i]
        for j in range(min(len(ci), Tp + 1)):
            prev = K[i - 1, : Tp + 1 - j] + ci[j]
            better = prev < K[i, j:]
            K[i, j:][better] = prev[better]
            I[i, j:][better] = j
    if not np.isfinite(float(K[n - 1, Tp])):
        raise ValueError("infeasible instance")
    x = np.zeros(n, dtype=np.int64)
    t = Tp
    for i in range(n - 1, -1, -1):
        x[i] = I[i, t]
        t -= int(x[i])
    fixed = sum(dtype(t[int(lo)]) for t, lo in zip(tables, lower))
    return x + lower, float(K[n - 1, Tp] + fixed)


def marin_schedule(T: int, lower, upper, tables, dtype=np.float64):
    """MarIn: the next task goes to the resource whose next marginal cost is
    least; equal marginals go to the lower resource index. Returns ``(x,
    objective)``; the objective is the fixed cost plus the picked marginals,
    summed in ``dtype``."""
    lower = np.asarray(lower, np.int64)
    upper = np.asarray(upper, np.int64)
    tabs = [np.asarray(t, np.float64).astype(dtype) for t in tables]
    n = len(tabs)
    x = lower.copy()
    heap = []
    for i in range(n):
        if upper[i] > lower[i]:
            j = int(lower[i]) + 1
            heapq.heappush(heap, (float(tabs[i][j] - tabs[i][j - 1]), i))
    total = sum((tabs[i][int(lower[i])] for i in range(n)), dtype(0))
    for _ in range(int(T) - int(lower.sum())):
        m, k = heapq.heappop(heap)
        x[k] += 1
        total = dtype(total + dtype(m))
        nxt = int(x[k]) + 1
        if nxt <= upper[k]:
            heapq.heappush(heap, (float(tabs[k][nxt] - tabs[k][nxt - 1]), k))
    return x, float(total)


def marginal_trend(table, lo, u):
    """``(non-decreasing, non-increasing)``: how one resource's marginal
    costs ``M(j) = C(j) - C(j-1)`` move over ``j`` in ``(lo, u]``."""
    d = np.diff(np.diff(np.asarray(table, np.float64)[int(lo) : int(u) + 1]))
    return bool(np.all(d >= 0)), bool(np.all(d <= 0))


def regime(lower, upper, tables, trend=marginal_trend) -> str:
    """``increasing | constant | decreasing | arbitrary``: how the marginal
    costs move across all resources (Definition 3). ``trend`` is
    :func:`marginal_trend` or a memo of it."""
    inc = dec = True
    for t, lo, u in zip(tables, lower, upper):
        i, d = trend(t, lo, u)
        inc &= i
        dec &= d
    if inc and dec:
        return "constant"
    return "increasing" if inc else "decreasing" if dec else "arbitrary"


SOLVERS = {"dp": dp_schedule, "marin": marin_schedule}


def solver(name: str, here=None):
    """The reference named ``name``: one of :data:`SOLVERS`, or the
    ``solve`` of ``references/<name>.py`` beside the benchmark, for a
    deployment whose reference is not here."""
    if name in SOLVERS:
        return SOLVERS[name]
    from .traffic import HERE, load

    return load("references", name, HERE if here is None else here).solve
