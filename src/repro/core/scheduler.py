"""Regime detection + algorithm dispatch (the facade's solve internals).

Table 2 of the paper maps each marginal-cost regime to its lowest-complexity
optimal algorithm:

  regime      | no binding upper limits | with upper limits
  ------------|-------------------------|-------------------
  increasing  | MarIn                   | MarIn
  constant    | MarDecUn*               | MarCo
  decreasing  | MarDecUn                | MarDec
  arbitrary   | (MC)^2MKP DP            | (MC)^2MKP DP

(*constant marginals without upper limits: MarDecUn's Θ(n) single-resource
assignment is optimal there too, per Table 2.)

Since PR 7 (DESIGN.md §15) the supported entrypoint is the
:class:`repro.core.solver.Solver` facade — ``solve`` / ``sweep`` /
``frontier`` — which calls the private ``_schedule`` / ``_schedule_batch`` /
``_deadline_sweep`` implementations here. The old module-level names
(``schedule``, ``schedule_batch``, ``schedule_with_deadline``,
``deadline_sweep``) remain as bit-identical deprecated shims. Nothing here
solves "directly" anymore in the batched paths: every batch solve routes
through the :class:`~repro.core.sweep.SweepEngine` compile cache, and the
single-instance path delegates to the per-algorithm callables in
``ALGORITHMS``.
"""

from __future__ import annotations

from typing import Callable, Dict

import numpy as np

from . import baselines
from ._deprecation import warn_deprecated
from .jax_dp import solve_schedule_dp_jax
from .marginal import marco, mardec, mardecun, marin
from .marginal_jax import select_algorithm_batch
from .mc2mkp import solve_schedule_dp
from .problem import Problem, total_cost, validate_schedule
from .sweep import _solve_cached

__all__ = [
    "schedule",
    "schedule_batch",
    "deadline_sweep",
    "ALGORITHMS",
    "select_algorithm",
    "select_algorithm_batch",
]

# algorithm names that run the (MC)^2MKP DP — in the batched entry point all
# of these route through the one batched min-plus program
_DP_ALGORITHMS = {"dp", "dp_jax", "dp_batch", "dp_jax_pallas"}

ALGORITHMS: Dict[str, Callable] = {
    "dp": solve_schedule_dp,
    "dp_jax": solve_schedule_dp_jax,
    # "pallas": the TPU kernel, interpreted on the CPU and compiled on a TPU
    "dp_jax_pallas": lambda p: solve_schedule_dp_jax(p, backend="pallas"),
    "marin": marin,
    "marco": marco,
    "mardecun": mardecun,
    "mardec": mardec,
    # baselines (not total-cost-optimal in general; for comparison)
    "olar": baselines.olar,
    "uniform": baselines.uniform,
    "proportional": baselines.proportional,
    "greedy_marginal": baselines.greedy_marginal,
}


def select_algorithm(problem: Problem) -> str:
    """Lowest-complexity optimal algorithm for ``problem``'s regime (paper
    Table 2). The ``B = 1`` slice of
    :func:`~repro.core.marginal_jax.select_algorithm_batch` — one shared
    regime-detection + dispatch rule, so serial and batched "auto" can
    never disagree (DESIGN.md §13)."""
    return select_algorithm_batch([problem])[0]


# ---------------------------------------------------------------------------
# private implementations — the Solver facade's solve internals. The public
# module-level names below are deprecated warn-once shims over these; keeping
# one body per behavior is what makes the shims bit-identical by construction.
# ---------------------------------------------------------------------------


def _schedule(problem: Problem, algorithm: str = "auto", check: bool = True):
    """Single-instance solve; returns ``(x, resolved_algorithm)`` so the
    facade can report which Table-2 algorithm "auto" picked."""
    if algorithm == "auto":
        algorithm = select_algorithm(problem)
    try:
        fn = ALGORITHMS[algorithm]
    except KeyError:
        raise ValueError(f"unknown algorithm {algorithm!r}; options: auto, {sorted(ALGORITHMS)}")
    x = fn(problem)
    if check:
        validate_schedule(problem, x)
    return x, algorithm


def _schedule_batch(
    problems,
    algorithm: str = "auto",
    check: bool = True,
    backend=None,
    engine=None,
):
    """Batched solve: every DP-shaped solve goes through the sweep engine's
    shape-bucketed compile cache (DESIGN.md §10); "auto" takes the
    regime-split path (§13). Returns a list of ``(n_b,)`` int64 schedules."""
    problems = list(problems)
    if not problems:
        return []
    out = [None] * len(problems)
    dp_idx = []
    if algorithm == "auto":
        X = _solve_cached(problems, backend, engine, split_regimes=True)
        for b, p in enumerate(problems):
            out[b] = np.asarray(X[b, : p.n], dtype=np.int64)
    elif algorithm in _DP_ALGORITHMS:
        dp_idx = list(range(len(problems)))
        if algorithm == "dp_jax_pallas":
            backend = "pallas"
    else:
        for b, p in enumerate(problems):
            out[b] = _schedule(p, algorithm, check=False)[0]
    if dp_idx:
        X = _solve_cached(
            [problems[b] for b in dp_idx], backend, engine, split_regimes=False
        )
        for row, b in zip(X, dp_idx):
            out[b] = np.asarray(row[: problems[b].n], dtype=np.int64)
    if check:
        for p, x in zip(problems, out):
            validate_schedule(p, x)
    return out


def _schedule_with_deadline(
    problem: Problem,
    time_tables,
    deadline: float,
    algorithm: str = "auto",
) -> np.ndarray:
    """ε-constraint single solve: tighten, then :func:`_schedule`."""
    return _schedule(tighten_for_deadline(problem, time_tables, deadline), algorithm)[0]


def _deadline_sweep(
    problem: Problem,
    time_tables,
    deadlines,
    check: bool = True,
    backend=None,
    engine=None,
) -> np.ndarray:
    """Whole deadline grid in ONE batched DP solve; ``(B, n)`` int64, row
    ``b`` optimal for ``deadlines[b]``. Infeasible points raise ValueError
    naming the offending deadline."""
    deadlines = list(deadlines)
    tight = []
    for d in deadlines:
        try:
            tight.append(tighten_for_deadline(problem, time_tables, float(d)))
        except ValueError as e:
            raise ValueError(f"deadline_sweep point {d}: {e}") from e
    X = _solve_cached(tight, backend, engine, split_regimes=False)[:, : problem.n]
    if check:
        for p, x in zip(tight, X):
            validate_schedule(p, x)
    return X.astype(np.int64)


# ---------------------------------------------------------------------------
# deprecated shims (PR 7, DESIGN.md §15) — use repro.core.solver.Solver
# ---------------------------------------------------------------------------


def schedule(problem: Problem, algorithm: str = "auto", check: bool = True) -> np.ndarray:
    """Deprecated shim: use ``Solver().solve(problem)`` (`.schedule` on the
    returned :class:`~repro.core.solver.Solution`). Bit-identical — same
    regime dispatch, same per-algorithm callables."""
    warn_deprecated("schedule", "Solver().solve(problem).schedule")
    return _schedule(problem, algorithm, check)[0]


def schedule_batch(
    problems,
    algorithm: str = "auto",
    check: bool = True,
    backend=None,
    engine=None,
):
    """Deprecated shim: use ``Solver(engine=...).solve(problems)``
    (`.schedules` on the returned :class:`~repro.core.solver.SolutionBatch`).

    Dispatch (unchanged, now documented on the facade):
      * ``algorithm="auto"``: the engine's regime-split path — each
        instance's regime picks its algorithm (one shared rule with the
        serial dispatch), MarIn/MarCo instances ride the batched marginal
        selection kernel (§13), MarDecUn/MarDec solve on the host, and only
        the arbitrary-regime remainder pays the batched DP; results come
        back in original problem order.
      * any DP algorithm name (``dp``, ``dp_jax``, ``dp_batch``,
        ``dp_jax_pallas``): ALL instances go through the batched DP
        (``dp_jax_pallas`` selects the Pallas kernel backend).
      * any other named algorithm: a plain per-instance loop.

    ``engine``: an explicit :class:`~repro.core.sweep.SweepEngine` (e.g. a
    sharded one); ``None`` uses the process-wide default for ``backend``.
    Requesting a backend that contradicts the given engine's raises
    ValueError. Returns a list of ``(n_b,)`` int64 schedules.
    """
    warn_deprecated("schedule_batch", "Solver(engine=...).solve(problems).schedules")
    return _schedule_batch(problems, algorithm, check, backend, engine)


def schedule_cost(problem: Problem, algorithm: str = "auto") -> float:
    return total_cost(problem, _schedule(problem, algorithm)[0])


def schedule_with_deadline(
    problem: Problem,
    time_tables,
    deadline: float,
    algorithm: str = "auto",
) -> np.ndarray:
    """Deprecated shim: use ``Solver().solve(problem, deadline=D,
    time_tables=tt)``.

    Energy-minimal schedule subject to a round deadline (beyond-paper). The
    ε-constraint reduces cleanly to the SAME problem: a deadline on each
    device's computation time is just a tighter upper limit
    ``U_i' = max{j : time_i(j) <= deadline}`` — feasible sets stay
    intervals, so every optimal algorithm applies unchanged
    (:func:`tighten_for_deadline`). Raises ValueError if the deadline makes
    the instance infeasible.

    Args:
      time_tables: list of (U_i+1,) arrays; time_tables[i][j] = seconds for
        device i to train j batches (monotone non-decreasing).
      deadline: maximum allowed per-device time (the target round duration).
    """
    warn_deprecated(
        "schedule_with_deadline", "Solver().solve(problem, deadline=D, time_tables=tt)"
    )
    return _schedule_with_deadline(problem, time_tables, deadline, algorithm)


def tighten_for_deadline(problem: Problem, time_tables, deadline: float) -> Problem:
    """The deadline-tightened instance: ``U_i' = max{j : time_i(j) <= D}``
    (clipped to ``U_i``). Raises ValueError if infeasible — a device cannot
    meet its lower limit, or fleet capacity drops below ``T``.

    NOT deprecated: this is the ε-constraint reduction itself, shared by the
    facade's ``sweep``/``frontier`` paths and ``repro.core.pareto``."""
    new_upper = []
    for i in range(problem.n):
        t = np.asarray(time_tables[i], dtype=np.float64)
        feas = np.nonzero(t <= deadline)[0]
        u = int(feas.max()) if len(feas) else -1
        if u < int(problem.lower[i]):
            raise ValueError(
                f"deadline {deadline} infeasible: device {i} cannot do its "
                f"lower limit {int(problem.lower[i])} batches in time"
            )
        new_upper.append(min(u, int(problem.upper[i])))
    if sum(new_upper) < problem.T:
        raise ValueError(
            f"deadline {deadline} infeasible: fleet capacity "
            f"{sum(new_upper)} < T={problem.T}"
        )
    return Problem(
        T=problem.T,
        lower=problem.lower,
        upper=np.asarray(new_upper),
        cost_tables=tuple(
            tbl[: u + 1] for tbl, u in zip(problem.cost_tables, new_upper)
        ),
    )


def deadline_sweep(
    problem: Problem,
    time_tables,
    deadlines,
    check: bool = True,
    backend=None,
    engine=None,
) -> np.ndarray:
    """Deprecated shim: use ``Solver(engine=...).sweep(problem, tt,
    deadlines)`` — or :meth:`~repro.core.solver.Solver.frontier` for the
    pruned Pareto set.

    Energy-minimal schedules for a whole grid of deadlines in ONE batched DP
    solve: the ``B`` deadline-tightened instances (same ``n`` and ``T``,
    progressively looser ``U_i``) stack through the sweep engine, so the
    entire ε-constraint sweep costs one kernel launch — and, once its shape
    bucket is warm, zero compilations. Returns a ``(B, n)`` int64 array, row
    ``b`` optimal for ``deadlines[b]``; raises ValueError (naming the
    offending deadline) if any point is infeasible.
    """
    warn_deprecated("deadline_sweep", "Solver(engine=...).sweep(problem, tt, deadlines)")
    return _deadline_sweep(problem, time_tables, deadlines, check, backend, engine)
