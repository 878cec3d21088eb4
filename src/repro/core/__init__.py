"""Core library: the paper's contribution.

Minimal Cost FL Schedule problem (Def. 1), the (MC)^2MKP knapsack problem and
its DP solution (Alg. 1), the monotone-regime algorithms MarIn/MarCo/
MarDecUn/MarDec (Algs. 2-7), cost-function families, and baselines.

The supported solve entrypoint is the :class:`Solver` facade (DESIGN.md §15):
``Solver().solve(...)`` / ``.sweep(...)`` / ``.frontier(...)``. The legacy
module-level entrypoints (``schedule``, ``schedule_batch``,
``schedule_with_deadline``, ``deadline_sweep``, ``solve_dp_batch_cached``,
``solve_schedule_batch_cached``) remain as bit-identical deprecated shims.
"""

from .baselines import greedy_marginal, olar, proportional, random_schedule, uniform
from .costs import (
    DEVICE_CLASSES,
    JOULES_PER_KWH,
    CostWindows,
    carbon_cost_table,
    device_fleet_problem,
    linear_cost,
    measured_cost,
    random_problem,
    sublinear_cost,
    superlinear_cost,
)
from .fleet import FleetSolution, PlanPolicy, cluster_clients, solve_fleet
from .jax_dp import solve_fused_batch_jax
from .marginal import marco, mardec, mardecun, marin
from .marginal_jax import (
    marco_batch,
    mardec_batch,
    mardecun_batch,
    marin_batch,
    select_algorithm_batch,
)
from .mc2mkp import (
    ItemClass,
    MC2MKPSolution,
    brute_force_schedule,
    mc2mkp_matrices,
    solve_mc2mkp,
    solve_schedule_dp,
)
from .resilience import (
    CircuitBreaker,
    RetryPolicy,
    TransientEngineError,
    is_transient,
    retry_call,
)
from .problem import (
    Problem,
    ProblemBatch,
    classify_regimes,
    remove_lower_limits,
    restore_lower_limits,
    total_cost,
    total_cost_batch,
    validate_schedule,
    validate_schedule_batch,
)
from .pareto import (
    ParetoFrontier,
    ParetoPoint,
    candidate_deadlines,
    deadline_grid,
    feasible_deadline_range,
    frontier_by_window,
    pareto_frontier,
)
from .scheduler import (
    ALGORITHMS,
    deadline_sweep,
    schedule,
    schedule_batch,
    schedule_with_deadline,
    select_algorithm,
    tighten_for_deadline,
)
from .solver import Solution, SolutionBatch, Solver
from .sweep import (
    SweepEngine,
    bucket_shape,
    default_engine,
    make_sweep_mesh,
    solve_dp_batch_cached,
    solve_schedule_batch_cached,
)

__all__ = [
    "Problem",
    "ProblemBatch",
    "remove_lower_limits",
    "restore_lower_limits",
    "total_cost",
    "total_cost_batch",
    "validate_schedule",
    "validate_schedule_batch",
    "ItemClass",
    "MC2MKPSolution",
    "solve_mc2mkp",
    "mc2mkp_matrices",
    "solve_schedule_dp",
    "solve_fused_batch_jax",
    "brute_force_schedule",
    "marin",
    "marco",
    "mardecun",
    "mardec",
    "marin_batch",
    "marco_batch",
    "mardecun_batch",
    "mardec_batch",
    "classify_regimes",
    "select_algorithm_batch",
    "solve_schedule_batch_cached",
    "olar",
    "uniform",
    "proportional",
    "random_schedule",
    "greedy_marginal",
    "schedule",
    "schedule_batch",
    "schedule_with_deadline",
    "deadline_sweep",
    "tighten_for_deadline",
    "select_algorithm",
    "ALGORITHMS",
    "Solver",
    "Solution",
    "SolutionBatch",
    "CircuitBreaker",
    "RetryPolicy",
    "TransientEngineError",
    "is_transient",
    "retry_call",
    "FleetSolution",
    "PlanPolicy",
    "cluster_clients",
    "solve_fleet",
    "ParetoFrontier",
    "ParetoPoint",
    "pareto_frontier",
    "frontier_by_window",
    "candidate_deadlines",
    "deadline_grid",
    "feasible_deadline_range",
    "CostWindows",
    "carbon_cost_table",
    "JOULES_PER_KWH",
    "SweepEngine",
    "bucket_shape",
    "default_engine",
    "make_sweep_mesh",
    "solve_dp_batch_cached",
    "DEVICE_CLASSES",
    "device_fleet_problem",
    "linear_cost",
    "superlinear_cost",
    "sublinear_cost",
    "measured_cost",
    "random_problem",
]
