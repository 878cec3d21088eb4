"""Freeze the public surface (PR 8).

``repro.__all__`` is the supported API; anything else is internal machinery
or a deprecated shim. These tests fail if a new top-level entrypoint appears
anywhere but the ``repro`` facade, or if importing the library emits a
DeprecationWarning — both must be deliberate, reviewed changes.
"""

from __future__ import annotations

import os
import subprocess
import sys

import pytest

import repro
import repro.core
import repro.fl
import repro.serve

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# The supported surface. Adding a name here is an API commitment; removing
# one is a breaking change. Keep sorted.
FACADE = [
    "CircuitBreaker",
    "DriftInjector",
    "DriftPlan",
    "FaultInjector",
    "FaultPlan",
    "FleetSolution",
    "ParetoFrontier",
    "PlanPolicy",
    "Problem",
    "ProblemBatch",
    "RetryPolicy",
    "SchedulerService",
    "Solution",
    "SolutionBatch",
    "Solver",
    "TransientEngineError",
]

# Subpackage surfaces, frozen so a new entrypoint added there without a
# matching facade decision trips this test.
CORE_ALL = {
    "ALGORITHMS", "CircuitBreaker", "CostWindows", "DEVICE_CLASSES",
    "FleetSolution", "ItemClass", "JOULES_PER_KWH", "MC2MKPSolution",
    "ParetoFrontier", "ParetoPoint", "PlanPolicy", "Problem", "ProblemBatch",
    "RetryPolicy", "Solution", "SolutionBatch", "Solver", "SweepEngine",
    "TransientEngineError", "is_transient", "retry_call",
    "brute_force_schedule",
    "bucket_shape", "candidate_deadlines", "carbon_cost_table",
    "classify_regimes", "cluster_clients", "deadline_grid", "deadline_sweep",
    "default_engine", "device_fleet_problem", "feasible_deadline_range",
    "frontier_by_window", "greedy_marginal", "linear_cost", "make_sweep_mesh",
    "marco", "marco_batch", "mardec", "mardec_batch", "mardecun",
    "mardecun_batch", "marin", "marin_batch", "mc2mkp_matrices",
    "measured_cost", "olar", "pareto_frontier", "proportional",
    "random_problem", "random_schedule", "remove_lower_limits",
    "restore_lower_limits", "schedule", "schedule_batch",
    "schedule_with_deadline", "select_algorithm", "select_algorithm_batch",
    "solve_dp_batch_cached", "solve_fleet", "solve_fused_batch_jax",
    "solve_mc2mkp", "solve_schedule_batch_cached", "solve_schedule_dp",
    "sublinear_cost", "superlinear_cost", "tighten_for_deadline",
    "total_cost", "total_cost_batch", "uniform", "validate_schedule",
    "validate_schedule_batch",
}

FL_ALL = {
    "AdaptiveCoordinator", "AdaptiveRoundStats", "AsyncCampaignRunner",
    "CampaignHistory", "CampaignRunner", "ClientFault",
    "DeviceProfile", "DriftDetector", "DriftInjector", "DriftPlan",
    "EnergyEstimator", "FLRoundResult", "FaultInjector",
    "FaultPlan", "FederatedServer", "FlakyEngine", "PipelineStats",
    "PlanFuture", "PlanPolicy", "RecoveryInfo", "RoundFaults", "RoundPlan",
    "ScenarioReport", "SerialPlanExecutor", "ThreadPlanExecutor",
    "WatermarkStats", "apply_dropout", "load_campaign_checkpoint",
    "local_train", "make_client_fn", "make_fleet", "proportional_greedy",
    "residual_problem", "run_campaign", "save_campaign_checkpoint",
    "watermark_split",
}

SERVE_ALL = {
    "FleetFuture", "FrontierFuture", "ScheduleFuture", "SchedulerService",
    "ServiceClosed", "ServiceOverloaded", "coalesce_key", "combine_batches",
    "pow2_ladder", "warm_batch",
}


def test_facade_all_is_frozen():
    assert list(repro.__all__) == FACADE
    assert sorted(repro.__all__) == list(repro.__all__), "keep __all__ sorted"


@pytest.mark.parametrize("name", FACADE)
def test_facade_names_resolve(name):
    obj = getattr(repro, name)
    assert obj is not None
    # every facade name must originate inside the package
    mod = getattr(obj, "__module__", "repro")
    assert mod.startswith("repro")


def test_subpackage_surfaces_are_frozen():
    assert set(repro.core.__all__) == CORE_ALL, (
        "repro.core.__all__ changed — new entrypoints must be a deliberate "
        "facade decision (update tests/test_public_api.py AND repro/__init__.py)"
    )
    assert set(repro.fl.__all__) == FL_ALL
    assert set(repro.serve.__all__) == SERVE_ALL


def test_facade_is_subset_of_subpackages():
    exported = CORE_ALL | FL_ALL | SERVE_ALL
    assert set(FACADE) <= exported


def test_import_emits_no_deprecation_warning():
    # Subprocess: -W error turns any DeprecationWarning raised at import
    # time (ours or a dependency's triggered by our imports) into a failure.
    proc = subprocess.run(
        [
            sys.executable,
            "-W", "error::DeprecationWarning",
            "-c", "import repro, repro.core, repro.fl, repro.serve",
        ],
        capture_output=True,
        text=True,
        timeout=240,
        env={**os.environ, "PYTHONPATH": os.path.join(REPO, "src")},
    )
    assert proc.returncode == 0, (
        f"importing repro raised under -W error::DeprecationWarning:\n"
        f"{proc.stderr[-3000:]}"
    )
