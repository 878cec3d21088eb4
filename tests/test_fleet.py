"""Hierarchical fleet scheduling (PR 8, DESIGN.md §16).

Covers the two-level decomposition in ``repro.core.fleet``:

- exactness: singleton clusters and/or quantum=1 reproduce the flat DP
  objective exactly (the decomposition's only loss is intra-cluster
  quantization);
- certified gap: for random small fleets the clustered objective stays
  within the self-reported ``gap_bound`` of the flat DP optimum, and never
  beats it (the flat DP is optimal);
- determinism: k-means labels are a pure function of (problem, seed) with
  canonical first-appearance numbering;
- PlanPolicy: FederatedServer legacy kwargs are warn-once shims that are
  bit-identical to the policy= spelling, and fleet-mode round planning
  goes through Solver.solve_fleet.
"""

from __future__ import annotations

import warnings

import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # pragma: no cover - clean container
    from _hypothesis_fallback import given, settings, st

from repro.core import (
    Problem,
    Solver,
    SweepEngine,
    cluster_clients,
    random_problem,
    solve_fleet,
    total_cost,
    validate_schedule,
)
from repro.core._deprecation import reset_deprecation_warnings
from repro.core.fleet import PlanPolicy


@pytest.fixture(autouse=True)
def _quiet_shims():
    reset_deprecation_warnings()
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        yield
    reset_deprecation_warnings()


def _flat_objective(problem: Problem, engine: SweepEngine) -> float:
    sol = Solver(engine=engine).solve([problem], algorithm="dp_batch")
    return float(sol.objectives[0])


def _rand(seed: int, n: int, T: int, regime: str = "arbitrary") -> Problem:
    return random_problem(np.random.default_rng(seed), n=n, T=T, regime=regime)


# ---------------------------------------------------------------------------
# exactness
# ---------------------------------------------------------------------------


def test_singleton_clusters_match_flat_dp_exactly():
    p = _rand(0, n=16, T=40)
    eng = SweepEngine()
    fsol = solve_fleet(p, engine=eng, clusters=16, quantum=1)
    assert fsol.num_clusters == 16
    assert fsol.gap_bound <= 1e-6
    flat = _flat_objective(p, eng)
    assert fsol.objective == pytest.approx(flat, rel=1e-9)
    validate_schedule(p, np.asarray(fsol.schedule))
    assert int(np.sum(fsol.schedule)) == p.T


@pytest.mark.parametrize("k", [2, 4, 7])
def test_quantum_one_is_exact_for_any_clustering(k):
    p = _rand(k, n=24, T=60)
    eng = SweepEngine()
    fsol = solve_fleet(p, engine=eng, clusters=k, quantum=1)
    flat = _flat_objective(p, eng)
    assert fsol.quantum == 1
    assert fsol.objective == pytest.approx(flat, rel=1e-9)
    assert fsol.gap_bound <= 1e-6
    validate_schedule(p, np.asarray(fsol.schedule))


# ---------------------------------------------------------------------------
# certified gap vs flat DP (hypothesis parity sweep, n <= 64)
# ---------------------------------------------------------------------------


@st.composite
def _fleet_cases(draw):
    return (
        draw(st.integers(min_value=0, max_value=10_000)),  # seed
        draw(st.integers(min_value=4, max_value=24)),  # n
        draw(st.integers(min_value=1, max_value=6)),  # k
        draw(st.integers(min_value=1, max_value=4)),  # q
        draw(st.sampled_from(["arbitrary", "increasing", "decreasing", "linear"])),
    )


@settings(max_examples=12, deadline=None)
@given(_fleet_cases())
def test_fleet_within_certified_gap_of_flat_dp(case):
    seed, n, k, q, regime = case
    p = _rand(seed, n=n, T=max(2 * n, 12), regime=regime)
    eng = SweepEngine()
    fsol = solve_fleet(p, engine=eng, clusters=min(k, n), quantum=q)
    flat = _flat_objective(p, eng)
    scale = max(abs(flat), 1.0)
    # flat DP is optimal: the decomposition can never beat it
    assert fsol.objective >= flat - 1e-6 * scale
    # ... and stays within its own certified bound
    assert fsol.objective <= flat * (1.0 + fsol.gap_bound) + 1e-6 * scale
    X = np.asarray(fsol.schedule)
    validate_schedule(p, X)
    assert int(X.sum()) == p.T
    assert fsol.objective == pytest.approx(total_cost(p, X), rel=1e-9)


def test_auto_parameters_and_solver_facade_agree():
    p = _rand(3, n=36, T=90)
    eng = SweepEngine()
    via_solver = Solver(engine=eng).solve_fleet(p)
    direct = solve_fleet(p, engine=SweepEngine())
    assert via_solver.objective == pytest.approx(direct.objective, rel=1e-12)
    assert np.array_equal(via_solver.schedule, direct.schedule)
    assert via_solver.num_clusters == max(1, round(np.sqrt(36)))


def test_solve_fleet_via_policy_defaults():
    p = _rand(9, n=20, T=50)
    pol = PlanPolicy(fleet_clusters=5, fleet_quantum=2, fleet_seed=7)
    a = Solver(engine=SweepEngine()).solve_fleet(p, policy=pol)
    b = solve_fleet(p, engine=SweepEngine(), clusters=5, quantum=2, seed=7)
    assert a.objective == pytest.approx(b.objective, rel=1e-12)
    assert np.array_equal(a.schedule, b.schedule)


# ---------------------------------------------------------------------------
# k-means determinism
# ---------------------------------------------------------------------------


def test_cluster_labels_deterministic_and_canonical():
    p = _rand(11, n=40, T=100)
    l1 = cluster_clients(p, clusters=6, seed=3)
    l2 = cluster_clients(p, clusters=6, seed=3)
    assert np.array_equal(l1, l2)
    # first-appearance canonical numbering: labels appear in increasing order
    seen = []
    for lab in l1:
        if lab not in seen:
            seen.append(int(lab))
    assert seen == sorted(seen) and seen[0] == 0
    # identity labels when k == n
    ident = cluster_clients(p, clusters=40, seed=3)
    assert np.array_equal(ident, np.arange(40))


def test_fleet_solution_deterministic_under_fixed_seed():
    p = _rand(21, n=48, T=120)
    a = solve_fleet(p, engine=SweepEngine(), seed=5)
    b = solve_fleet(p, engine=SweepEngine(), seed=5)
    assert np.array_equal(a.schedule, b.schedule)
    assert np.array_equal(a.labels, b.labels)
    assert a.objective == b.objective and a.gap_bound == b.gap_bound


# ---------------------------------------------------------------------------
# serve-layer front-end
# ---------------------------------------------------------------------------


def test_service_submit_fleet_matches_engine_path():
    from repro.serve import SchedulerService

    p = _rand(17, n=18, T=44)
    with SchedulerService(max_batch=16, max_delay_s=0.001) as svc:
        fut = svc.submit_fleet(p, clusters=4, quantum=2)
        fsol = fut.result(timeout=120)
        assert fut.done()
    ref = solve_fleet(p, engine=SweepEngine(), clusters=4, quantum=2)
    assert fsol.objective == pytest.approx(ref.objective, rel=1e-9)
    assert np.array_equal(fsol.schedule, ref.schedule)


# ---------------------------------------------------------------------------
# PlanPolicy: legacy FederatedServer kwargs are bit-identical warn-once shims
# ---------------------------------------------------------------------------


def _make_server(**kwargs):
    import jax.numpy as jnp

    from repro.fl import EnergyEstimator, FederatedServer, make_fleet
    from repro.optim.optimizers import sgd

    est = EnergyEstimator(make_fleet(np.random.default_rng(0), 6))
    est.calibrate(np.random.default_rng(1))
    loss = lambda params, batch: jnp.mean((params["w"] - batch) ** 2)  # noqa: E731
    return FederatedServer(loss, {"w": jnp.ones(())}, sgd(1e-2), est, **kwargs)


def test_legacy_server_kwargs_bit_identical_to_policy():
    s_old = _make_server(round_T=12, algorithm="auto")
    s_new = _make_server(policy=PlanPolicy(round_T=12, algorithm="auto"))
    po, pn = s_old.plan_round(0, 12), s_new.plan_round(0, 12)
    assert np.array_equal(po.assignments, pn.assignments)
    assert po.est_cost == pn.est_cost


def test_legacy_server_kwargs_warn_once_per_kwarg():
    reset_deprecation_warnings()
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        _make_server(round_T=12, algorithm="auto")
        _make_server(round_T=12)  # second use: already warned
    msgs = [str(w.message) for w in rec if issubclass(w.category, DeprecationWarning)]
    assert len(msgs) == 2
    assert any("FederatedServer(round_T=...)" in m for m in msgs)
    assert any("FederatedServer(algorithm=...)" in m for m in msgs)
    assert all("PlanPolicy" in m for m in msgs)


def test_policy_and_legacy_kwargs_are_mutually_exclusive():
    with pytest.raises(ValueError, match="not both"):
        _make_server(policy=PlanPolicy(), round_T=5)


def test_fleet_mode_round_plan_is_a_valid_schedule():
    s = _make_server(policy=PlanPolicy(fleet_clusters=3, round_T=12))
    plan = s.plan_round(0, 12)
    assert int(plan.assignments.sum()) == 12
    assert plan.est_cost >= 0.0


def test_plan_policy_validation():
    with pytest.raises(ValueError, match="frontier_mode requires time_tables"):
        PlanPolicy(frontier_mode="knee")
