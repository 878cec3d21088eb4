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
- ring sharding: the class-axis ring DP is bit-identical to the unsharded
  fused DP on a forced-8-device host (subprocess, same pattern as
  test_sweep_engine.py);
- PlanPolicy: FederatedServer legacy kwargs are warn-once shims that are
  bit-identical to the policy= spelling, and fleet-mode round planning
  goes through Solver.solve_fleet.
"""

from __future__ import annotations

import os
import subprocess
import sys
import textwrap
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

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


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


# ---------------------------------------------------------------------------
# class-axis ring sharding: bit-identical on a forced-8-device host
# ---------------------------------------------------------------------------


def test_ring_sharded_dp_bit_identical_forced_8_devices():
    src = textwrap.dedent(
        """
        import os
        os.environ["XLA_FLAGS"] = (
            "--xla_force_host_platform_device_count=8 " + os.environ.get("XLA_FLAGS", "")
        )
        import sys
        sys.path[:0] = %r
        import numpy as np
        import jax
        from repro.core import (SweepEngine, make_sweep_mesh, random_problem,
                                solve_schedule_dp_batch)

        assert len(jax.devices()) == 8, jax.devices()
        rng = np.random.default_rng(7)
        regimes = ("arbitrary", "linear", "increasing", "decreasing")
        probs = [
            random_problem(rng, n=int(rng.integers(3, 12)), T=int(rng.integers(8, 30)),
                           regime=regimes[b %% len(regimes)])
            for b in range(6)
        ]
        mesh = make_sweep_mesh()
        eng_ring = SweepEngine(ring_mesh=mesh)
        X_ring = eng_ring.solve(probs)
        X_ref = SweepEngine().solve(probs)
        X_un = solve_schedule_dp_batch(probs)
        assert np.array_equal(X_ring, X_ref), "ring-sharded != unsharded"
        assert np.array_equal(X_ring, X_un), "ring-sharded != uncached"

        # argmin slabs narrowed to int8 (W 64) and int16 (W 256) on every
        # ring device: schedules equal the int32 argmin path's
        import jax.numpy as jnp
        from _int32_dp import fused_int32
        from repro.core import Problem
        from repro.core.jax_dp import pack_problem
        from repro.core.problem import ProblemBatch

        for U in (63, 255):
            wide = []
            for b in range(3):
                n = int(rng.integers(3, 12))
                marg = rng.integers(1, 40, size=(n, U)).astype(float)
                marg[0] = -1.0  # client 0's curve falls: it takes the whole band
                tables = [np.concatenate([[500.0], 500.0 + np.cumsum(m)]) for m in marg]
                T = int(rng.integers(U, n * U * 2 // 3 + 1))
                wide.append(Problem(T=T, lower=np.zeros(n, np.int64),
                                    upper=np.full(n, U, np.int64), cost_tables=tables))
            X_wide = SweepEngine(ring_mesh=mesh).solve(wide)
            b0 = ProblemBatch.from_problems(wide)
            X32, _ = fused_int32(pack_problem(b0), jnp.asarray(b0.T, jnp.int32),
                                 int(b0.T.max()), "blocked")
            X32 = np.asarray(X32)
            assert np.array_equal(X_wide, X32), f"ring at U={U} != int32 argmin path"
            assert int(X32.max()) == U

        # mesh and ring_mesh are mutually exclusive
        try:
            SweepEngine(mesh=mesh, ring_mesh=mesh)
        except ValueError:
            pass
        else:
            raise AssertionError("mesh+ring_mesh should raise")

        # fleet solve riding on the ring engine stays exact at q=1
        from repro.core import Solver
        p = random_problem(np.random.default_rng(3), n=16, T=40)
        fsol = Solver(engine=SweepEngine(ring_mesh=make_sweep_mesh())).solve_fleet(
            p, clusters=4, quantum=1)
        flat = Solver(engine=SweepEngine()).solve([p], algorithm="dp_batch")
        assert abs(fsol.objective - float(flat.objectives[0])) <= 1e-6
        print("RING_OK")
        """
        % [os.path.join(REPO, "src"), os.path.dirname(os.path.abspath(__file__))]
    )
    proc = subprocess.run(
        [sys.executable, "-c", src], capture_output=True, text=True, timeout=420
    )
    assert proc.returncode == 0, (
        f"subprocess failed:\nSTDOUT:{proc.stdout}\nSTDERR:{proc.stderr[-3000:]}"
    )
    assert "RING_OK" in proc.stdout
