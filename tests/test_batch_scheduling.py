"""Batched scheduling engine (DESIGN.md §9): ProblemBatch packing, the
vmapped/stacked min-plus DP, batched backtracking, dispatch, sweeps, and the
FL scenario-planning hook.

Core claim under test: ``solve_schedule_dp_batch`` over B stacked instances
is EQUIVALENT to looping the per-instance solvers — bit-identical schedules
vs ``solve_schedule_dp_jax`` (same float32 program, same tie-breaking) and
equal assignments/costs vs the numpy ``solve_schedule_dp``, across mixed
regimes and ragged ``n`` / ``U_i`` / ``T``.
"""

import numpy as np
import pytest

from repro.core import (
    Problem,
    ProblemBatch,
    deadline_sweep,
    random_problem,
    remove_lower_limits,
    schedule_batch,
    solve_schedule_dp,
    solve_schedule_dp_batch,
    solve_schedule_dp_jax,
    total_cost,
    total_cost_batch,
    validate_schedule,
    validate_schedule_batch,
)

REGIMES = ("arbitrary", "linear", "increasing", "decreasing")


def random_mixed_problems(rng, B, max_n=6, max_T=24):
    """B instances with ragged n, ragged U_i, ragged T, mixed regimes."""
    out = []
    for b in range(B):
        n = int(rng.integers(1, max_n + 1))
        T = int(rng.integers(max(1, n), max_T + 1))
        out.append(random_problem(rng, n=n, T=T, regime=REGIMES[b % len(REGIMES)]))
    return out


# ---------------------------------------------------------------------------
# ProblemBatch packing
# ---------------------------------------------------------------------------


def test_problem_batch_roundtrip():
    rng = np.random.default_rng(11)
    probs = random_mixed_problems(rng, 7)
    batch = ProblemBatch.from_problems(probs)
    assert batch.B == 7
    assert batch.n == max(p.n for p in probs)
    assert batch.W == max(int(p.upper.max()) for p in probs) + 1
    for b, p in enumerate(probs):
        q = batch.instance(b)
        assert q.T == p.T
        assert np.array_equal(q.lower[: p.n], p.lower)
        assert np.array_equal(q.upper[: p.n], p.upper)
        for i in range(p.n):
            np.testing.assert_allclose(q.cost_tables[i], p.cost_tables[i])
        # padded resources can only take 0 tasks at 0 cost
        for i in range(p.n, batch.n):
            assert int(q.upper[i]) == 0 and float(q.cost_tables[i][0]) == 0.0


def test_problem_batch_lower_limit_removal_matches_per_instance():
    rng = np.random.default_rng(12)
    probs = random_mixed_problems(rng, 9)
    batch = ProblemBatch.from_problems(probs)
    b0 = remove_lower_limits(batch)
    assert np.all(b0.lower == 0)
    for b, p in enumerate(probs):
        p0 = remove_lower_limits(p)
        assert int(b0.T[b]) == p0.T
        assert np.array_equal(b0.upper[b, : p.n], p0.upper)
        for i in range(p.n):
            u = int(p0.upper[i])
            np.testing.assert_allclose(
                b0.costs[b, i, : u + 1], p0.cost_tables[i][: u + 1]
            )


def test_problem_batch_validation_errors():
    rng = np.random.default_rng(13)
    p = random_problem(rng, n=3, T=8, regime="linear")
    with pytest.raises(ValueError):
        ProblemBatch.from_problems([])
    batch = ProblemBatch.from_problems([p])
    bad = ProblemBatch(
        T=np.array([10**6]), lower=batch.lower, upper=batch.upper, costs=batch.costs
    )
    with pytest.raises(ValueError):
        bad.validate()


# ---------------------------------------------------------------------------
# Batched DP == per-instance solvers (randomized, mixed regimes, ragged)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("B", [1, 7, 32])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_batch_dp_equals_per_instance(B, seed):
    rng = np.random.default_rng(100 + seed)
    probs = random_mixed_problems(rng, B)
    X = solve_schedule_dp_batch(probs)
    assert X.shape == (B, max(p.n for p in probs))
    for b, p in enumerate(probs):
        row = X[b, : p.n]
        validate_schedule(p, row)
        # padded resources are always assigned 0
        assert np.all(X[b, p.n :] == 0)
        # bit-identical vs the per-instance jitted solver
        assert np.array_equal(row, solve_schedule_dp_jax(p)), (b, row)
        # equal cost (and, with float32-safe tables, equal schedule) vs numpy
        x_np = solve_schedule_dp(p)
        assert total_cost(p, row) == pytest.approx(total_cost(p, x_np), rel=1e-5)


def test_batch_dp_prebuilt_batch_and_costs():
    rng = np.random.default_rng(42)
    probs = random_mixed_problems(rng, 5)
    batch = ProblemBatch.from_problems(probs)
    X = solve_schedule_dp_batch(batch)
    validate_schedule_batch(batch, X)
    tc = total_cost_batch(batch, X)
    for b, p in enumerate(probs):
        assert tc[b] == pytest.approx(total_cost(p, X[b, : p.n]), rel=1e-12)


def test_batch_dp_ragged_T_uses_per_instance_t_star():
    """Same fleet, very different workloads: padding to T_max must not leak
    across instances."""
    rng = np.random.default_rng(7)
    base = random_problem(rng, n=5, T=40, regime="arbitrary", with_lower=False)
    probs = [
        Problem(T=t, lower=base.lower, upper=base.upper, cost_tables=base.cost_tables)
        for t in (1, 7, 23, 40)
    ]
    X = solve_schedule_dp_batch(probs)
    for b, p in enumerate(probs):
        assert int(X[b].sum()) == p.T
        assert np.array_equal(X[b], solve_schedule_dp_jax(p))


def test_batch_dp_with_lower_limits():
    rng = np.random.default_rng(8)
    probs = [random_problem(rng, n=4, T=16, regime="arbitrary") for _ in range(6)]
    assert any(int(p.lower.sum()) > 0 for p in probs)
    X = solve_schedule_dp_batch(probs)
    for b, p in enumerate(probs):
        validate_schedule(p, X[b, : p.n])
        assert total_cost(p, X[b, : p.n]) == pytest.approx(
            total_cost(p, solve_schedule_dp(p)), rel=1e-5
        )


# ---------------------------------------------------------------------------
# Batched Pallas kernel vs batched reference
# ---------------------------------------------------------------------------


def _random_rows(rng, B, Tp, W):
    k = rng.uniform(0, 100, size=(B, Tp)).astype(np.float32)
    k[rng.random((B, Tp)) < 0.3] = 1e30
    k[:, 0] = 0.0
    c = rng.uniform(0, 10, size=(B, W)).astype(np.float32)
    c[rng.random((B, W)) < 0.1] = 1e30
    return k, c


@pytest.mark.parametrize("B,Tp,W,BT", [
    (1, 64, 16, 128),
    (4, 300, 33, 128),
    pytest.param(8, 255, 64, 128, marks=pytest.mark.slow),  # larger interpret-mode sweep
])
def test_batched_pallas_matches_batched_ref(B, Tp, W, BT):
    from repro.kernels import minplus_pallas_batch, minplus_step_ref_batch

    rng = np.random.default_rng(B * 1000 + Tp + W)
    k, c = _random_rows(rng, B, Tp, W)
    rv, ri = minplus_step_ref_batch(k, c)
    pv, pi = minplus_pallas_batch(k, c, BT=BT, interpret=True)
    np.testing.assert_allclose(np.asarray(pv), np.asarray(rv), rtol=1e-6)
    # argmin: reconstructed value must equal the min (ties may differ)
    pi = np.asarray(pi)
    src = np.arange(Tp)[None, :] - pi
    ok = src >= 0
    rows = np.arange(B)[:, None]
    recon = np.where(
        ok, k[rows, np.maximum(src, 0)] + np.take_along_axis(c, pi, axis=1), 1e30
    )
    recon = np.minimum(recon, 1e30)
    np.testing.assert_allclose(recon, np.asarray(rv), rtol=1e-6)


def test_batched_ref_matches_unbatched_rows():
    from repro.kernels import minplus_step_ref, minplus_step_ref_batch

    rng = np.random.default_rng(3)
    k, c = _random_rows(rng, 6, 90, 40)
    bv, bi = minplus_step_ref_batch(k, c)
    for b in range(6):
        v, i = minplus_step_ref(k[b], c[b])
        np.testing.assert_array_equal(np.asarray(bv)[b], np.asarray(v))
        np.testing.assert_array_equal(np.asarray(bi)[b], np.asarray(i))


def test_batch_dp_pallas_backend_end_to_end():
    rng = np.random.default_rng(9)
    probs = [random_problem(rng, n=3, T=10, regime=r) for r in ("arbitrary", "decreasing")]
    Xp = solve_schedule_dp_batch(probs, backend="pallas")
    Xr = solve_schedule_dp_batch(probs, backend="ref")
    for b, p in enumerate(probs):
        validate_schedule(p, Xp[b, : p.n])
        assert total_cost(p, Xp[b, : p.n]) == pytest.approx(
            total_cost(p, Xr[b, : p.n]), rel=1e-5
        )


# ---------------------------------------------------------------------------
# schedule_batch dispatch + deadline_sweep
# ---------------------------------------------------------------------------


def test_schedule_batch_auto_dispatch_optimal():
    rng = np.random.default_rng(20)
    probs = random_mixed_problems(rng, 12)
    xs = schedule_batch(probs, "auto")
    assert len(xs) == len(probs)
    for p, x in zip(probs, xs):
        validate_schedule(p, x)
        assert total_cost(p, x) == pytest.approx(
            total_cost(p, solve_schedule_dp(p)), rel=1e-5, abs=1e-9
        )


def test_schedule_batch_named_algorithms():
    rng = np.random.default_rng(21)
    probs = [random_problem(rng, n=4, T=15, regime="increasing") for _ in range(4)]
    for alg in ("dp_batch", "marin", "olar"):
        xs = schedule_batch(probs, alg)
        for p, x in zip(probs, xs):
            validate_schedule(p, x)
    with pytest.raises(ValueError):
        schedule_batch(probs, "no_such_algorithm")
    assert schedule_batch([]) == []


def test_deadline_sweep_matches_looped_and_is_monotone():
    from repro.core.scheduler import schedule_with_deadline

    rng = np.random.default_rng(22)
    n, T = 5, 30
    p = random_problem(rng, n=n, T=T, regime="increasing")
    speeds = rng.uniform(0.5, 3.0, size=n)
    times = [np.arange(int(u) + 1) / s for u, s in zip(p.upper, speeds)]
    x_free = solve_schedule_dp(p)
    d_max = max(float(times[i][int(x_free[i])]) for i in range(n))
    deadlines = [d_max * f for f in (1.0, 1.5, 2.5, 10.0)]

    X = deadline_sweep(p, times, deadlines)
    assert X.shape == (len(deadlines), n)
    prev = None
    for d, x in zip(deadlines, X):
        validate_schedule(p, x)
        for i in range(n):
            assert times[i][int(x[i])] <= d + 1e-9
        x_loop = schedule_with_deadline(p, times, d, algorithm="dp_jax")
        assert total_cost(p, x) == pytest.approx(total_cost(p, x_loop), rel=1e-5)
        e = total_cost(p, x)
        assert prev is None or e <= prev + 1e-9
        prev = e


def test_deadline_sweep_infeasible_point_raises():
    rng = np.random.default_rng(23)
    p = random_problem(rng, n=3, T=10, regime="linear")
    times = [np.arange(int(u) + 1) * 1.0 for u in p.upper]
    with pytest.raises(ValueError, match="deadline_sweep point"):
        deadline_sweep(p, times, [100.0, 0.5])


# ---------------------------------------------------------------------------
# FL scenario-planning hook
# ---------------------------------------------------------------------------


def test_server_scenario_planning_hook():
    import jax.numpy as jnp

    from repro.fl import EnergyEstimator, FederatedServer, make_fleet
    from repro.fl.server import apply_dropout
    from repro.optim.optimizers import sgd

    rng = np.random.default_rng(0)
    fleet = make_fleet(rng, 6, max_batches=12)
    est = EnergyEstimator(fleet)
    est.calibrate(rng)

    def loss_fn(params, batch):
        return jnp.mean((params["w"] * batch[..., 0] - batch[..., 1]) ** 2)

    server = FederatedServer(
        loss_fn,
        {"w": jnp.ones(())},
        sgd(1e-2),
        est,
        round_T=20,
        scenario_T_candidates=[10, 30, 10**9],  # last one clamps to capacity
        scenario_dropouts=[(0,), (1, 2)],
    )
    batches = rng.normal(size=(6, 4, 2, 2)).astype(np.float32)
    res = server.run_round(0, batches, rng)
    assert res.scenarios is not None
    rep = res.scenarios
    assert len(rep.labels) == 5
    assert rep.assignments.shape == (5, 6)
    assert rep.energies.shape == (5,)
    # each scenario's schedule is optimal for its instance
    cap = sum(d.max_batches for d in fleet)
    base = est.problem(20)
    expected = [
        est.problem(10),
        est.problem(30),
        est.problem(cap),
        apply_dropout(base, (0,)),
        apply_dropout(base, (1, 2)),
    ]
    for b, p in enumerate(expected):
        validate_schedule(p, rep.assignments[b])
        assert rep.energies[b] == pytest.approx(
            total_cost(p, solve_schedule_dp(p)), rel=1e-5
        )
    # dropout scenarios assign nothing to dropped clients
    assert rep.assignments[3, 0] == 0
    assert rep.assignments[4, 1] == 0 and rep.assignments[4, 2] == 0


def test_server_explicit_round_T_param():
    import jax.numpy as jnp

    from repro.fl import EnergyEstimator, FederatedServer, make_fleet
    from repro.optim.optimizers import sgd

    rng = np.random.default_rng(1)
    fleet = make_fleet(rng, 4, max_batches=10)
    est = EnergyEstimator(fleet)
    est.calibrate(rng)

    def loss_fn(params, batch):
        return jnp.mean((params["w"] * batch[..., 0] - batch[..., 1]) ** 2)

    server = FederatedServer(loss_fn, {"w": jnp.ones(())}, sgd(1e-2), est, round_T=12)
    batches = rng.normal(size=(4, 4, 2, 2)).astype(np.float32)
    res = server.run_round(0, batches, rng)
    assert res.scenarios is None
    assert int(res.assignments.sum()) == 12
    # None falls back to half the round-tensor capacity, and the attribute
    # can still be set post-construction (run_campaign does this)
    server2 = FederatedServer(loss_fn, {"w": jnp.ones(())}, sgd(1e-2), est)
    assert server2.round_T is None
    server2.round_T = 8
    res2 = server2.run_round(0, batches, rng)
    assert int(res2.assignments.sum()) == 8
