"""Solver facade + deprecated shims (PR 7, DESIGN.md §15).

Claims under test:
  * all six legacy entrypoints — ``schedule``, ``schedule_batch``,
    ``schedule_with_deadline``, ``deadline_sweep``,
    ``solve_dp_batch_cached``, ``solve_schedule_batch_cached`` — return
    BIT-IDENTICAL results to the facade verbs that replace them;
  * each shim warns exactly ONCE per process (DeprecationWarning naming the
    replacement), regardless of call count;
  * :class:`Solution` / :class:`SolutionBatch` round-trip: indexing a batch
    yields per-instance views whose fields match, including through the
    serve layer (``Solver(service=...)``);
  * substrate conflicts (engine vs backend, engine vs service.engine) raise
    at construction;
  * the single-instance device DP names (``dp_jax``, ``dp_jax_pallas``) run
    the shared sweep engine's fused program: one compile, then warm hits.
"""

import warnings

import numpy as np
import pytest

from repro.core import (
    Problem,
    Solver,
    SweepEngine,
    deadline_sweep,
    random_problem,
    schedule,
    schedule_batch,
    schedule_with_deadline,
    solve_dp_batch_cached,
    solve_schedule_batch_cached,
    total_cost,
)
from repro.core._deprecation import reset_deprecation_warnings
from repro.core.sweep import default_engine, reset_default_engines
from repro.core.scheduler import _schedule
from repro.serve import SchedulerService

from _fused_ref import solve_one_unbucketed

REGIMES = ("arbitrary", "linear", "increasing", "decreasing")


@pytest.fixture(autouse=True)
def _quiet_shims():
    """Each test sees fresh warn-once state and never fails on the shims'
    own DeprecationWarnings."""
    reset_deprecation_warnings()
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        yield
    reset_deprecation_warnings()


def mixed_problems(seed=0, B=6, n=5, T=14):
    rng = np.random.default_rng(seed)
    return [
        random_problem(rng, n=n, T=T, regime=REGIMES[b % len(REGIMES)], max_upper=8)
        for b in range(B)
    ]


def time_tables_for(p, seed=1):
    rng = np.random.default_rng(seed)
    tt = [np.sort(rng.uniform(0.1, 2.0, int(u) + 1)) for u in p.upper]
    for t in tt:
        t[0] = 0.0
    return tt


def test_schedule_shim_bit_identity():
    for p in mixed_problems():
        for alg in ("auto", "dp", "marin" if p.regime() == "MarIn" else "auto"):
            old = schedule(p, algorithm=alg)
            new = Solver().solve(p, algorithm=alg)
            assert np.array_equal(old, new.schedule)
            assert new.algorithm != "auto"  # resolved, never leaked
            assert new.objective == total_cost(p, old)
            assert new.regime == p.regime()


def test_schedule_batch_shim_bit_identity():
    probs = mixed_problems(seed=2)
    eng = SweepEngine()
    for alg in ("auto", "dp_batch"):
        old = schedule_batch(probs, algorithm=alg, engine=eng)
        new = Solver(engine=eng).solve(probs, algorithm=alg)
        assert len(old) == len(new) == len(probs)
        for xo, xn in zip(old, new.schedules):
            assert np.array_equal(xo, xn)
    # DP-name solves carry the free final-row telemetry
    assert Solver(engine=eng).solve(probs, algorithm="dp_batch").k_last is not None


def test_schedule_with_deadline_shim_bit_identity():
    p = mixed_problems(seed=4, B=1)[0]
    tt = time_tables_for(p)
    D = float(max(t[-1] for t in tt))  # loosest: always feasible
    old = schedule_with_deadline(p, tt, D)
    new = Solver().solve(p, deadline=D, time_tables=tt)
    assert np.array_equal(old, new.schedule)
    assert new.deadline == D
    with pytest.raises(ValueError):
        Solver().solve(p, deadline=D)  # time_tables go with deadline


def test_deadline_sweep_shim_bit_identity():
    p = mixed_problems(seed=5, B=1)[0]
    tt = time_tables_for(p, seed=6)
    hi = float(max(t[-1] for t in tt))
    deadlines = np.linspace(0.7 * hi, hi, 5)
    eng = SweepEngine()
    old = deadline_sweep(p, tt, deadlines, engine=eng)
    new = Solver(engine=eng).sweep(p, tt, deadlines)
    assert np.array_equal(old, np.stack(new.schedules))
    assert np.array_equal(new.deadlines, deadlines)
    assert new.k_last is not None and len(new.k_last) == len(deadlines)
    # both spellings name the offending point on infeasible grids
    with pytest.raises(ValueError, match="sweep point"):
        Solver(engine=eng).sweep(p, tt, [1e-9])
    with pytest.raises(ValueError, match="deadline_sweep point"):
        deadline_sweep(p, tt, [1e-9], engine=eng)


def test_cached_solve_shims_bit_identity():
    probs = mixed_problems(seed=7)
    eng = SweepEngine()
    old_dp = solve_dp_batch_cached(probs, engine=eng)
    new_dp = Solver(engine=eng).solve(probs, algorithm="dp_batch")
    for b, p in enumerate(probs):
        assert np.array_equal(old_dp[b, : p.n], new_dp.schedules[b])
    old_split = solve_schedule_batch_cached(probs, engine=eng)
    new_split = Solver(engine=eng).solve(probs)  # auto = regime-split path
    for b, p in enumerate(probs):
        assert np.array_equal(old_split[b, : p.n], new_split.schedules[b])


def test_shims_warn_exactly_once():
    p = mixed_problems(seed=8, B=1)[0]
    tt = time_tables_for(p, seed=8)
    reset_deprecation_warnings()
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        schedule(p)
        schedule(p)  # second call: silent
        deadline_sweep(p, tt, [float(max(t[-1] for t in tt))])
        deadline_sweep(p, tt, [float(max(t[-1] for t in tt))])
    dep = [w for w in rec if issubclass(w.category, DeprecationWarning)]
    assert len(dep) == 2  # one per distinct shim, not per call
    assert any("schedule is deprecated" in str(w.message) for w in dep)
    assert any("Solver" in str(w.message) for w in dep)
    # the facade itself never warns
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        Solver().solve(p)
    assert not [w for w in rec if issubclass(w.category, DeprecationWarning)]


def test_solution_batch_roundtrip_and_serve():
    probs = mixed_problems(seed=9)
    eng = SweepEngine()
    direct = Solver(engine=eng).solve(probs)
    with SchedulerService(engine=eng, max_batch=64, max_delay_s=0.005) as svc:
        served = Solver(service=svc).solve(probs)
        with pytest.raises(ValueError, match="conflicts"):
            Solver(service=svc, engine=SweepEngine())
    assert np.array_equal(direct.objectives, served.objectives)
    for xd, xs in zip(direct.schedules, served.schedules):
        assert np.array_equal(xd, xs)
    assert served.algorithms == direct.algorithms
    # batch -> per-instance Solution views
    assert len(served) == len(probs)
    for b, sol in enumerate(served):
        assert np.array_equal(sol.schedule, served.schedules[b])
        assert sol.objective == float(served.objectives[b])
        assert sol.regime == probs[b].regime()
        assert sol.algorithm == served.algorithms[b]
    assert np.array_equal(served[-1].schedule, served.schedules[-1])
    assert served.cache_stats is not None and "hits" in served.cache_stats


def test_substrate_conflicts_raise():
    eng = SweepEngine(backend="ref")
    other = "blocked" if eng.backend == "ref" else "ref"
    with pytest.raises(ValueError, match="conflicts"):
        Solver(engine=eng, backend=other)
    assert Solver(engine=eng, backend="ref").engine is eng


def test_solution_objective_is_exact_float64():
    p = Problem(
        T=3,
        lower=[0, 0],
        upper=[3, 3],
        cost_tables=(
            np.array([0.0, 0.1, 0.2, 0.3]),
            np.array([0.0, 0.15, 0.25, 0.35]),
        ),
    )
    sol = Solver().solve(p)
    assert sol.objective == total_cost(p, sol.schedule)  # host f64, exact


@pytest.mark.parametrize("name,backend", [("dp_jax", "auto"), ("dp_jax_pallas", "pallas")])
def test_device_dp_names_run_the_shared_engine(name, backend):
    reset_default_engines()
    p = random_problem(np.random.default_rng(17), n=5, T=30, regime="arbitrary")
    want = solve_one_unbucketed(p, backend)
    np.testing.assert_array_equal(Solver().solve(p, algorithm=name).schedule, want)
    s = default_engine(backend).cache_stats()
    assert (s["compiles"], s["misses"], s["hits"]) == (1, 1, 0), s
    np.testing.assert_array_equal(Solver().solve(p, algorithm=name).schedule, want)
    s = default_engine(backend).cache_stats()
    assert (s["compiles"], s["misses"], s["hits"]) == (1, 1, 1), s
    reset_default_engines()
