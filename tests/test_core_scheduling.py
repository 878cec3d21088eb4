"""Unit + property tests for the core scheduling library.

Paper claims validated here:
  * Theorem 1: the (MC)^2MKP DP is optimal (== brute force).
  * Theorems 2/3/4/5: MarIn/MarCo/MarDecUn/MarDec are optimal on their
    regimes (== DP).
  * Section 5.2: lower-limit removal preserves optimal cost.
  * Section 3.1 insight: OLAR/uniform/greedy are NOT total-cost optimal in
    general (strictly worse on some instance).
"""

import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # clean container: deterministic fallback sampler
    from _hypothesis_fallback import given, settings, st

from repro.core import (
    ItemClass,
    Problem,
    Solver,
    brute_force_schedule,
    marco,
    mardec,
    mardecun,
    marin,
    olar,
    proportional,
    random_problem,
    random_schedule,
    remove_lower_limits,
    restore_lower_limits,
    schedule,
    select_algorithm,
    solve_mc2mkp,
    solve_schedule_dp,
    total_cost,
    uniform,
    validate_schedule,
)

# ---------------------------------------------------------------------------
# hypothesis strategies
# ---------------------------------------------------------------------------

regimes = st.sampled_from(["arbitrary", "linear", "increasing", "decreasing"])


@st.composite
def instances(draw, regime=None, max_n=5, max_T=14):
    rgm = regime or draw(regimes)
    n = draw(st.integers(1, max_n))
    T = draw(st.integers(max(1, n), max_T))
    seed = draw(st.integers(0, 2**32 - 1))
    rng = np.random.default_rng(seed)
    return random_problem(rng, n=n, T=T, regime=rgm)


# ---------------------------------------------------------------------------
# DP vs brute force (Theorem 1)
# ---------------------------------------------------------------------------


@settings(max_examples=120, deadline=None)
@given(instances())
def test_dp_optimal_vs_brute_force(p):
    dp = solve_schedule_dp(p)
    validate_schedule(p, dp)
    bf = brute_force_schedule(p)
    assert total_cost(p, dp) == pytest.approx(total_cost(p, bf), rel=1e-9)


@settings(max_examples=40, deadline=None)
@given(instances())
def test_jax_dp_matches_numpy_dp(p):
    xj = Solver().solve(p, algorithm="dp_jax").schedule
    validate_schedule(p, xj)
    assert total_cost(p, xj) == pytest.approx(total_cost(p, solve_schedule_dp(p)), rel=1e-5)


# ---------------------------------------------------------------------------
# Monotone-regime algorithms vs DP (Theorems 2-5)
# ---------------------------------------------------------------------------


@settings(max_examples=100, deadline=None)
@given(instances(regime="increasing"))
def test_marin_optimal(p):
    x = marin(p)
    validate_schedule(p, x)
    assert total_cost(p, x) == pytest.approx(total_cost(p, solve_schedule_dp(p)), rel=1e-9)


@settings(max_examples=100, deadline=None)
@given(instances(regime="linear"))
def test_marco_optimal(p):
    x = marco(p)
    validate_schedule(p, x)
    assert total_cost(p, x) == pytest.approx(total_cost(p, solve_schedule_dp(p)), rel=1e-9)


@settings(max_examples=100, deadline=None)
@given(st.integers(1, 5), st.integers(1, 14), st.integers(0, 2**32 - 1))
def test_mardecun_optimal(n, T, seed):
    rng = np.random.default_rng(seed)
    p = random_problem(rng, n=n, T=T, regime="decreasing", max_upper=T, with_lower=False)
    # force unlimited: U_i = T for all
    tables = tuple(
        np.interp(np.arange(T + 1), np.arange(len(t)), t) if len(t) < T + 1 else t[: T + 1]
        for t in p.cost_tables
    )
    # re-synthesize with proper decreasing tables of full width instead
    from repro.core.costs import sublinear_cost

    tables = tuple(
        sublinear_cost(T, float(rng.uniform(5, 40)), float(rng.uniform(2, 20)), float(rng.uniform(0, 0.2)))
        for _ in range(n)
    )
    p = Problem(T=T, lower=np.zeros(n, int), upper=np.full(n, T), cost_tables=tables)
    x = mardecun(p)
    validate_schedule(p, x)
    assert total_cost(p, x) == pytest.approx(total_cost(p, solve_schedule_dp(p)), rel=1e-9, abs=1e-9)


@settings(max_examples=100, deadline=None)
@given(instances(regime="decreasing"))
def test_mardec_optimal(p):
    x = mardec(p)
    validate_schedule(p, x)
    assert total_cost(p, x) == pytest.approx(total_cost(p, solve_schedule_dp(p)), rel=1e-9, abs=1e-9)


# ---------------------------------------------------------------------------
# Lower-limit removal (Section 5.2)
# ---------------------------------------------------------------------------


@settings(max_examples=60, deadline=None)
@given(instances())
def test_lower_limit_removal_equivalence(p):
    p0 = remove_lower_limits(p)
    p0.validate()
    assert p0.T == p.T - int(p.lower.sum())
    assert np.all(p0.lower == 0)
    x0 = solve_schedule_dp(p0)
    x = restore_lower_limits(p, x0)
    validate_schedule(p, x)
    assert total_cost(p, x) == pytest.approx(total_cost(p, solve_schedule_dp(p)), rel=1e-9)


# ---------------------------------------------------------------------------
# Baselines: validity everywhere, suboptimality somewhere
# ---------------------------------------------------------------------------


@settings(max_examples=60, deadline=None)
@given(instances())
def test_baselines_valid(p):
    rng = np.random.default_rng(0)
    for fn in (olar, uniform, proportional):
        validate_schedule(p, fn(p))
    validate_schedule(p, random_schedule(p, rng))


def test_baselines_not_total_cost_optimal():
    """On a decreasing-marginal fleet, consolidation wins; spreading
    baselines must be strictly worse somewhere."""
    rng = np.random.default_rng(7)
    worse = {"olar": False, "uniform": False, "proportional": False}
    for _ in range(50):
        p = random_problem(rng, n=4, T=12, regime="decreasing")
        opt = total_cost(p, solve_schedule_dp(p))
        for name, fn in (("olar", olar), ("uniform", uniform), ("proportional", proportional)):
            if total_cost(p, fn(p)) > opt + 1e-9:
                worse[name] = True
    assert all(worse.values()), worse


# ---------------------------------------------------------------------------
# General (MC)^2MKP (arbitrary weights, partial packing allowed)
# ---------------------------------------------------------------------------


def test_mc2mkp_partial_packing():
    """With arbitrary weights the knapsack may not be fillable; the solver
    must return the minimal-cost MAXIMAL packing (occupancy precedence)."""
    classes = [
        ItemClass(weights=[3, 5], costs=[10.0, 1.0]),
        ItemClass(weights=[4], costs=[2.0]),
    ]
    # capacity 8: 3+4=7 or 5+4=9(too big) -> maximal occupancy 7, cost 12
    sol = solve_mc2mkp(classes, T=8)
    assert sol.used_capacity == 7
    assert sol.total_cost == pytest.approx(12.0)
    # capacity 9: 5+4=9 fills it, cost 3 < alternative 3+4=7
    sol = solve_mc2mkp(classes, T=9)
    assert sol.used_capacity == 9
    assert sol.total_cost == pytest.approx(3.0)


def test_mc2mkp_occupancy_precedence_over_cost():
    """Maximal occupancy has precedence even when a lighter packing is
    cheaper (rule 2a's -y * large-constant term)."""
    classes = [ItemClass(weights=[1, 4], costs=[0.0, 100.0])]
    sol = solve_mc2mkp(classes, T=4)
    assert sol.used_capacity == 4
    assert sol.total_cost == pytest.approx(100.0)


# ---------------------------------------------------------------------------
# Dispatch
# ---------------------------------------------------------------------------


@settings(max_examples=40, deadline=None)
@given(instances())
def test_auto_dispatch_is_optimal(p):
    x = schedule(p, "auto")
    validate_schedule(p, x)
    assert total_cost(p, x) == pytest.approx(total_cost(p, solve_schedule_dp(p)), rel=1e-9, abs=1e-9)


def test_select_algorithm_regimes():
    rng = np.random.default_rng(3)
    assert select_algorithm(random_problem(rng, 4, 10, "increasing")) == "marin"
    p_lin = random_problem(rng, 4, 10, "linear")
    assert select_algorithm(p_lin) in ("marco", "mardecun")
    assert select_algorithm(random_problem(rng, 4, 10, "arbitrary")) == "dp"


# ---------------------------------------------------------------------------
# Beyond-paper: deadline-constrained energy minimization
# ---------------------------------------------------------------------------


def test_schedule_with_deadline():
    from repro.core.scheduler import schedule_with_deadline
    from repro.core.costs import linear_cost

    rng = np.random.default_rng(5)
    n, T = 4, 20
    p = random_problem(rng, n=n, T=T, regime="increasing")
    # time ~ j / speed, speeds differ
    speeds = rng.uniform(0.5, 3.0, size=n)
    times = [np.arange(int(u) + 1) / s for u, s in zip(p.upper, speeds)]

    # loose deadline: same optimum as unconstrained
    x_loose = schedule_with_deadline(p, times, deadline=1e9)
    assert total_cost(p, x_loose) == pytest.approx(total_cost(p, solve_schedule_dp(p)), rel=1e-9)

    # binding deadline: valid, respects per-device time, >= unconstrained cost
    dl = max(float(times[i][int(x_loose[i])]) for i in range(n)) * 0.9 + 1e-9
    try:
        x_tight = schedule_with_deadline(p, times, deadline=dl)
    except ValueError:
        return  # infeasible at this T - acceptable outcome for random case
    validate_schedule(p, x_tight)
    for i in range(n):
        assert times[i][int(x_tight[i])] <= dl + 1e-12
    assert total_cost(p, x_tight) >= total_cost(p, x_loose) - 1e-9


def test_schedule_with_deadline_infeasible():
    from repro.core.scheduler import schedule_with_deadline

    rng = np.random.default_rng(6)
    p = random_problem(rng, n=3, T=10, regime="linear")
    times = [np.arange(int(u) + 1) * 1.0 for u in p.upper]
    with pytest.raises(ValueError):
        schedule_with_deadline(p, times, deadline=0.5)  # < 1 batch anywhere


# ---------------------------------------------------------------------------
# General (MC)^2MKP with ARBITRARY item weights vs brute force (the paper's
# full Definition 2 generality, not just the scheduling specialization)
# ---------------------------------------------------------------------------


def _brute_force_mc2mkp(classes, T):
    import itertools

    best = (-1, float("inf"))  # (occupancy, cost) with occupancy precedence
    for combo in itertools.product(*[range(len(c.weights)) for c in classes]):
        w = sum(int(c.weights[j]) for c, j in zip(classes, combo))
        cost = sum(float(c.costs[j]) for c, j in zip(classes, combo))
        if w > T:
            continue
        if w > best[0] or (w == best[0] and cost < best[1]):
            best = (w, cost)
    return best


@settings(max_examples=80, deadline=None)
@given(st.integers(1, 4), st.integers(1, 20), st.integers(0, 2**32 - 1))
def test_general_mc2mkp_vs_brute_force(n, T, seed):
    rng = np.random.default_rng(seed)
    classes = []
    for _ in range(n):
        m = int(rng.integers(1, 5))
        weights = rng.integers(0, T + 3, size=m)
        costs = rng.uniform(0, 10, size=m)
        classes.append(ItemClass(weights=weights, costs=costs))
    want_w, want_c = _brute_force_mc2mkp(classes, T)
    if want_w < 0:
        return  # no feasible packing; solver raises - separately covered
    sol = solve_mc2mkp(classes, T)
    assert sol.used_capacity == want_w
    assert sol.total_cost == pytest.approx(want_c, rel=1e-9, abs=1e-9)
