"""Monotone-cost fast path (DESIGN.md §13): batched marginal schedulers.

Claims under test:
  * batched MarIn/MarCo (the jitted selection kernel) and MarDecUn/MarDec
    are BIT-IDENTICAL to the serial NumPy heap/sort/packing oracles on
    monotone instances — ragged n/T, lower/upper limits, inert batch
    padding, and exact-tie tie-breaking included;
  * on monotone instances the fast path's schedules cost exactly what the
    DP's cost (both optimal);
  * mixed-regime ``schedule_batch``/``SweepEngine`` solves return rows in
    ORIGINAL problem order, bit-identical to solving each regime sub-batch
    alone;
  * serial and batched algorithm dispatch share one regime detector and
    cannot disagree;
  * marginal selection executables live in their own sweep-engine shape
    buckets (compile once, hit afterwards) without disturbing the DP
    buckets.

All parity instances use float32-representable cost tables (integer-valued
or pre-rounded) so the float32 kernel and the float64 oracles see the same
marginal order — see the precision contract in ``core/marginal_jax.py``.
"""

import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # clean container: deterministic fallback sampler
    from _hypothesis_fallback import given, settings, st

from repro.core import (
    Problem,
    ProblemBatch,
    SweepEngine,
    marco,
    marco_batch,
    mardec,
    mardec_batch,
    mardecun,
    mardecun_batch,
    marin,
    marin_batch,
    random_problem,
    schedule,
    schedule_batch,
    select_algorithm,
    select_algorithm_batch,
    solve_schedule_dp,
    total_cost,
    validate_schedule,
)

from _fused_ref import solve_unbucketed

# one fixed kernel envelope for most parity tests: every batch is padded to
# it, so the selection kernel compiles exactly once for the whole module
ENV_B, ENV_N, ENV_W = 8, 8, 32


def f32_safe(p: Problem) -> Problem:
    """The instance the float32 paths actually see (tables rounded once)."""
    return Problem(
        T=p.T,
        lower=p.lower,
        upper=p.upper,
        cost_tables=tuple(t.astype(np.float32).astype(np.float64) for t in p.cost_tables),
    )


def integer_increasing_problem(rng, n, T, max_u=None, max_marginal=6, with_lower=True):
    """Increasing-marginal instance with INTEGER tables: exact in float32
    and riddled with exact marginal ties — the tie-break torture case."""
    max_u = max_u or min(T, ENV_W - 1)
    while True:
        upper = rng.integers(1, max_u + 1, size=n)
        if upper.sum() >= T:
            break
    lower = np.minimum(rng.integers(0, 3, size=n), upper) if with_lower else np.zeros(n, np.int64)
    while lower.sum() > T:
        k = int(rng.integers(0, n))
        lower[k] = max(0, lower[k] - 1)
    tables = tuple(
        np.concatenate(
            [[0.0], np.cumsum(np.sort(rng.integers(0, max_marginal, size=int(u))))]
        ).astype(np.float64)
        for u in upper
    )
    return Problem(T=T, lower=lower, upper=upper, cost_tables=tables)


def padded(problems) -> ProblemBatch:
    return ProblemBatch.from_problems(problems).pad_to(B=ENV_B, n=ENV_N, W=ENV_W)


# ---------------------------------------------------------------------------
# selection kernel vs serial heap (MarIn) / sort-and-fill (MarCo)
# ---------------------------------------------------------------------------


@settings(max_examples=25, deadline=None)
@given(st.integers(1, ENV_B), st.integers(0, 2**32 - 1))
def test_marin_batch_bit_identical_to_serial(B, seed):
    rng = np.random.default_rng(seed)
    probs = [
        f32_safe(
            random_problem(
                rng,
                n=int(rng.integers(1, ENV_N + 1)),
                T=int(rng.integers(1, 25)),
                regime="increasing",
                max_upper=ENV_W - 1,
            )
        )
        for _ in range(B)
    ]
    X = marin_batch(padded(probs))
    for b, p in enumerate(probs):
        assert np.array_equal(X[b, : p.n], marin(p)), (b, X[b], marin(p))
        assert np.all(X[b, p.n :] == 0)
    assert np.all(X[B:] == 0)  # phantom instances stay empty


@settings(max_examples=25, deadline=None)
@given(st.integers(1, ENV_B), st.integers(0, 2**32 - 1))
def test_marin_batch_integer_tie_breaks(B, seed):
    rng = np.random.default_rng(seed)
    probs = [
        integer_increasing_problem(rng, n=int(rng.integers(1, ENV_N + 1)), T=int(rng.integers(1, 20)))
        for _ in range(B)
    ]
    X = marin_batch(padded(probs))
    for b, p in enumerate(probs):
        assert np.array_equal(X[b, : p.n], marin(p)), (b, X[b], marin(p))


@settings(max_examples=25, deadline=None)
@given(st.integers(1, ENV_B), st.integers(0, 2**32 - 1))
def test_marco_batch_bit_identical_to_serial(B, seed):
    rng = np.random.default_rng(seed)
    probs = []
    for _ in range(B):
        n = int(rng.integers(1, ENV_N + 1))
        while True:
            upper = rng.integers(1, ENV_W, size=n)
            T = int(rng.integers(1, 25))
            if upper.sum() >= T:
                break
        # integer per-task marginals with cross-resource ties: MarCo's
        # stable sort order is the thing under test
        tables = tuple(
            np.arange(int(u) + 1, dtype=np.float64) * int(rng.integers(1, 5)) for u in upper
        )
        probs.append(Problem(T=T, lower=np.zeros(n, np.int64), upper=upper, cost_tables=tables))
    X = marco_batch(padded(probs))
    for b, p in enumerate(probs):
        assert np.array_equal(X[b, : p.n], marco(p)), (b, X[b], marco(p))


def test_marginal_batch_dp_objective_equality():
    """On monotone instances the fast path and the DP are both optimal:
    integer tables make the equality EXACT (float32 sums below 2^24)."""
    rng = np.random.default_rng(7)
    probs = [integer_increasing_problem(rng, n=4, T=14, max_marginal=5) for _ in range(6)]
    X = marin_batch(probs)
    X_dp = solve_unbucketed(probs)
    for b, p in enumerate(probs):
        validate_schedule(p, X[b, : p.n])
        assert total_cost(p, X[b, : p.n]) == total_cost(p, X_dp[b, : p.n])
        assert total_cost(p, X[b, : p.n]) == total_cost(p, solve_schedule_dp(p))


# ---------------------------------------------------------------------------
# MarDecUn / MarDec
# ---------------------------------------------------------------------------


@settings(max_examples=20, deadline=None)
@given(st.integers(1, 6), st.integers(0, 2**32 - 1))
def test_mardecun_batch_bit_identical_to_serial(B, seed):
    rng = np.random.default_rng(seed)
    probs = []
    for _ in range(B):
        n = int(rng.integers(1, 6))
        T = int(rng.integers(1, 15))
        p = random_problem(rng, n=n, T=T, regime="decreasing", max_upper=T, with_lower=False)
        # force unlimited: widen every table to full T capacity
        from repro.core.costs import sublinear_cost

        tables = tuple(
            sublinear_cost(T, float(rng.uniform(5, 40)), float(rng.uniform(2, 20)))
            for _ in range(n)
        )
        probs.append(Problem(T=T, lower=np.zeros(n, np.int64), upper=np.full(n, T), cost_tables=tables))
    X = mardecun_batch(probs)
    for b, p in enumerate(probs):
        assert np.array_equal(X[b, : p.n], mardecun(p))
        # ragged batching pads with zero-capacity resources; the serial
        # algorithm must agree on the padded materialization too
        assert np.array_equal(X[b], mardecun(ProblemBatch.from_problems(probs).instance(b)))


def test_mardecun_capacity_guard():
    """Zero-capacity resources are ignored (dropout/padding); resources with
    SOME capacity below T still raise, serial and batched alike."""
    tbl = lambda u: np.concatenate([[0.0], 10 - np.arange(1, u + 1, dtype=np.float64) * 0.5]).cumsum()  # noqa: E731
    ok = Problem(T=6, lower=[0, 0], upper=[6, 0], cost_tables=(tbl(6), np.zeros(1)))
    x = mardecun(ok)
    assert np.array_equal(x, mardecun_batch([ok])[0])
    assert x.sum() == 6 and x[1] == 0
    bad = Problem(T=6, lower=[0, 0], upper=[6, 3], cost_tables=(tbl(6), tbl(3)))
    with pytest.raises(ValueError, match="MarDecUn requires"):
        mardecun(bad)
    with pytest.raises(ValueError, match="MarDecUn requires"):
        mardecun_batch([bad])


@settings(max_examples=15, deadline=None)
@given(st.integers(1, 5), st.integers(0, 2**32 - 1))
def test_mardec_batch_bit_identical_to_serial(B, seed):
    rng = np.random.default_rng(seed)
    probs = [
        random_problem(rng, n=int(rng.integers(1, 5)), T=int(rng.integers(4, 14)), regime="decreasing")
        for _ in range(B)
    ]
    X_list = mardec_batch(probs)
    X_batch = mardec_batch(ProblemBatch.from_problems(probs))  # padded envelope
    np.testing.assert_array_equal(X_list, X_batch)
    for b, p in enumerate(probs):
        assert np.array_equal(X_list[b, : p.n], mardec(p))


# ---------------------------------------------------------------------------
# one shared dispatch rule (serial == batched, padding-invariant)
# ---------------------------------------------------------------------------


@settings(max_examples=30, deadline=None)
@given(st.sampled_from(["arbitrary", "linear", "increasing", "decreasing"]), st.integers(0, 2**32 - 1))
def test_select_algorithm_serial_equals_batched(regime, seed):
    rng = np.random.default_rng(seed)
    probs = [
        random_problem(rng, n=int(rng.integers(1, 6)), T=int(rng.integers(2, 20)), regime=regime)
        for _ in range(4)
    ]
    batched = select_algorithm_batch(probs)
    padded_algs = select_algorithm_batch(ProblemBatch.from_problems(probs).pad_to(B=8, n=8))
    for b, p in enumerate(probs):
        assert select_algorithm(p) == batched[b]
        assert batched[b] == padded_algs[b]  # padding cannot change dispatch
    batch = ProblemBatch.from_problems(probs)
    assert list(batch.regimes()) == [batch.instance(b).regime() for b in range(batch.B)]


def test_dropout_zero_capacity_dispatch():
    """A dropped-out client (U = 0) must not flip the dispatch rule or
    break the selected algorithm (paper §6 'loss of a device')."""
    n, T = 4, 9
    tables = [np.arange(13, dtype=np.float64) * c for c in (2.0, 3.0, 1.0, 2.0)]
    tables[1] = np.zeros(1)  # client 1 dropped: U = 0
    upper = np.array([12, 0, 12, 12])
    p = Problem(T=T, lower=np.zeros(n, np.int64), upper=upper, cost_tables=tuple(tables))
    assert p.regime() == "constant"
    alg = select_algorithm(p)
    assert alg == "mardecun"  # capacity-aware: the U=0 client is ignored
    x = schedule(p, "auto")
    validate_schedule(p, x)
    assert total_cost(p, x) == total_cost(p, solve_schedule_dp(p))
    assert np.array_equal(x, schedule_batch([p], "auto")[0])


# ---------------------------------------------------------------------------
# mixed-regime split: order, sub-batch bit-identity, engine bucketing
# ---------------------------------------------------------------------------


def _mixed_problems(rng, B=8):
    regimes = ("arbitrary", "linear", "increasing", "decreasing")
    return [
        random_problem(
            rng,
            n=int(rng.integers(1, ENV_N + 1)),
            T=int(rng.integers(2, 16)),
            regime=regimes[b % len(regimes)],
        )
        for b in range(B)
    ]


def test_mixed_regime_schedule_batch_matches_subbatches():
    rng = np.random.default_rng(11)
    probs = _mixed_problems(rng)
    eng = SweepEngine()
    xs = schedule_batch(probs, "auto", engine=eng)
    assert len(xs) == len(probs)
    algs = select_algorithm_batch(probs)
    for alg_group in sorted(set(algs)):
        idx = [b for b, a in enumerate(algs) if a == alg_group]
        xs_alone = schedule_batch([probs[b] for b in idx], "auto", engine=eng)
        for j, b in enumerate(idx):
            # original-order rows == solving the regime sub-batch alone
            assert np.array_equal(xs[b], xs_alone[j]), (alg_group, b)
    for p, x in zip(probs, xs):
        validate_schedule(p, x)
        assert total_cost(p, x) == pytest.approx(
            total_cost(p, solve_schedule_dp(p)), rel=1e-5, abs=1e-9
        )


def test_split_engine_handle_and_bucketing():
    rng = np.random.default_rng(12)
    probs = _mixed_problems(rng)
    eng = SweepEngine()
    h = eng.dispatch(probs, split_regimes=True)
    X = h.result()
    assert X.shape == (len(probs), max(p.n for p in probs))
    # objectives: 0-lower-limit optimal cost per instance, any regime
    from repro.core import remove_lower_limits

    obj = h.objectives()
    for b, p in enumerate(probs):
        p0 = remove_lower_limits(p)
        x0 = X[b, : p.n] - p.lower
        assert obj[b] == pytest.approx(total_cost(p0, x0), rel=1e-5, abs=1e-5)
    with pytest.raises(ValueError, match="k_last"):
        h.k_last()
    s1 = eng.cache_stats()
    assert s1["entries"] >= 2  # at least one DP + one marginal bucket
    # same shapes again: pure hits, no new compiles
    X2 = eng.solve(probs, split_regimes=True)
    np.testing.assert_array_equal(X, X2)
    s2 = eng.cache_stats()
    assert s2["compiles"] == s1["compiles"] and s2["entries"] == s1["entries"]
    assert s2["hits"] > s1["hits"]
    # a pure-DP batch takes the classic path: plain SweepHandle, k_last works
    dp_probs = [p for p, a in zip(probs, select_algorithm_batch(probs)) if a == "dp"]
    h_dp = eng.dispatch(dp_probs, split_regimes=True)
    assert hasattr(h_dp, "k_last") and h_dp.k_last().shape[0] == len(dp_probs)
    np.testing.assert_array_equal(
        h_dp.result(), solve_unbucketed(dp_probs)
    )


def test_unsplit_default_unchanged():
    """The default (no split) engine contract is untouched: bit-identical
    to the unbucketed fused DP even on monotone instances."""
    rng = np.random.default_rng(13)
    probs = _mixed_problems(rng, B=6)
    X = SweepEngine().solve(probs)
    np.testing.assert_array_equal(X, solve_unbucketed(probs))


@pytest.mark.slow
def test_wide_sweep_parity_slow():
    """Sweep-scale parity: the acceptance-criteria shape class (wide W,
    many units) against the serial heap, plus DP-cost equality."""
    rng = np.random.default_rng(14)
    B, n, T = 8, 16, 512
    probs = []
    for _ in range(B):
        upper = np.full(n, (2 * T) // n)
        tables = tuple(
            np.concatenate(
                [[0.0], np.cumsum(np.sort(rng.integers(1, 1000, size=int(u))))]
            ).astype(np.float64)
            for u in upper
        )
        probs.append(Problem(T=T, lower=np.zeros(n, np.int64), upper=upper, cost_tables=tables))
    X = marin_batch(probs)
    for b, p in enumerate(probs):
        assert np.array_equal(X[b, : p.n], marin(p))
    X_dp = solve_unbucketed(probs)
    for b, p in enumerate(probs):
        assert total_cost(p, X[b, : p.n]) == total_cost(p, X_dp[b, : p.n])
