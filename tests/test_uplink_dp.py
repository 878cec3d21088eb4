"""Cross-device rounds that pay a per-participant upload, through the
planning service on the CPU.

Each phone that takes part in a round uploads its model update once, so its
table is ``C_i(0) = 0`` and ``C_i(j) = E_up,i + E_comp,i(j)`` for ``j >= 1``:
its marginals fall at ``j = 2`` and rise after, every request is of the
arbitrary regime, and the regime split sends it to the exact DP. The served
schedules are compared with an exact integer DP kept here (the algorithm of
the benchmark's ``uplink_dp`` reference): the same recurrence over clients
in order, in int64, with two rows of ``K`` and uint8 argmins.
"""

import numpy as np
import pytest

from repro.core import Problem, SweepEngine
from repro.core.marginal_jax import select_algorithm_batch
from repro.core.problem import ProblemBatch
from repro.serve import SchedulerService

# one upload of LEAF's FEMNIST CNN (6 603 710 float32 parameters) at 1.2 W
# over 1-20 Mbit/s, in mJ
UPLOAD_MJ = (12_683, 253_582)


def exact_dp(T: int, upper, tables):
    """``(x, cost)``: an optimal schedule of ``T`` tasks over clients with
    zero lower limits and integer tables, and its exact cost."""
    n = len(tables)
    upper = np.asarray(upper, np.int64)
    inf = np.iinfo(np.int64).max // 4
    before = np.concatenate([[0], np.cumsum(upper)])
    hi = np.minimum(T, before[1:])  # the workloads row i can hold
    lo = np.maximum(0, T - (before[-1] - before[1:]))  # that still reach T
    K = np.full(T + 1, inf, np.int64)
    K[0] = 0
    new = np.full_like(K, inf)
    I = np.zeros((n, T + 1), np.uint8)
    for i, tab in enumerate(tables):
        c = np.rint(np.asarray(tab, np.float64)).astype(np.int64)
        a0, b = int(lo[i]), int(hi[i])
        new[a0 : b + 1] = inf
        for j, cj in enumerate(c):  # ascending j, strict improvement
            a = max(a0, j)
            if a > b:
                break
            cand = K[a - j : b - j + 1] + cj
            better = cand < new[a : b + 1]
            np.copyto(new[a : b + 1], cand, where=better)
            np.copyto(I[i, a : b + 1], np.uint8(j), where=better)
        K, new = new, K
    x = np.zeros(n, np.int64)
    t = T
    for i in range(n - 1, -1, -1):
        x[i] = I[i, t]
        t -= int(x[i])
    return x, int(K[T])


def uplink_population(rng, n: int):
    """``(upper, tables)`` of ``n`` phones: 1-63 batches each (one at 63, so
    the band is 64 wide), compute marginals of 2.2-9 J rising with ``j``,
    and an upload in :data:`UPLOAD_MJ`; all integer mJ."""
    upper = rng.integers(1, 64, size=n)
    upper[0] = 63
    tables = []
    for u in upper:
        marg = np.sort(rng.integers(2200, 9001, size=int(u))).astype(np.float64)
        e_up = float(rng.integers(UPLOAD_MJ[0], UPLOAD_MJ[1] + 1))
        tables.append(np.concatenate([[0.0], e_up + np.cumsum(marg)]))
    return upper, tables


def rounds(rng, upper, tables, count: int):
    """``count`` round requests over the population: each plans a random
    60-100% of the phones and asks for 30-70% of their batches."""
    out = []
    for _ in range(count):
        m = int(round(rng.uniform(0.6, 1.0) * len(upper)))
        idx = np.sort(rng.choice(len(upper), size=m, replace=False))
        u = upper[idx]
        out.append(
            Problem(
                T=int(rng.uniform(0.3, 0.7) * u.sum()),
                lower=np.zeros(m, np.int64),
                upper=u,
                cost_tables=[tables[i] for i in idx],
            )
        )
    return out


def test_the_exact_dp_matches_brute_force_on_tiny_rounds():
    rng = np.random.default_rng(5)
    for _ in range(6):
        upper, tables = uplink_population(rng, 4)
        upper = np.minimum(upper, 5)
        tables = [t[: u + 1] for t, u in zip(tables, upper)]
        T = int(rng.integers(1, upper.sum() + 1))
        grids = np.stack(np.meshgrid(*[np.arange(u + 1) for u in upper], indexing="ij"), -1)
        grids = grids.reshape(-1, len(upper))
        ok = grids[grids.sum(axis=1) == T]
        costs = [sum(int(t[v]) for t, v in zip(tables, x)) for x in ok]
        x, cost = exact_dp(T, upper, tables)
        assert x.sum() == T and cost == min(costs)


@pytest.mark.parametrize("seed,n", [(1, 50), (2, 120), (3, 200), (4, 300)])
def test_served_uplink_schedules_cost_the_exact_optimum(seed, n):
    rng = np.random.default_rng(seed)
    upper, tables = uplink_population(rng, n)
    problems = rounds(rng, upper, tables, 3)
    assert set(select_algorithm_batch(ProblemBatch.from_problems(problems))) == {"dp"}
    engine = SweepEngine()
    with SchedulerService(engine=engine, max_batch=4) as svc:
        futures = [svc.submit(p, split_regimes=True) for p in problems]
        answers = [(f.result(timeout=120), float(f.objectives())) for f in futures]
    assert all(k.startswith("dp:") for k in engine.cache_stats()["per_bucket_hits"])
    for p, (x, objective) in zip(problems, answers):
        assert x.sum() == p.T and np.all(x <= p.upper) and np.all(x >= 0)
        served = sum(int(t[int(v)]) for t, v in zip(p.cost_tables, x))
        _, optimum = exact_dp(p.T, p.upper, p.cost_tables)
        assert served == optimum
        # float32 sums of at most n entries, one rounding per row
        gamma = n * 2.0**-24 / (1 - n * 2.0**-24)
        assert abs(objective - served) <= gamma * served
    # an upload makes taking part cost: some eligible phones are left out
    assert all((x == 0).any() for x, _ in answers)


def test_fused_dp_on_the_pallas_kernel_matches_ref_and_the_numpy_dp():
    """The engine's fused DP on the Pallas kernel (interpret mode), whose band
    loops end at each row's bound, against the same DP on the ref backend and
    the serial numpy DP: ragged limits, lower limits, phantom rows from the
    pow2 buckets, W 64. Integer mJ tables below 2**24 in sum, so float32 is
    exact and schedules and final rows must agree bit for bit."""
    from repro.core.jax_dp import pack_problem, solve_fused_batch_jax
    from repro.core.mc2mkp import _classes_from_problem, mc2mkp_matrices, solve_schedule_dp
    from repro.core.problem import remove_lower_limits
    from repro.kernels import BIG

    rng = np.random.default_rng(17)
    upper, tables = uplink_population(rng, 12)
    tables = [np.rint(t / 100.0) for t in tables]
    problems = []
    for n in (12, 9, 5):  # ragged n: phantom rows to the n bucket of 16
        idx = np.sort(rng.choice(12, size=n, replace=False))
        u = upper[idx]
        lower = np.minimum(rng.integers(0, 3, size=n), u)
        problems.append(
            Problem(
                T=int(lower.sum() + rng.uniform(0.3, 0.7) * (u - lower).sum()),
                lower=lower,
                upper=u,
                cost_tables=[tables[i] for i in idx],
            )
        )
    batch = remove_lower_limits(ProblemBatch.from_problems(problems)).pad_to(B=4, n=16, W=64)
    costs = pack_problem(batch)
    t_star = np.asarray(batch.T, np.int32)
    Tb = 1 << int(batch.T.max()).bit_length()
    X_pal, K_pal = map(np.asarray, solve_fused_batch_jax(costs, t_star, Tb, backend="pallas"))
    X_ref, K_ref = map(np.asarray, solve_fused_batch_jax(costs, t_star, Tb, backend="ref"))
    np.testing.assert_array_equal(X_pal, X_ref)
    np.testing.assert_array_equal(K_pal, K_ref)
    for b, p in enumerate(problems):
        p0 = remove_lower_limits(p)
        np.testing.assert_array_equal(X_pal[b, : p.n] + p.lower, solve_schedule_dp(p))
        K, _ = mc2mkp_matrices(_classes_from_problem(p0), int(p0.T))
        want = np.where(np.isfinite(K[-1]), K[-1], float(BIG)).astype(np.float32)
        np.testing.assert_array_equal(K_pal[b, : p0.T + 1], want)
    assert np.all(X_pal[len(problems) :] == 0) and np.all(X_pal[:, 12:] == 0)
