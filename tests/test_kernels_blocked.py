"""Blocked min-plus backend + fused DP/backtrack + dispatch (DESIGN.md §12).

Claims under test:
  * the blocked backend is BIT-IDENTICAL to the dense oracle — values AND
    first-min argmins — over ragged (B, T, W) and odd/pathological block
    sizes, including BIG saturation and all-BIG rows (property-based, with
    the hypothesis fallback);
  * the Pallas-GPU blocked kernel (interpret mode) matches the oracle too;
  * the fused single-dispatch solver returns exactly what the legacy
    two-dispatch chain returns, plus a correct K_last row;
  * ``SweepEngine`` on the fused path still compiles once per bucket, and
    its handles expose per-instance objectives for free;
  * the per-hardware dispatch table resolves "auto" to the blocked backend
    on this CPU container;
  * vectorized ``pack_problem`` packs exactly like the old per-class loop.
"""

import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # clean container: deterministic fallback sampler
    from _hypothesis_fallback import given, settings, st

import jax
import jax.numpy as jnp

from repro.core import (
    Problem,
    SweepEngine,
    random_problem,
    solve_schedule_dp,
    solve_schedule_dp_batch,
    total_cost,
)
from repro.core.jax_dp import (
    backtrack_batch_jax,
    dp_tables_batch_jax,
    pack_problem,
    solve_fused_batch_jax,
)
from repro.core.problem import ProblemBatch, remove_lower_limits
from repro.kernels import (
    BIG,
    DISPATCH_TABLE,
    auto_block_sizes,
    minplus_blocked_batch,
    minplus_pallas_gpu_batch,
    minplus_step_batch,
    minplus_step_ref_batch,
    resolve_backend,
    tpu_tuned_bt,
)


def random_band_inputs(rng, B, Tp, W, frac_inf=0.3):
    """A DP row + cost stack with BIG sprinkled in both (band edges, padded
    tails, and saturation are all exercised)."""
    kprev = rng.uniform(0, 100, (B, Tp)).astype(np.float32)
    kprev[rng.random((B, Tp)) < frac_inf] = float(BIG)
    kprev[:, 0] = 0.0
    cost = rng.uniform(0, 10, (B, W)).astype(np.float32)
    cost[rng.random((B, W)) < 0.2] = float(BIG)
    return kprev, cost


def assert_bit_identical(got, want):
    gv, gi = got
    wv, wi = want
    np.testing.assert_array_equal(np.asarray(gv), np.asarray(wv))
    np.testing.assert_array_equal(np.asarray(gi), np.asarray(wi))


# ---------------------------------------------------------------------------
# property-based parity: blocked vs dense oracle
# ---------------------------------------------------------------------------


@st.composite
def band_shapes(draw):
    B = draw(st.integers(1, 4))
    Tp = draw(st.integers(1, 400))
    W = draw(st.integers(1, 300))
    # odd, tiny, and oversized block edges all legal. BW is the chunk
    # unroll factor, i.e. compile time — the fast tier keeps it <= 64 and
    # the slow-marked sweep below covers the wide chunks.
    BT = draw(st.sampled_from([1, 3, 7, 33, 64, 100, 256, 1024]))
    BW = draw(st.sampled_from([1, 2, 5, 17, 64]))
    seed = draw(st.integers(0, 2**31 - 1))
    return B, Tp, W, BT, BW, seed


@settings(max_examples=8, deadline=None)
@given(band_shapes())
def test_blocked_matches_dense_property(shape):
    B, Tp, W, BT, BW, seed = shape
    rng = np.random.default_rng(seed)
    kprev, cost = random_band_inputs(rng, B, Tp, W)
    assert_bit_identical(
        minplus_blocked_batch(kprev, cost, BT=BT, BW=BW),
        minplus_step_ref_batch(kprev, cost),
    )


@pytest.mark.slow
@pytest.mark.parametrize("BT,BW", [(256, 128), (100, 512), (1024, 512)])
def test_blocked_matches_dense_wide_chunks(BT, BW):
    rng = np.random.default_rng(BT + BW)
    kprev, cost = random_band_inputs(rng, 3, 700, 600)
    assert_bit_identical(
        minplus_blocked_batch(kprev, cost, BT=BT, BW=BW),
        minplus_step_ref_batch(kprev, cost),
    )


def test_blocked_auto_block_sizes_parity_and_sanity():
    rng = np.random.default_rng(7)
    for B, Tp, W in [(1, 1, 1), (2, 513, 77)]:
        kprev, cost = random_band_inputs(rng, B, Tp, W)
        assert_bit_identical(
            minplus_blocked_batch(kprev, cost),  # BT/BW from the heuristic
            minplus_step_ref_batch(kprev, cost),
        )
        BT, BW = auto_block_sizes(B, Tp, W)
        assert BT >= 1 and BW >= 1
        assert BT & (BT - 1) == 0 and BW & (BW - 1) == 0  # pow2-aligned tiles
    # heuristic is deterministic and lands on the tuned config at the
    # memory-bound benchmark shape
    assert auto_block_sizes(8, 8193, 512) == auto_block_sizes(8, 8193, 512) == (512, 128)


def test_blocked_all_big_saturation_and_argmin_convention():
    # an all-infeasible row stays BIG everywhere and keeps argmin = 0 (the
    # oracle's argmin-of-constant convention) — padding inertness depends
    # on this
    B, Tp, W = 2, 37, 11
    kprev = np.full((B, Tp), float(BIG), dtype=np.float32)
    cost = np.full((B, W), float(BIG), dtype=np.float32)
    bv, bi = minplus_blocked_batch(kprev, cost, BT=8, BW=3)
    assert np.all(np.asarray(bv) == float(BIG))
    assert np.all(np.asarray(bi) == 0)
    assert_bit_identical((bv, bi), minplus_step_ref_batch(kprev, cost))


@pytest.mark.parametrize("Tp,W,BT,BW", [(64, 16, 32, 8), (255, 130, 256, 64)])
def test_pallas_gpu_matches_dense_interpret(Tp, W, BT, BW):
    rng = np.random.default_rng(Tp + W)
    kprev, cost = random_band_inputs(rng, 2, Tp, W)
    assert_bit_identical(
        minplus_pallas_gpu_batch(kprev, cost, BT=BT, BW=BW, interpret=True),
        minplus_step_ref_batch(kprev, cost),
    )


# ---------------------------------------------------------------------------
# dispatch table
# ---------------------------------------------------------------------------


@pytest.mark.skipif(
    jax.default_backend() != "cpu", reason="asserts the CPU row of the dispatch table"
)
def test_dispatch_table_resolves_auto_per_hardware():
    assert DISPATCH_TABLE == {"cpu": "blocked", "tpu": "pallas_tpu", "gpu": "pallas_gpu"}
    assert resolve_backend("auto") == "blocked"
    assert resolve_backend(None) == "blocked"
    assert resolve_backend("ref") == "ref"  # explicit names pass through
    with pytest.raises(ValueError, match="unknown backend"):
        resolve_backend("triton")
    # the auto path really runs the blocked kernel: identical to calling it
    rng = np.random.default_rng(0)
    kprev, cost = random_band_inputs(rng, 2, 200, 40)
    assert_bit_identical(
        minplus_step_batch(kprev, cost, backend="auto"),
        minplus_blocked_batch(kprev, cost),
    )


def test_resolve_backend_interprets_only_on_cpu(monkeypatch):
    # steer the platform the resolver sees: "pallas" is the compiled kernel
    # on a TPU, and a platform with no backend raises instead of guessing
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert resolve_backend("auto") == "pallas_tpu"
    assert resolve_backend("pallas") == "pallas_tpu"
    monkeypatch.setattr(jax, "default_backend", lambda: "metal")
    with pytest.raises(ValueError, match="no min-plus backend"):
        resolve_backend("auto")
    with pytest.raises(ValueError, match="runs on cpu or tpu"):
        resolve_backend("pallas")
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert resolve_backend("pallas") == "pallas"


def test_tpu_tuned_bt_respects_vmem_budget():
    # short rows get a tile of whole lane tiles that does not overshoot the
    # row; longer rows get the default tile; rows whose double-buffered
    # padded row does not fit VMEM raise instead of reaching the compiler
    assert tpu_tuned_bt(100, 512) == 128
    assert tpu_tuned_bt(4096, 512) == 1024
    assert tpu_tuned_bt(60_000, 512) == 1024
    with pytest.raises(ValueError, match="VMEM"):
        tpu_tuned_bt(4_000_000, 1024)
    for Tp, W in [(1, 1), (1000, 100), (100_000, 2048), (1_000_000, 512)]:
        bt = tpu_tuned_bt(Tp, W)
        assert bt % 128 == 0  # f32 lane granularity of the output tiles
        tpad = -(-Tp // bt) * bt
        wp = -(-W // 128) * 128
        assert 2 * 4 * (wp + tpad) + 2 * 2 * 4 * bt <= 16 * 2**20


# ---------------------------------------------------------------------------
# fused DP + backtrack
# ---------------------------------------------------------------------------


def _random_sweep(rng, B, n_max=6, T_max=40):
    regimes = ("arbitrary", "linear", "increasing", "decreasing")
    return [
        random_problem(
            rng,
            n=int(rng.integers(1, n_max + 1)),
            T=int(rng.integers(1, T_max + 1)),
            regime=regimes[b % len(regimes)],
        )
        for b in range(B)
    ]


def test_fused_solver_matches_twodispatch_and_numpy_dp():
    rng = np.random.default_rng(11)
    probs = _random_sweep(rng, 7)
    b0 = remove_lower_limits(ProblemBatch.from_problems(probs))
    costs = pack_problem(b0)
    Tmax = int(b0.T.max())
    t_star = jnp.asarray(b0.T, dtype=jnp.int32)
    for backend in ("blocked", "ref"):
        X, k_last = solve_fused_batch_jax(costs, t_star, Tmax, backend=backend)
        k2, I = dp_tables_batch_jax(costs, Tmax, backend=backend)
        X2 = backtrack_batch_jax(I, t_star, Tmax)
        np.testing.assert_array_equal(np.asarray(X), np.asarray(X2))
        np.testing.assert_array_equal(np.asarray(k_last), np.asarray(k2))
        assert X.shape == (b0.B, b0.n) and k_last.shape == (b0.B, Tmax + 1)
    # K_last at t* IS the optimal reduced-instance objective (== numpy DP)
    X, k_last = solve_fused_batch_jax(costs, t_star, Tmax, backend="blocked")
    for b, p in enumerate(probs):
        x_np = solve_schedule_dp(p)
        k_at = float(np.asarray(k_last)[b, int(b0.T[b])])
        offset = sum(p.cost(i, int(lo)) for i, lo in enumerate(p.lower))
        assert k_at + offset == pytest.approx(total_cost(p, x_np), rel=1e-5, abs=1e-4)


def test_batched_solver_blocked_bit_identical_to_ref_end_to_end():
    rng = np.random.default_rng(23)
    probs = _random_sweep(rng, 9)
    np.testing.assert_array_equal(
        solve_schedule_dp_batch(probs, backend="blocked"),
        solve_schedule_dp_batch(probs, backend="ref"),
    )
    # and "auto" matches its resolved concrete backend ("blocked" on CPU)
    np.testing.assert_array_equal(
        solve_schedule_dp_batch(probs, backend="auto"),
        solve_schedule_dp_batch(probs, backend=resolve_backend("auto")),
    )


# ---------------------------------------------------------------------------
# sweep engine on the fused path
# ---------------------------------------------------------------------------


def test_sweep_engine_fused_path_compiles_once_per_bucket():
    rng = np.random.default_rng(31)
    probs = _random_sweep(rng, 5)
    eng = SweepEngine()  # backend="auto" resolves per hardware at init
    assert eng.backend == resolve_backend("auto")
    X = eng.solve(probs)
    np.testing.assert_array_equal(X, solve_schedule_dp_batch(probs))
    # drifted costs, same shapes: 2 more solves, still ONE compilation
    for f in (1.05, 0.93):
        drifted = [
            Problem(
                T=p.T,
                lower=p.lower,
                upper=p.upper,
                cost_tables=tuple(t * f for t in p.cost_tables),
            )
            for p in probs
        ]
        np.testing.assert_array_equal(
            eng.solve(drifted), solve_schedule_dp_batch(drifted)
        )
    s = eng.cache_stats()
    assert s["compiles"] == 1 and s["misses"] == 1 and s["hits"] == 2, s


def test_sweep_handle_exposes_k_last_and_objectives():
    rng = np.random.default_rng(41)
    probs = _random_sweep(rng, 4)
    eng = SweepEngine()
    handle = eng.dispatch(probs)
    X = handle.result()
    obj = handle.objectives()
    assert obj.shape == (len(probs),)
    k_last = handle.k_last()
    assert k_last.shape[0] == len(probs)
    for b, p in enumerate(probs):
        # objective is the REDUCED instance's cost: original minus the
        # fixed lower-limit spend (Section 5.2 rebases C'(0) = 0)
        offset = sum(p.cost(i, int(lo)) for i, lo in enumerate(p.lower))
        assert float(obj[b]) + offset == pytest.approx(
            total_cost(p, X[b, : p.n]), rel=1e-5, abs=1e-4
        )
        # k_last row is consistent with the objective at t*
        t_star = int(p.T - p.lower.sum())
        assert float(k_last[b, t_star]) == float(obj[b])


# ---------------------------------------------------------------------------
# pack_problem vectorization
# ---------------------------------------------------------------------------


def test_pack_problem_masked_scatter_matches_loop():
    rng = np.random.default_rng(53)
    for _ in range(5):
        p = random_problem(
            rng, n=int(rng.integers(1, 7)), T=int(rng.integers(2, 30)), regime="arbitrary"
        )
        p0 = remove_lower_limits(p)
        got = np.asarray(pack_problem(p0))
        W = int(p0.upper.max()) + 1
        want = np.full((p0.n, W), float(BIG), dtype=np.float32)
        for i in range(p0.n):  # the old per-class loop, as the oracle
            u = int(p0.upper[i])
            want[i, : u + 1] = p0.cost_tables[i][: u + 1]
        np.testing.assert_array_equal(got, want)
