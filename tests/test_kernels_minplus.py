"""Shape/dtype sweeps for the Pallas min-plus kernel vs the jnp oracle.

The kernel runs in interpret mode on the CPU; the oracle is
``minplus_step_ref``; a hand-rolled numpy triple-check guards the oracle.
"""

import numpy as np
import pytest

from repro.core import Problem, Solver, solve_schedule_dp, total_cost
from repro.kernels import (
    BIG,
    minplus_pallas,
    minplus_pallas_batch,
    minplus_step_ref,
    minplus_step_ref_batch,
)
from repro.kernels.minplus import band_steps


def numpy_minplus(kprev, cost):
    Tp, W = len(kprev), len(cost)
    out = np.full(Tp, float(BIG))
    idx = np.zeros(Tp, dtype=np.int32)
    for t in range(Tp):
        for j in range(min(W, t + 1)):
            v = kprev[t - j] + cost[j]
            v = min(v, float(BIG))
            if v < out[t]:
                out[t] = v
                idx[t] = j
    return out, idx


def random_row(rng, Tp, frac_inf=0.3):
    k = rng.uniform(0, 100, size=Tp).astype(np.float32)
    mask = rng.random(Tp) < frac_inf
    k[mask] = float(BIG)
    k[0] = 0.0
    return k


@pytest.mark.parametrize("Tp", [1, 7, 64, 255, 1024, 1500])
@pytest.mark.parametrize("W", [1, 5, 130, 700])
def test_ref_matches_numpy(Tp, W):
    rng = np.random.default_rng(Tp * 1000 + W)
    kprev = random_row(rng, Tp)
    cost = rng.uniform(0, 10, size=W).astype(np.float32)
    got_v, got_i = minplus_step_ref(kprev, cost)
    want_v, want_i = numpy_minplus(kprev.astype(np.float64), cost.astype(np.float64))
    np.testing.assert_allclose(np.asarray(got_v), want_v, rtol=1e-6)
    # argmin must point at an equally-minimal item (ties may differ)
    chosen = kprev[np.maximum(np.arange(Tp) - np.asarray(got_i), 0)] + cost[np.asarray(got_i)]
    chosen = np.minimum(chosen, float(BIG))
    np.testing.assert_allclose(chosen, want_v, rtol=1e-6)


@pytest.mark.parametrize("Tp,W,BT", [
    (64, 16, 128),
    (255, 64, 128),
    (1024, 256, 256),
    (1000, 511, 128),
    pytest.param(2048, 1024, 1024, marks=pytest.mark.slow),  # big interpret-mode sweep
    (33, 33, 1024),  # tile larger than the row
    # rows that do not fill the eight folded segments of whole tiles
    (1, 1, 128),
    (129, 64, 128),
    (1025, 130, 128),
    (2 * 1024 + 1, 256, 256),
    (300, 700, 128),  # band wider than a segment: the halo spans several segments
])
def test_pallas_matches_ref(Tp, W, BT):
    rng = np.random.default_rng(Tp + W + BT)
    kprev = random_row(rng, Tp)
    cost = rng.uniform(0, 10, size=W).astype(np.float32)
    cost[W // 2 :] += np.where(rng.random(W - W // 2) < 0.2, float(BIG), 0.0).astype(np.float32)
    cost = np.minimum(cost, float(BIG))
    ref_v, ref_i = minplus_step_ref(kprev, cost)
    pal_v, pal_i = minplus_pallas(kprev, cost, BT=BT, interpret=True)
    # the same float32 sums in the same band order: bit-identical to the oracle
    np.testing.assert_array_equal(np.asarray(pal_v), np.asarray(ref_v))
    np.testing.assert_array_equal(np.asarray(pal_i), np.asarray(ref_i))
    # argmin consistency: reconstruct value from index
    pi = np.asarray(pal_i)
    src = np.arange(Tp) - pi
    ok = src >= 0
    recon = np.where(ok, kprev[np.maximum(src, 0)] + cost[pi], float(BIG))
    recon = np.minimum(recon, float(BIG))
    np.testing.assert_allclose(recon, np.asarray(ref_v), rtol=1e-6)


@pytest.mark.parametrize("B,Tp,W,BT", [
    (1, 200, 64, 128),
    (3, 1025, 130, 128),
    (3, 300, 700, 128),
])
def test_pallas_ties_resolve_to_first_minimum(B, Tp, W, BT):
    # constant cost rows over a previous row of few distinct values: most
    # outputs tie across many band steps, and each must take the first
    rng = np.random.default_rng(B * 1000 + Tp + W)
    kprev = rng.integers(0, 3, size=(B, Tp)).astype(np.float32)
    kprev[rng.random((B, Tp)) < 0.2] = float(BIG)
    cost = np.repeat(rng.integers(0, 5, size=(B, 1)), W, axis=1).astype(np.float32)
    ref_v, ref_i = minplus_step_ref_batch(kprev, cost)
    pal_v, pal_i = minplus_pallas_batch(kprev, cost, BT=BT, interpret=True)
    np.testing.assert_array_equal(np.asarray(pal_v), np.asarray(ref_v))
    np.testing.assert_array_equal(np.asarray(pal_i), np.asarray(ref_i))


@pytest.mark.parametrize("dtype", [np.float32, np.float64, np.int32])
def test_pallas_dtype_coercion(dtype):
    rng = np.random.default_rng(0)
    kprev = rng.integers(0, 50, size=128).astype(dtype)
    cost = rng.integers(0, 9, size=32).astype(dtype)
    ref_v, _ = minplus_step_ref(kprev.astype(np.float32), cost.astype(np.float32))
    pal_v, _ = minplus_pallas(kprev, cost, BT=128, interpret=True)
    np.testing.assert_allclose(np.asarray(pal_v), np.asarray(ref_v), rtol=1e-6)


def test_dp_via_pallas_backend_end_to_end():
    """Full scheduling DP with the Pallas kernel == numpy DP."""
    rng = np.random.default_rng(42)
    from repro.core import random_problem

    for regime in ("arbitrary", "decreasing", "increasing"):
        p = random_problem(rng, n=5, T=40, regime=regime)
        x_pal = Solver().solve(p, algorithm="dp_jax_pallas").schedule
        x_np = solve_schedule_dp(p)
        assert total_cost(p, x_pal) == pytest.approx(total_cost(p, x_np), rel=1e-5)


def _banded_rows(rng, bounds, W):
    """Cost rows of width ``W`` whose last finite entry is at ``bound - 1``,
    BIG after it: rebased costs, some negative; the first row with room
    holds an interior BIG too."""
    cost = rng.uniform(-8, 10, size=(len(bounds), W)).astype(np.float32)
    for b, bound in enumerate(bounds):
        cost[b, bound:] = float(BIG)
    interior = [b for b, bound in enumerate(bounds) if bound > 2]
    if interior:
        b = interior[0]
        cost[b, bounds[b] // 2] = float(BIG)
    return cost


@pytest.mark.parametrize("W,bounds", [
    (64, (1,)),
    (64, (31,)),
    (64, (1, 33, 64)),
    (64, (32, 31, 1)),
    (100, (33,)),
    (100, (1, 32, 100)),
    (100, (97, 99, 31)),  # past the last whole group of 32: the remainder runs
    (1024, (32,)),
    (1024, (1, 33, 1024)),
])
def test_pallas_band_bound_matches_ref(W, bounds):
    """With each row's own band bound the kernel stays bit-identical to the
    oracle, and a full-width row is unchanged by the bound."""
    B, Tp = len(bounds), 300
    rng = np.random.default_rng(W * 100 + sum(bounds))
    kprev = np.stack([random_row(rng, Tp) for _ in range(B)])
    kprev -= np.float32(40.0) * (kprev < float(BIG))  # negative DP values too
    kprev[:, rng.choice(Tp, size=Tp // 10, replace=False)] = float(BIG)  # all-BIG columns
    kprev[:, 0] = 0.0
    cost = _banded_rows(rng, bounds, W)
    band = np.asarray(bounds, np.int32)
    ref_v, ref_i = minplus_step_ref_batch(kprev, cost)
    pal_v, pal_i = minplus_pallas_batch(kprev, cost, band, BT=128, interpret=True)
    np.testing.assert_array_equal(np.asarray(pal_v), np.asarray(ref_v))
    np.testing.assert_array_equal(np.asarray(pal_i), np.asarray(ref_i))
    full = np.full(B, W, np.int32)
    full_v, full_i = minplus_pallas_batch(kprev, cost, full, BT=128, interpret=True)
    none_v, none_i = minplus_pallas_batch(kprev, cost, BT=128, interpret=True)
    np.testing.assert_array_equal(np.asarray(full_v), np.asarray(none_v))
    np.testing.assert_array_equal(np.asarray(full_i), np.asarray(none_i))
    np.testing.assert_array_equal(np.asarray(none_v), np.asarray(ref_v))
    np.testing.assert_array_equal(np.asarray(none_i), np.asarray(ref_i))
    # the single-instance entry point takes a scalar bound
    one_v, one_i = minplus_pallas(kprev[0], cost[0], band[0], BT=128, interpret=True)
    np.testing.assert_array_equal(np.asarray(one_v), np.asarray(ref_v)[0])
    np.testing.assert_array_equal(np.asarray(one_i), np.asarray(ref_i)[0])


@pytest.mark.parametrize("W,bounds", [(64, (1, 31, 33)), (100, (32, 97, 100))])
def test_pallas_band_bound_ends_the_band_loop(W, bounds):
    """Where the costs run on past the bound, the kernel runs exactly
    ``band_steps`` of it: its answer is the oracle's on the table cut there."""
    B, Tp = len(bounds), 300
    rng = np.random.default_rng(W + sum(bounds))
    kprev = np.stack([random_row(rng, Tp) for _ in range(B)])
    cost = rng.uniform(0, 10, size=(B, W)).astype(np.float32)  # finite to W
    steps = band_steps(np.asarray(bounds), W)
    cut = np.where(np.arange(W)[None, :] < steps[:, None], cost, np.float32(BIG))
    ref_v, ref_i = minplus_step_ref_batch(kprev, cut)
    pal_v, pal_i = minplus_pallas_batch(
        kprev, cost, np.asarray(bounds, np.int32), BT=128, interpret=True
    )
    np.testing.assert_array_equal(np.asarray(pal_v), np.asarray(ref_v))
    np.testing.assert_array_equal(np.asarray(pal_i), np.asarray(ref_i))
    assert list(steps) == [min(W, -(-b // 32) * 32) for b in bounds]
