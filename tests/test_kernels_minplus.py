"""Shape/dtype sweeps for the Pallas min-plus kernel vs the jnp oracle.

The kernel runs in interpret mode on the CPU; the oracle is
``minplus_step_ref``; a hand-rolled numpy triple-check guards the oracle.
"""

import numpy as np
import pytest

from repro.core import Problem, solve_schedule_dp, total_cost
from repro.core.jax_dp import solve_schedule_dp_jax
from repro.kernels import BIG, minplus_pallas, minplus_step_ref


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
])
def test_pallas_matches_ref(Tp, W, BT):
    rng = np.random.default_rng(Tp + W + BT)
    kprev = random_row(rng, Tp)
    cost = rng.uniform(0, 10, size=W).astype(np.float32)
    cost[W // 2 :] += np.where(rng.random(W - W // 2) < 0.2, float(BIG), 0.0).astype(np.float32)
    cost = np.minimum(cost, float(BIG))
    ref_v, _ = minplus_step_ref(kprev, cost)
    pal_v, pal_i = minplus_pallas(kprev, cost, BT=BT, interpret=True)
    np.testing.assert_allclose(np.asarray(pal_v), np.asarray(ref_v), rtol=1e-6)
    # argmin consistency: reconstruct value from index
    pi = np.asarray(pal_i)
    src = np.arange(Tp) - pi
    ok = src >= 0
    recon = np.where(ok, kprev[np.maximum(src, 0)] + cost[pi], float(BIG))
    recon = np.minimum(recon, float(BIG))
    np.testing.assert_allclose(recon, np.asarray(ref_v), rtol=1e-6)


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
        x_pal = solve_schedule_dp_jax(p, backend="pallas")
        x_np = solve_schedule_dp(p)
        assert total_cost(p, x_pal) == pytest.approx(total_cost(p, x_np), rel=1e-5)
