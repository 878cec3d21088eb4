"""Blocked min-plus kernel engine vs the dense oracle (DESIGN.md §12).

Two legs are measured and written to ``BENCH_kernels.json``:

  * **blocked vs dense** — one warm batched DP row update at a memory-bound
    shape (B=8, T=8192, W=512: the oracle materializes a ~134 MB candidate
    tensor; the blocked backend streams BT x BW cache-resident blocks).
    ``speedup_blocked_vs_dense`` is the gated headline (hard floor 2.0 in
    scripts/check_bench.py; ~4-8x measured on CPU), and the same run
    asserts bit-identical values AND argmins (``max_parity_err`` must be
    exactly 0).
  * **fused solve** — a warm batched solve through the fused DP+backtrack
    program (one jit call returning only ``(B, n)`` + K_last).
    ``fused_solve_s`` is reported info-only.

Run as::

    PYTHONPATH=src python benchmarks/bench_kernels.py [--smoke] [--out PATH]

(The interpret-mode Pallas TPU kernel is validated in the test
suite, not timed here — Python-interpreted kernel timing says nothing
about hardware. The blocked jnp backend IS the CPU production path.)
"""

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import random_problem
from repro.core.jax_dp import pack_problem, solve_fused_batch_jax
from repro.core.problem import ProblemBatch, remove_lower_limits
from repro.kernels import minplus_blocked_batch, minplus_step_ref_batch


def _bench(fn, reps):
    """Warm best-of-``reps`` seconds (fn must block on its own result)."""
    fn()  # warmup / compile
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def bench_blocked_vs_dense(B: int, Tp: int, W: int, reps: int) -> dict:
    rng = np.random.default_rng(0)
    kprev = rng.uniform(0, 100, (B, Tp)).astype(np.float32)
    kprev[:, 0] = 0.0
    cost = rng.uniform(0, 10, (B, W)).astype(np.float32)
    k, c = jnp.asarray(kprev), jnp.asarray(cost)

    dense = jax.jit(minplus_step_ref_batch)
    blocked = jax.jit(lambda a, b: minplus_blocked_batch(a, b))

    dv, di = dense(k, c)
    bv, bi = blocked(k, c)
    err = float(np.max(np.abs(np.asarray(dv) - np.asarray(bv))))
    idx_mismatch = int(np.sum(np.asarray(di) != np.asarray(bi)))
    # enforced here, not just reported: the property suite stops at small
    # shapes, so this is the only parity check at the production shape
    # (a real raise, not `assert` — python -O must not strip it)
    if err != 0.0 or idx_mismatch != 0:
        raise RuntimeError(
            f"blocked kernel diverged from oracle at B={B} T={Tp - 1} W={W}: "
            f"maxerr={err}, argmin mismatches={idx_mismatch}"
        )

    dense_s = _bench(lambda: dense(k, c)[0].block_until_ready(), reps)
    blocked_s = _bench(lambda: blocked(k, c)[0].block_until_ready(), reps)
    return {
        "B": B,
        "T": Tp - 1,
        "W": W,
        "dense_step_s": dense_s,
        "blocked_step_s": blocked_s,
        "speedup_blocked_vs_dense": dense_s / blocked_s,
        "max_parity_err": err,
        "argmin_mismatches": idx_mismatch,
    }


def bench_fused_solve(B: int, n: int, T: int, reps: int) -> dict:
    rng = np.random.default_rng(1)
    probs = [
        random_problem(rng, n=n, T=int(t), regime="arbitrary", with_lower=False)
        for t in np.linspace(max(1, T // 2), T, B).astype(int)
    ]
    b0 = remove_lower_limits(ProblemBatch.from_problems(probs))
    costs = pack_problem(b0)
    Tmax = int(b0.T.max())
    t_star = jnp.asarray(b0.T, dtype=jnp.int32)

    def fused():
        X, _ = solve_fused_batch_jax(costs, t_star, Tmax, backend="blocked")
        return np.asarray(jax.device_get(X))

    return {
        "solve_B": B,
        "solve_n": n,
        "solve_T": T,
        "fused_solve_s": _bench(fused, reps),
    }


def run_bench(smoke: bool) -> dict:
    # the acceptance shape: memory-bound for the oracle on any CPU
    reps = 3 if smoke else 10
    out = bench_blocked_vs_dense(B=8, Tp=8193, W=512, reps=reps)
    out.update(bench_fused_solve(B=16, n=8, T=256 if smoke else 1024, reps=reps))
    return out


def run():
    """Harness entry point (benchmarks.run): CSV rows from one smoke pass."""
    r = run_bench(smoke=True)
    return [
        (
            f"minplus_blocked_B{r['B']}_T{r['T']}_W{r['W']}",
            r["blocked_step_s"] * 1e6,
            f"speedup_vs_dense={r['speedup_blocked_vs_dense']:.1f}x "
            f"maxerr={r['max_parity_err']:.1e}",
        ),
        (
            f"fused_solve_B{r['solve_B']}_T{r['solve_T']}",
            r["fused_solve_s"] * 1e6,
            f"n={r['solve_n']}",
        ),
    ]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true", help="fewer reps for CI")
    ap.add_argument("--out", default="BENCH_kernels.json")
    args = ap.parse_args()
    result = run_bench(smoke=args.smoke)
    with open(args.out, "w") as f:
        json.dump(result, f, indent=2)
        f.write("\n")
    print(json.dumps(result, indent=2))
    print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
