"""Batched vs looped (MC)^2MKP DP throughput (DESIGN.md §9).

A what-if sweep — ``B`` candidate workloads over one fleet — is solved two
ways:

  * ``loop``:  a Python loop of ``B`` single-instance solves, each through
    :func:`solve_fused_batch_jax` on its own pack; every distinct ``T``
    compiles its own program, and every instance pays packing + dispatch +
    device_get.
  * ``batch``: ONE :func:`solve_fused_batch_jax` call — the instances are
    stacked ``(B, n, W)`` and the whole sweep is a single compiled program.

Reports cold (fresh jit caches, the first-sweep experience) and warm
(steady-state) timings and writes ``BENCH_batch.json`` with the headline
``speedup_vs_loop`` (cold, since a fresh sweep is the production shape of a
scenario-planning call). Run as::

    PYTHONPATH=src python benchmarks/bench_batch.py [--smoke] [--out PATH]
"""

import argparse
import json
import time

import jax.numpy as jnp
import numpy as np

from repro.core import Problem, ProblemBatch, random_problem
from repro.core.jax_dp import pack_problem, solve_fused_batch_jax
from repro.core.problem import remove_lower_limits, restore_lower_limits
from repro.kernels import resolve_backend


def make_sweep(rng: np.random.Generator, B: int, n: int, T: int):
    """One fleet, ``B`` distinct candidate workloads in [T/2, T]."""
    base = random_problem(rng, n=n, T=T, regime="arbitrary", with_lower=False)
    Ts = np.unique(np.linspace(max(1, T // 2), T, B).astype(int))
    while len(Ts) < B and Ts.min() > 1:  # tiny T ranges: extend downward
        Ts = np.unique(np.concatenate([[Ts.min() - 1], Ts]))
    if len(Ts) < B:  # fewer than B distinct workloads exist in [1, T]: reuse
        Ts = np.concatenate([Ts, np.resize(Ts, B - len(Ts))])
    return [
        Problem(T=int(t), lower=base.lower, upper=base.upper, cost_tables=base.cost_tables)
        for t in sorted(Ts)
    ]


def solve_fused(problems) -> np.ndarray:
    """``(B, n)`` int64 schedules from the fused program on the problems'
    own pack, with no shape bucketing."""
    batch = ProblemBatch.from_problems(problems)
    batch.validate()
    b0 = remove_lower_limits(batch)
    X, _ = solve_fused_batch_jax(
        pack_problem(b0),
        jnp.asarray(b0.T, jnp.int32),
        int(b0.T.max()),
        backend=resolve_backend("auto"),
    )
    return restore_lower_limits(batch, np.asarray(X).astype(np.int64))


def _clear_jit_caches():
    import jax

    jax.clear_caches()


def time_sweep(problems, mode: str, reps: int = 3, cold: bool = False):
    """Best-of-``reps`` wall time for one full sweep; ``cold`` clears jit
    caches before every rep so each timing includes compilation."""
    best = float("inf")
    schedules = None
    for _ in range(reps):
        if cold:
            _clear_jit_caches()
        t0 = time.perf_counter()
        if mode == "loop":
            schedules = [solve_fused([p])[0] for p in problems]
        else:
            X = solve_fused(problems)
            schedules = [X[b, : p.n] for b, p in enumerate(problems)]
        best = min(best, time.perf_counter() - t0)
    return best, schedules


def run_bench(B: int, n: int, T: int, reps: int = 3) -> dict:
    rng = np.random.default_rng(0)
    problems = make_sweep(rng, B, n, T)

    loop_cold, xs_loop = time_sweep(problems, "loop", reps=1, cold=True)
    batch_cold, xs_batch = time_sweep(problems, "batch", reps=1, cold=True)
    for a, b in zip(xs_loop, xs_batch):  # same programs => identical schedules
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    loop_warm, _ = time_sweep(problems, "loop", reps=reps)
    batch_warm, _ = time_sweep(problems, "batch", reps=reps)

    return {
        "B": len(problems),
        "n": n,
        "T": T,
        "loop_cold_s": loop_cold,
        "batch_cold_s": batch_cold,
        "loop_warm_s": loop_warm,
        "batch_warm_s": batch_warm,
        "speedup_cold": loop_cold / batch_cold,
        "speedup_warm": loop_warm / batch_warm,
        # headline: a fresh sweep is how scenario planning meets the solver
        "speedup_vs_loop": loop_cold / batch_cold,
    }


def run():
    """Harness entry point (benchmarks.run): one moderate sweep."""
    r = run_bench(B=16, n=16, T=128)
    return [
        (
            f"batch_dp_B{r['B']}_T{r['T']}",
            r["batch_warm_s"] / r["B"] * 1e6,
            f"speedup_cold={r['speedup_cold']:.1f}x speedup_warm={r['speedup_warm']:.1f}x",
        )
    ]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true", help="small fast config for CI")
    ap.add_argument("--out", default="BENCH_batch.json")
    ap.add_argument("--B", type=int, default=None)
    ap.add_argument("--n", type=int, default=16)
    ap.add_argument("--T", type=int, default=None)
    args = ap.parse_args()
    B = args.B or (16 if args.smoke else 32)
    T = args.T or (96 if args.smoke else 256)
    result = run_bench(B=B, n=args.n, T=T)
    with open(args.out, "w") as f:
        json.dump(result, f, indent=2)
        f.write("\n")
    print(json.dumps(result, indent=2))
    print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
