"""Sweep-engine benchmark: compile cache + device sharding (DESIGN.md §10).

Two questions, answered in one run and written to ``BENCH_sweep.json``:

  * **cold vs cached**: the first solve of a shape bucket pays XLA
    compilation; every later solve in the bucket (drifting costs, shifted
    workloads — the multi-round-campaign shape of traffic) reuses the warm
    executable. Headline ``speedup_cached_vs_cold`` (CI floor: >= 5x).
  * **sharded vs single-device**: the same warm solve with the batch axis
    sharded over all host devices (forced to ``--devices`` CPU devices via
    XLA_FLAGS, which must be set BEFORE jax initializes — hence the env
    fiddling at the top of main). Schedules are checked bit-identical.
    ``throughput_ratio`` > 1 means sharding won; on one physical CPU the
    forced host devices share cores, so this is a scaling smoke, not a
    speedup demo.

Run as::

    PYTHONPATH=src python benchmarks/bench_sweep.py [--smoke] [--out PATH]
"""

import argparse
import json
import os
import time


def drift(problems, factor):
    """Same shapes, perturbed cost values — the round-over-round estimate
    drift that must stay inside one compile-cache bucket."""
    from repro.core import Problem

    return [
        Problem(
            T=p.T,
            lower=p.lower,
            upper=p.upper,
            cost_tables=tuple(t * factor for t in p.cost_tables),
        )
        for p in problems
    ]


def time_best(fn, reps):
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def run_bench(B: int, n: int, T: int, reps: int = 3, sharded: bool = True) -> dict:
    import jax
    import numpy as np

    from repro.core import SweepEngine, make_sweep_mesh
    try:  # package import (python -m benchmarks.run) or script (python benchmarks/bench_sweep.py)
        from benchmarks.bench_batch import make_sweep, solve_fused
    except ImportError:
        from bench_batch import make_sweep, solve_fused

    rng = np.random.default_rng(0)
    problems = make_sweep(rng, B, n, T)

    # cold: fresh engine + cleared jit caches — the first-campaign experience
    jax.clear_caches()
    eng = SweepEngine()
    t0 = time.perf_counter()
    X_cold = eng.solve(problems)
    cold_s = time.perf_counter() - t0

    # cached: drifted instances land in the same bucket -> warm executable
    cached_s = time_best(lambda: eng.solve(drift(problems, 1.01)), reps)
    np.testing.assert_array_equal(X_cold, solve_fused(problems))

    result = {
        "B": len(problems),
        "n": n,
        "T": T,
        "cold_solve_s": cold_s,
        "cached_solve_s": cached_s,
        "speedup_cached_vs_cold": cold_s / cached_s,
        "cache": eng.cache_stats(),
    }

    n_dev = len(jax.devices())
    if sharded and n_dev > 1:
        eng_sh = SweepEngine(mesh=make_sweep_mesh())
        X_sh = eng_sh.solve(problems)  # warm-up (compiles the sharded program)
        np.testing.assert_array_equal(X_sh, X_cold)  # sharded == single-device
        sharded_s = time_best(lambda: eng_sh.solve(drift(problems, 1.01)), reps)
        result.update(
            {
                "sharded_devices": n_dev,
                "sharded_solve_s": sharded_s,
                "throughput_ratio": cached_s / sharded_s,
            }
        )
    return result


def run():
    """Harness entry point (benchmarks.run): cache behaviour only — the
    harness process has already initialized jax, so device forcing is out."""
    r = run_bench(B=16, n=16, T=128, sharded=False)
    return [
        (
            f"sweep_cached_B{r['B']}_T{r['T']}",
            r["cached_solve_s"] / r["B"] * 1e6,
            f"speedup_cached_vs_cold={r['speedup_cached_vs_cold']:.1f}x",
        )
    ]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true", help="small fast config for CI")
    ap.add_argument("--out", default="BENCH_sweep.json")
    ap.add_argument("--B", type=int, default=None)
    ap.add_argument("--n", type=int, default=16)
    ap.add_argument("--T", type=int, default=None)
    ap.add_argument(
        "--devices",
        type=int,
        default=8,
        help="forced host device count for the sharded leg (0 disables)",
    )
    args = ap.parse_args()

    # Must precede ANY jax import: the flag binds at first jax init.
    flags = os.environ.get("XLA_FLAGS", "")
    if args.devices > 1 and "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.devices} " + flags
        )

    B = args.B or (16 if args.smoke else 32)
    T = args.T or (96 if args.smoke else 256)
    result = run_bench(B=B, n=args.n, T=T, sharded=args.devices > 1)
    with open(args.out, "w") as f:
        json.dump(result, f, indent=2)
        f.write("\n")
    print(json.dumps(result, indent=2))
    print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
