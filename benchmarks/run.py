"""Benchmark harness — one module per paper table/figure (see DESIGN.md §6).

Prints ``name,us_per_call,derived`` CSV. Run as:
    PYTHONPATH=src python -m benchmarks.run [--only example,kernels,...]
"""

import argparse
import sys

MODULES = ("example", "optimality", "runtime", "batch", "sweep", "async", "fl_energy", "pareto", "kernels", "marginal", "roofline", "serve", "fleet", "faults", "adaptive")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None, help="comma-separated subset of: " + ",".join(MODULES))
    args = ap.parse_args()
    which = args.only.split(",") if args.only else MODULES

    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    print("name,us_per_call,derived")
    failed = []
    for name in which:
        try:
            mod = __import__(f"benchmarks.bench_{name}", fromlist=["run"])
            for row_name, us, derived in mod.run():
                print(f"{row_name},{us:.2f},{derived}")
            sys.stdout.flush()
        except Exception as e:  # keep the harness going; report at the end
            failed.append((name, repr(e)))
            print(f"bench_{name}_FAILED,0.00,{e!r}")
    if failed:
        raise SystemExit(f"benchmarks failed: {[n for n, _ in failed]}")


if __name__ == "__main__":
    main()
