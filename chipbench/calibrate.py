#!/usr/bin/env python3
"""Measurements that set the benchmark's numbers, each in one process that
pays the cell's set-up once.

    python3 chipbench/calibrate.py sweep --workload <cell> --rates 8,10,12 --seconds 10
    python3 chipbench/calibrate.py readings --workload <cell> --seeds 1,2,3 \
        --control-seeds 4,5,6 --seconds 6

``sweep`` drives the cell's traffic at each offered rate in turn and prints,
per rate, the rate answered within the window, the latency percentiles, and
the mean latency of the first and last thirds of the requests: a backlog that
grows shows as a last third far slower than the first. The knee is the
highest rate whose answers keep up. ``readings`` runs short windows at the
cell's own rate on each seed and prints the numbers that decide ``correct``,
then the same numbers for the control (``control.py``) on its seeds: the
lower and upper readings each limit is set between. Without a TPU both exit
non-zero.
"""

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from chipbench import harness  # noqa: E402


def _prepare(workload: str, root: Path):
    bench = harness.Bench(root)
    cell = bench.cell(workload)
    traffic = bench.traffic(cell)
    harness.check_devices(int(cell["chips"]))
    return traffic


def _window(session, mix, seed, seconds):
    plan = session.traffic.plan(seed, seconds, mix)
    record = session.window(plan, session.problems(plan), seconds, mix=mix)
    return plan, record


def _latency_summary(record) -> dict:
    lat = harness.stats.latencies_s(record["requests"], record["give_up_s"])
    third = max(1, len(lat) // 3)
    answered = sum(1 for r in record["requests"] if r.get("ok") and r["done"] <= record["window_s"])
    return {
        "requests": len(lat),
        "answered_in_window_per_s": harness.stats.rate(answered, record["window_s"]),
        "p50_ms": 1e3 * harness.stats.percentile(lat, 50),
        "p95_ms": 1e3 * harness.stats.percentile(lat, 95),
        "first_third_mean_ms": 1e3 * float(lat[:third].mean()),
        "last_third_mean_ms": 1e3 * float(lat[-third:].mean()),
        "flushes": record["service"]["flushes"],
        "mean_flush_rows": record["service"]["flushed_rows"] / max(1, record["service"]["flushes"]),
        "compiles_in_window": record["compiles"],
        "late_ms_max": 1e3 * max(r["sent"] - r["due"] for r in record["requests"]),
    }


def sweep(args, root: Path) -> list:
    traffic = _prepare(args.workload, root)
    rates = [float(r) for r in args.rates.split(",")]
    session = harness.Session(traffic)
    top = dict(traffic.mix, rate_per_s=max(rates))
    session.warm(traffic.plan(args.seed, args.seconds, top))
    print(f"set-up {time.perf_counter() - T_PROCESS:.3f} s", flush=True)
    rows = []
    for rate in rates:
        _, record = _window(session, dict(traffic.mix, rate_per_s=rate), args.seed, args.seconds)
        row = {"offered_per_s": rate, **_latency_summary(record)}
        print(json.dumps(row), flush=True)
        rows.append(row)
    session.close()
    return rows


def readings(args, root: Path) -> dict:
    from chipbench import check, control

    traffic = _prepare(args.workload, root)
    config = traffic.config
    seeds = [int(s) for s in args.seeds.split(",") if s]
    session = harness.Session(traffic)
    session.warm(traffic.plan(seeds[0], args.seconds))
    print(f"set-up {time.perf_counter() - T_PROCESS:.3f} s", flush=True)
    program = []
    for seed in seeds:
        plan, record = _window(session, traffic.mix, seed, args.seconds)
        t = time.perf_counter()
        instances = [plan.instances[r["i"]] for r in record["requests"]]
        correct, numbers = check.judge(config, instances, record["requests"], seed)
        numbers += check.path_numbers(config, record, instances)
        correct = correct and all(v <= lim for _, v, lim in numbers)
        row = {
            "seed": seed,
            "correct": correct,
            "numbers": {k: v for k, v, _ in numbers},
            "check_s": time.perf_counter() - t,
            **_latency_summary(record),
        }
        print(json.dumps(row), flush=True)
        program.append(row)
    session.close()
    controls = []
    for seed in [int(s) for s in args.control_seeds.split(",") if s]:
        plan = traffic.plan(seed, args.seconds)
        t = time.perf_counter()
        correct, numbers = control.judge(config, plan, seed)
        row = {"control_seed": seed, "correct": correct, "numbers": {k: v for k, v, _ in numbers},
               "check_s": time.perf_counter() - t}
        print(json.dumps(row), flush=True)
        controls.append(row)
    summary = {
        name: {
            "lower": max(r["numbers"][name] for r in program),
            "upper": min((r["numbers"][name] for r in controls), default=None),
        }
        for name in check.NUMBERS
    }
    summary.update(
        {name: {"lower": max(r["numbers"][name] for r in program), "upper": None}
         for name in check.PATH_NUMBERS}
    )
    print(json.dumps({"summary": summary}), flush=True)
    return {"program": program, "control": controls, "summary": summary}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("mode", choices=("sweep", "readings"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seed", type=int, default=1, help="sweep: the traffic seed")
    ap.add_argument("--rates", default="", help="sweep: offered rates, comma-separated")
    ap.add_argument("--seeds", default="", help="readings: the program's seeds")
    ap.add_argument("--control-seeds", default="", help="readings: the control's seeds")
    args = ap.parse_args(argv)
    try:
        (sweep if args.mode == "sweep" else readings)(args, ROOT)
    except harness.BenchmarkError as e:
        print(f"calibrate: {e}", file=sys.stderr)
        return 2
    return 0


if __name__ == "__main__":
    harness.use_compile_cache()
    sys.exit(main())
