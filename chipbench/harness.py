"""The benchmark harness: finds a cell's configuration, traffic mix and
metrics by name, drives the planning service with the cell's traffic, and
assembles the result line.

Everything that belongs to one configuration, mix or metric is a file of its
own, found by the name ``BENCHMARK.json`` gives it:

* ``configs/<config>.json``: the deployment (the ``file`` of its entry), whose
  ``family`` names ``families/<family>.py`` (see :mod:`chipbench.traffic`);
* ``traffic/<mix>.json``: the load, whose ``arrivals`` names
  ``arrivals/<arrivals>.py``;
* ``metrics/<metric>.py``: a reader with ``read(record)`` that returns the
  metric's value from the run record, or ``None`` where it finds nothing.

The system under test is the program's ``SchedulerService`` over a
``SweepEngine``; the benchmark takes from it only its answers, its counters
and the device trace. Requests are timed on the benchmark's own clock, from
the time each was due.
"""

from __future__ import annotations

import json
import math
import os
import shutil
import sys
import tempfile
import threading
import time
from contextlib import nullcontext
from pathlib import Path

import numpy as np

from . import check, stats, trace as tracemod
from .traffic import Traffic, load

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
# how long after the window closes the benchmark still waits for an answer
GIVE_UP_AFTER_S = 60.0


class BenchmarkError(RuntimeError):
    """The benchmark cannot run as asked: no such cell, file or device."""


# ---------------------------------------------------------------------------
# finding things by name
# ---------------------------------------------------------------------------


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


class Bench:
    """``BENCHMARK.json`` and the files it names, under ``root``."""

    def __init__(self, root: Path = ROOT):
        self.root = Path(root)
        self.here = self.root / HERE.name
        self.spec = load_json(self.root / "BENCHMARK.json")

    def cell(self, name: str) -> dict:
        for w in self.spec["workloads"]:
            if w["name"] == name:
                return w
        raise BenchmarkError(f"no workload named {name!r} in BENCHMARK.json")

    def config(self, cell: dict) -> dict:
        for c in self.spec["configs"]:
            if c["name"] == cell["config"]:
                return load_json(self.root / c["file"])
        raise BenchmarkError(f"no configuration named {cell['config']!r}")

    def mix(self, cell: dict) -> dict:
        return load_json(self.here / "traffic" / f"{cell['traffic']}.json")

    def traffic(self, cell: dict) -> Traffic:
        try:
            return Traffic(self.config(cell), self.mix(cell), self.here)
        except LookupError as e:
            raise BenchmarkError(str(e)) from None

    def metrics(self, cell: dict, trace: bool) -> list:
        """The metrics a run of ``cell`` reports: its end-to-end metrics, or
        with ``trace`` its per-layer ones."""
        group = self.spec["per_layer" if trace else "end_to_end"]
        return [m for m in group if cell["name"] in m.get("workloads", [cell["name"]])]

    def reader(self, name: str):
        try:
            return load("metrics", name, self.here).read
        except LookupError as e:
            raise BenchmarkError(str(e)) from None


# ---------------------------------------------------------------------------
# the system under test
# ---------------------------------------------------------------------------


def _annotation(name: str, on: bool):
    if not on:
        return nullcontext()
    import jax

    return jax.profiler.TraceAnnotation(name)


class TimedEngine:
    """The program's engine behind a proxy that times every ``dispatch`` on
    the benchmark's clock (and, when tracing, marks it in the trace)."""

    def __init__(self, engine):
        self._engine = engine
        self.spans = []  # (start, end) on time.perf_counter
        self.annotate = False

    def dispatch(self, problems, split_regimes: bool = False):
        t0 = time.perf_counter()
        with _annotation("chipbench.dispatch", self.annotate):
            handle = self._engine.dispatch(problems, split_regimes=split_regimes)
        self.spans.append((t0, time.perf_counter()))
        return handle

    def __getattr__(self, name):
        return getattr(self._engine, name)


def _pow2(x: int) -> int:
    return 1 if x <= 1 else 1 << (int(x) - 1).bit_length()


class Window:
    """The measured window as an arrival module sees it: a clock from the
    window's opening, and two ways to send request ``i`` of the plan.

    :meth:`send` sends it now and leaves a thread of its own waiting for the
    answer (open loop); :meth:`call` sends it now and waits in the calling
    thread (closed loop). A request whose plan gives no due time is due when
    it is sent. Every request is timed from its due time to its answer."""

    def __init__(self, service, split: bool, problems, due_s, seconds: float, traced: bool):
        self.seconds = float(seconds)
        self.give_up = self.seconds + GIVE_UP_AFTER_S
        self._svc, self._split, self._problems = service, split, problems
        self._due = due_s
        self._traced = traced
        self._lock = threading.Lock()
        self.requests = {}  # plan index -> record
        self.waiters = []
        self.t0 = time.perf_counter()

    def now(self) -> float:
        return time.perf_counter() - self.t0

    def wait_until(self, t: float) -> None:
        delay = t - self.now()
        if delay > 0:
            time.sleep(delay)

    def _submit(self, i: int):
        sent = self.now()
        due = float(self._due[i])
        rec = {"i": int(i), "due": sent if math.isnan(due) else due, "sent": sent}
        try:
            with _annotation("chipbench.submit", self._traced):
                fut = self._svc.submit(self._problems[i], split_regimes=self._split)
        except Exception as e:  # a refused request is judged, not raised
            rec["error"], fut = repr(e), None
        rec["submitted"] = self.now()
        with self._lock:
            self.requests[int(i)] = rec
        return fut, rec

    def _wait(self, fut, rec) -> None:
        if fut is None:
            return
        try:
            x = fut.result(timeout=max(0.0, self.give_up - self.now()))
            rec["done"] = self.now()
            with _annotation("chipbench.waiter", self._traced):
                rec["objective"] = float(fut.objectives())
            rec["x"] = np.asarray(x)
            rec["ok"] = True
        except Exception as e:  # a failed request is judged, not raised
            rec["error"] = repr(e)

    def send(self, i: int) -> None:
        fut, rec = self._submit(i)
        th = threading.Thread(target=self._wait, args=(fut, rec), name="chipbench-waiter")
        th.start()
        with self._lock:
            self.waiters.append(th)

    def call(self, i: int) -> None:
        self._wait(*self._submit(i))


class Session:
    """One cell's planning service: built and warmed once, then driven by
    one or more windows of traffic."""

    def __init__(self, traffic: Traffic):
        from repro.core import SweepEngine
        from repro.serve import SchedulerService

        config = traffic.config
        svc = config["service"]
        self.traffic = traffic
        self.config = config
        self.split = bool(svc["split_regimes"])
        self.engine = TimedEngine(SweepEngine())
        self.service = SchedulerService(
            engine=self.engine,
            max_batch=int(svc["max_batch"]),
            max_delay_s=float(svc["max_delay_s"]),
        )

    @staticmethod
    def problems(plan):
        from repro.core import Problem

        return [
            Problem(T=i.T, lower=i.lower, upper=i.upper, cost_tables=i.tables)
            for i in plan.instances
        ]

    def warm(self, plan) -> None:
        """Compiles (or loads from the persistent cache) and runs once every
        executable this plan's traffic can reach: its buckets over every batch
        size a flush can have. A monotone population on the regime-split path
        warms only the selection kernel's buckets."""
        from repro.serve.coalesce import pow2_ladder, warm_batch

        specs = sorted(
            {
                (_pow2(i.n), _pow2(i.T - int(i.lower.sum())), _pow2(int(i.upper.max()) + 1))
                for i in plan.instances
            }
        )
        if self.split and self.config["regime"] == "increasing":
            # the selection buckets have no T axis: one warm-up per (n, W)
            for (n, W), T in sorted({(n, W): T for n, T, W in specs}.items()):
                for B in pow2_ladder(self.service.max_batch):
                    batch = warm_batch(n, T, W, B, regime="increasing")
                    self.engine.dispatch(batch, split_regimes=True).result()
        else:
            self.service.warm(specs, split_regimes=self.split)

    def window(self, plan, problems, seconds: float, trace_dir=None, mix=None) -> dict:
        """Drives the plan's requests for ``seconds`` as the mix's arrival
        module sends them, waits for every answer (at most
        :data:`GIVE_UP_AFTER_S` past the close) and returns the run record.
        With ``trace_dir`` the window is traced. Requests the arrival module
        never sent are not part of the run."""
        svc, eng = self.service, self.engine
        mix = self.traffic.mix if mix is None else mix
        eng.spans.clear()
        stats0, cache0 = svc.stats(), eng.cache_stats()
        traced = trace_dir is not None
        eng.annotate = traced
        if traced:
            import jax

            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
        with _annotation("chipbench.window", traced):
            win = Window(svc, self.split, problems, plan.due_s, seconds, traced)
            self.traffic.arrivals.drive(win, mix, plan.due_s)
            win.wait_until(win.seconds)
        for th in list(win.waiters):
            th.join(max(0.0, win.give_up + 1.0 - win.now()))
        if traced:
            jax.profiler.stop_trace()
        eng.annotate = False
        stats1, cache1 = svc.stats(), eng.cache_stats()
        hits0 = cache0["per_bucket_hits"]
        requests = [win.requests[i] for i in sorted(win.requests)]
        return {
            "window_s": win.seconds,
            "give_up_s": win.give_up,
            "requests": requests,
            "dispatch_spans": [(a - win.t0, b - win.t0) for a, b in eng.spans],
            "service": {
                k: stats1[k] - stats0[k]
                for k in stats1
                if isinstance(stats1[k], (int, float)) and k in stats0
            },
            "compiles": cache1["compiles"] - cache0["compiles"],
            "bucket_hits": {
                k: v - hits0.get(k, 0)
                for k, v in cache1["per_bucket_hits"].items()
                if v != hits0.get(k, 0)
            },
            "band_cells": [plan.instances[r["i"]].band_cells() for r in requests],
        }

    def close(self) -> None:
        self.service.close(timeout=GIVE_UP_AFTER_S)


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------


def check_devices(chips: int):
    """The devices of this run; raises where JAX finds no TPU or too few."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise BenchmarkError(
            f"JAX found no TPU (platform {devices[0].platform!r}); the benchmark "
            "does not run elsewhere"
        )
    if len(devices) < chips:
        raise BenchmarkError(f"the cell needs {chips} chips, JAX found {len(devices)}")
    if devices[0].device_kind not in load_json(HERE / "peaks.json"):
        raise BenchmarkError(f"no published peaks for {devices[0].device_kind!r} in peaks.json")
    return devices


def _device_info(chips: int) -> dict:
    import jax

    d = jax.devices()[0]
    mem = d.memory_stats() or {}
    return {
        "platform": d.platform,
        "kind": d.device_kind,
        "count": int(chips),
        "memory_peak_bytes": int(mem.get("peak_bytes_in_use", 0)),
    }


def run(
    workload: str,
    seed: int,
    seconds: float,
    trace: bool,
    t_process: float,
    root: Path = ROOT,
    log=sys.stderr,
) -> dict:
    """One run of one cell; returns the result line's object. ``t_process``
    is ``time.perf_counter()`` at process start, where set-up begins."""
    bench = Bench(root)
    cell = bench.cell(workload)
    traffic = bench.traffic(cell)
    config = traffic.config
    metrics = bench.metrics(cell, trace)
    readers = {m["name"]: bench.reader(m["name"]) for m in metrics}
    check_devices(int(cell["chips"]))
    plan = traffic.plan(seed, seconds)
    session = Session(traffic)
    problems = session.problems(plan)
    session.warm(plan)
    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None
    try:
        setup_s = time.perf_counter() - t_process
        record = session.window(plan, problems, seconds, trace_dir)
        record["setup_s"] = setup_s
        device = _device_info(cell["chips"])
        session.close()
        if trace:
            reduced = tracemod.reduce_dir(trace_dir)
            record["trace"] = reduced
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]
    finally:
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)
    del session, problems
    instances = [plan.instances[r["i"]] for r in record["requests"]]
    correct, numbers = check.judge(config, instances, record["requests"], seed, bench.here)
    numbers += check.path_numbers(config, record, instances)
    correct = correct and all(v <= lim for _, v, lim in numbers)
    out_metrics = {}
    for m in metrics:
        value = readers[m["name"]](record)
        if value is not None:
            out_metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    failed = sum(1 for r in record["requests"] if not r.get("ok"))
    result = {
        "correct": bool(correct),
        "attempted": len(record["requests"]),
        "failed": int(failed),
        "metrics": out_metrics,
        "device": device,
    }
    if trace:
        result["breakdown"] = {
            "device_ops": record["trace"]["top_ops"],
            "idle_gaps": record["trace"]["idle_gaps"],
        }
    result["checks"] = {name: {"value": value, "limit": limit} for name, value, limit in numbers}
    _report(record, numbers, log)
    return result


def _report(record: dict, numbers, log) -> None:
    """The run's diagnostics on ``log``; the compared numbers come last."""
    lateness = [r["sent"] - r["due"] for r in record["requests"]]
    if lateness:
        print(
            f"generator lateness ms: p50 {stats.percentile(lateness, 50) * 1e3:.3f} "
            f"max {max(lateness) * 1e3:.3f}",
            file=log,
        )
    print(f"service stats delta: {json.dumps(record['service'], sort_keys=True)}", file=log)
    print(f"buckets hit in window: {json.dumps(record['bucket_hits'], sort_keys=True)}", file=log)
    for name, value, limit in numbers:
        print(f"check {name} {value!r} limit {limit!r}", file=log)
    log.flush()


def main(argv=None, t_process: float | None = None) -> int:
    import argparse

    t_process = time.perf_counter() if t_process is None else t_process
    ap = argparse.ArgumentParser(description="Runs one benchmark cell on the chip.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a whole number >= 0")
    try:
        result = run(args.workload, args.seed, args.seconds, bool(args.trace), t_process)
    except BenchmarkError as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    print(json.dumps(result), flush=True)
    return 0


def use_compile_cache() -> None:
    """Turns on the program's persistent compilation cache (its directory,
    or ``$JAX_COMPILATION_CACHE_DIR`` where that is set), and caches every
    program however fast it compiles, so that only a cell's first run
    compiles. Call before JAX is imported."""
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    from repro.launch.compile_cache import use_compile_cache as program_cache

    program_cache()
