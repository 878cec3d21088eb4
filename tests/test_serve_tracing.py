"""Spans and counters of the planning service and the engine (DESIGN.md §14,
"Observability").

Claims under test:
  * ``SchedulerService.stats()`` holds the stage timings and DP cell counts
    from construction, zero until traffic comes;
  * every admitted request is counted as taken by the coalescer, and every
    engine-served flush as landed;
  * the regime split is timed only where it runs, and the engine's phases
    add up to no more than its whole dispatch;
  * the DP cell counts equal a count made here from the problems' own
    shapes and their pow2 buckets, exactly;
  * a profiler trace holds the named spans, with the engine's inside the
    service's flush, each ``take`` joined to its request and its flush by id,
    and ``repro.engine.compile`` once per new bucket, never on a warm hit.
"""

import glob
import sys
import threading

import jax
import numpy as np
import pytest

from repro.core import Problem, SweepEngine, random_problem
from repro.core.sweep import DISPATCH_PHASES
from repro.serve import SchedulerService

NEW_KEYS = (
    "submit_s",
    "queue_wait_s",
    "taken_requests",
    "land_s",
    "landed_flushes",
) + DISPATCH_PHASES


def _pow2(x: int) -> int:
    p = 1
    while p < x:
        p *= 2
    return p


def _cells(problems):
    """``(band, computed)`` DP cells of ONE flush of ``problems``, counted
    from their shapes: the band over clients with work to place, and the
    executable's pow2 bucket over its rows."""
    band = 0
    for p in problems:
        Tp = p.T - int(p.lower.sum())
        width = p.upper - p.lower
        band += (Tp + 1) * int((width[width > 0] + 1).sum())
    Bb = _pow2(len(problems))
    nb = _pow2(max(p.n for p in problems))
    Tb = _pow2(max(p.T - int(p.lower.sum()) for p in problems))
    Wb = _pow2(max(int(p.upper.max()) for p in problems) + 1)
    return band, Bb * nb * (Tb + 1) * Wb


def _requests(seed: int, regime: str = "arbitrary"):
    """Four requests over two buckets (n 3 and n 6), one to three rows each."""
    rng = np.random.default_rng(seed)
    out = []
    for n, rows in ((3, 1), (3, 3), (6, 2), (6, 1)):
        out.append([random_problem(rng, n=n, T=10, regime=regime) for _ in range(rows)])
    return out


def _serve_one_at_a_time(svc, requests, split_regimes=False):
    """Each request alone in its flush: submitted once the last is answered."""
    for probs in requests:
        svc.submit(probs, split_regimes=split_regimes).result(timeout=60)


def test_new_counters_are_numbers_and_zero_on_a_new_service():
    with SchedulerService(engine=SweepEngine()) as svc:
        st = svc.stats()
    for k in NEW_KEYS:
        assert isinstance(st[k], (int, float)) and not isinstance(st[k], bool), k
        assert st[k] == 0, k
    assert SweepEngine().cache_stats()["compile_s"] == 0


def test_counts_and_cells_over_two_buckets():
    requests = _requests(31)
    svc = SchedulerService(engine=SweepEngine(), max_batch=4, max_delay_s=0.001)
    _serve_one_at_a_time(svc, requests)
    svc.close(timeout=60)
    st = svc.stats()
    assert st["requests"] == st["taken_requests"] == len(requests)
    assert st["flushes"] == st["landed_flushes"] == len(requests)
    assert st["classify_s"] == 0  # no regime split asked for
    assert st["pack_s"] > 0 and st["launch_s"] + st["compile_s"] > 0
    assert st["classify_s"] + st["pack_s"] + st["launch_s"] <= st["dispatch_s"]
    assert st["submit_s"] > 0 and st["queue_wait_s"] > 0 and st["land_s"] > 0
    want = [_cells(probs) for probs in requests]
    assert st["dp_band_cells"] == sum(b for b, _ in want)
    assert st["dp_computed_cells"] == sum(c for _, c in want)
    assert 0 < st["dp_band_cells"] < st["dp_computed_cells"]


def test_dp_rows_and_live_rows_per_flush():
    """Rows the class scan runs (``Bb * nb``) and the clients among them
    with work, summed over flushes of several buckets."""
    requests = _requests(35)
    rng = np.random.default_rng(36)
    upper = np.array([200, 50, 60, 70, 80])  # band 201: the W 256 bucket
    tables = [np.cumsum(rng.integers(0, 30, size=u + 1)).astype(float) for u in upper]
    wide = Problem(T=300, lower=np.zeros(5, np.int64), upper=upper, cost_tables=tables)
    requests.append([wide])
    svc = SchedulerService(engine=SweepEngine(), max_batch=4, max_delay_s=0.001)
    _serve_one_at_a_time(svc, requests)
    svc.close(timeout=60)
    st = svc.stats()
    rows = live = 0
    for probs in requests:
        rows += _pow2(len(probs)) * _pow2(max(p.n for p in probs))
        live += sum(int(np.count_nonzero(p.upper - p.lower)) for p in probs)
    assert (st["dp_rows"], st["dp_live_rows"]) == (rows, live)
    assert 0 < st["dp_live_rows"] < st["dp_rows"]


def test_classify_is_timed_only_on_the_regime_split_path():
    requests = _requests(32, regime="increasing")
    svc = SchedulerService(engine=SweepEngine(), max_batch=4, max_delay_s=0.001)
    _serve_one_at_a_time(svc, requests, split_regimes=True)
    svc.close(timeout=60)
    st = svc.stats()
    assert st["classify_s"] > 0
    assert st["classify_s"] + st["pack_s"] + st["launch_s"] <= st["dispatch_s"]
    # monotone instances ride the selection kernel: no DP cell is launched
    assert st["dp_band_cells"] == st["dp_computed_cells"] == 0
    assert st["flushes"] == st["landed_flushes"] == len(requests)


def _read_trace(trace_dir):
    """Host events named ``repro.*``: ``(thread, name, start, end, meta)``."""
    (path,) = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    out = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("repro."):
                    s = int(e.start_ns)
                    out.append((line.name, e.name, s, s + int(e.duration_ns), {k: v for k, v in e.stats}))
    return out


def _inside(inner, outer) -> bool:
    return inner[0] == outer[0] and outer[2] <= inner[2] and inner[3] <= outer[3]


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """Four requests over two buckets, served one at a time under a CPU
    profile by a cold engine, then again by the now warm one (the service's
    stats, and the trace's spans)."""
    trace_dir = tmp_path_factory.mktemp("trace")
    requests = _requests(33)
    svc = SchedulerService(engine=SweepEngine(), max_batch=4, max_delay_s=0.001, name="traced")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    try:
        _serve_one_at_a_time(svc, requests)
        _serve_one_at_a_time(svc, requests)
        svc.warm([(2, 10, 11)], batch_sizes=[1])  # a bucket of its own (n 2), cold
    finally:
        jax.profiler.stop_trace()
        svc.close(timeout=60)
    return svc.stats(), _read_trace(trace_dir)


def test_trace_holds_the_named_spans(traced):
    _, events = traced
    names = {name for _, name, *_ in events}
    for stage in ("submit", "pack", "idle", "flush", "take", "materialize", "demux", "warm"):
        assert f"repro.serve.{stage}" in names, stage
    for stage in ("dispatch", "pack", "launch", "compile"):
        assert f"repro.engine.{stage}" in names, stage
    flushes = [e for e in events if e[1] == "repro.serve.flush"]
    dispatches = [e for e in events if e[1] == "repro.engine.dispatch"]
    assert len(flushes) == 8
    assert len(dispatches) == 8 + 1  # the eight flushes and the warm-up
    for d in dispatches:
        if any(_inside(d, w) for w in events if w[1] == "repro.serve.warm"):
            continue
        assert sum(_inside(d, f) for f in flushes) == 1
    for f in flushes:
        assert {"flush", "rows", "requests", "trigger"} <= set(f[4])


def test_every_take_joins_its_request_and_its_flush(traced):
    _, events = traced
    submitted = {e[4]["request"] for e in events if e[1] == "repro.serve.submit"}
    flushes = [e for e in events if e[1] == "repro.serve.flush"]
    takes = [e for e in events if e[1] == "repro.serve.take"]
    assert len(submitted) == len(takes) == 8
    assert {t[4]["request"] for t in takes} == submitted
    for t in takes:
        (around,) = [f for f in flushes if _inside(t, f)]
        assert t[4]["flush"] == around[4]["flush"]
        assert t[4]["wait_us"] >= 0
    # the completer's spans name the same flushes
    for stage in ("repro.serve.materialize", "repro.serve.demux"):
        ids = {e[4]["flush"] for e in events if e[1] == stage}
        assert ids == {f[4]["flush"] for f in flushes}


def test_compile_once_per_new_bucket_never_on_a_warm_hit(traced):
    st, events = traced
    compiles = {e[4]["bucket"]: e[2] for e in events if e[1] == "repro.engine.compile"}
    launches = [e for e in events if e[1] == "repro.engine.launch"]
    # flushes of 1, 3, 2, 1 rows: buckets B1 and B4 at n 4, B2 and B1 at n 8;
    # then the warm-up's own bucket at n 2
    assert len(compiles) == sum(e[1] == "repro.engine.compile" for e in events) == 5
    # the second pass hits the four warm buckets, each compiled before
    assert len(launches) == 4
    for _, _, start, _, meta in launches:
        assert compiles[meta["bucket"]] < start
    assert st["compile_s"] > 0


def test_warm_hits_launch_without_compiling():
    """The same requests again on a warm engine: launches only."""
    requests = _requests(33)
    eng = SweepEngine()
    svc = SchedulerService(engine=eng, max_batch=4, max_delay_s=0.001)
    _serve_one_at_a_time(svc, requests)
    before = svc.stats()
    _serve_one_at_a_time(svc, requests)
    svc.close(timeout=60)
    after = svc.stats()
    assert after["compile_s"] == before["compile_s"]
    assert after["launch_s"] > before["launch_s"]


def test_a_single_problem_request_is_counted_like_a_batch():
    rng = np.random.default_rng(34)
    p = random_problem(rng, n=4, T=9)
    svc = SchedulerService(engine=SweepEngine(), max_batch=2, max_delay_s=0.001)
    svc.submit(p).result(timeout=60)
    svc.close(timeout=60)
    st = svc.stats()
    assert (st["dp_band_cells"], st["dp_computed_cells"]) == _cells([p])
    assert st["taken_requests"] == st["landed_flushes"] == 1


def test_counters_hold_under_concurrent_submitters():
    """Eight threads submitting at once with a short switch interval: every
    sum and count is updated under the service's lock, so none is lost."""
    rng = np.random.default_rng(35)
    probs = [random_problem(rng, n=3, T=8) for _ in range(64)]
    svc = SchedulerService(engine=SweepEngine(), max_batch=4, max_delay_s=0.001)
    svc.submit(probs[0]).result(timeout=60)  # compile outside the stress
    before = svc.stats()
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [
            threading.Thread(
                target=lambda ps: [f.result(timeout=60) for f in [svc.submit(p) for p in ps]],
                args=(probs[k::8],),
            )
            for k in range(8)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(old)
        svc.close(timeout=60)
    st = svc.stats()
    assert st["requests"] - before["requests"] == 64
    assert st["taken_requests"] == st["requests"] == st["completed_requests"]
    assert st["landed_flushes"] == st["flushes"]
    assert st["dp_band_cells"] - before["dp_band_cells"] == sum(_cells([p])[0] for p in probs)


def _table(rng, u: int):
    return np.cumsum(rng.integers(1, 30, size=u + 1)).astype(float)


def test_dp_run_cells_count_the_band_loops():
    """``dp_run_cells`` is zero on a new service; on the blocked backend it
    is every computed cell; on the Pallas kernel it counts each row's band
    steps, ``min(Wb, ceil((U' + 1) / 32) * 32)``, with padding rows at
    ``U' = 0``, times ``Tb + 1``, for a known run of two flushes."""
    rng = np.random.default_rng(37)
    # flush 1: U' 38, 5, 0 (lower limits 2, 0, 0) in the W 64 bucket, n 4,
    # T' 18 -> Tb 32: 33 * (64 + 32 + 32 + 32 for the padding row)
    one = Problem(
        T=20,
        lower=np.array([2, 0, 0]),
        upper=np.array([40, 5, 0]),
        cost_tables=[_table(rng, u) for u in (40, 5, 0)],
    )
    # flush 2: two instances of U' (99, 31) and (32, 10) in the W 128
    # bucket, Tb 64: 65 * (128 + 32 + 64 + 32)
    two = [
        Problem(
            T=40,
            lower=np.zeros(2, np.int64),
            upper=np.array(u),
            cost_tables=[_table(rng, v) for v in u],
        )
        for u in ((99, 31), (32, 10))
    ]
    want = 33 * (64 + 32 + 32 + 32) + 65 * (128 + 32 + 64 + 32)
    requests = [[one], two]
    for backend in ("blocked", "pallas"):
        svc = SchedulerService(engine=SweepEngine(backend=backend), max_batch=4, max_delay_s=0.001)
        assert svc.stats()["dp_run_cells"] == 0
        _serve_one_at_a_time(svc, requests)
        svc.close(timeout=60)
        st = svc.stats()
        assert st["flushes"] == 2
        assert st["dp_computed_cells"] == 1 * 4 * 33 * 64 + 2 * 2 * 65 * 128
        if backend == "blocked":
            assert st["dp_run_cells"] == st["dp_computed_cells"]
        else:
            assert st["dp_run_cells"] == want
            assert st["dp_band_cells"] < st["dp_run_cells"] < st["dp_computed_cells"]
