"""Scheduler-as-a-service (DESIGN.md §14): coalescing batcher over the engine.

Claims under test:
  * every coalesced request is BIT-IDENTICAL to solving it alone — mixed
    regimes, ragged shapes, nonzero lower limits, single-Problem and batch
    requests, plain and regime-split, including k_last/objectives demux;
  * the service actually batches (fewer flushes than requests) and a lone
    sub-max-batch request still flushes within ~max_delay;
  * bounded admission: a stuck engine backs producers up, times them out
    with :class:`ServiceOverloaded`, and serves everything on release;
  * close() drains in-flight requests, then refuses new ones;
  * warm() covers the pow2 ladder so served steady state performs zero
    fresh XLA traces; flushes never exceed max_batch rows (they'd leave
    the warmed ladder);
  * an FL campaign planning through the service matches the engine path.
"""

import threading
import time

import numpy as np
import pytest

from repro.core import Problem, ProblemBatch, SweepEngine, random_problem
from repro.core.sweep import request_bucket
from repro.serve import (
    SchedulerService,
    ServiceClosed,
    ServiceOverloaded,
    coalesce_key,
    combine_batches,
    pow2_ladder,
    warm_batch,
)

from _fused_ref import solve_unbucketed

REGIMES = ("arbitrary", "linear", "increasing", "decreasing")


def ragged_problems(rng, N, max_n=6, max_T=24, with_lower=True):
    return [
        random_problem(
            rng,
            n=int(rng.integers(1, max_n + 1)),
            T=int(rng.integers(1, max_T + 1)),
            regime=REGIMES[i % len(REGIMES)],
            with_lower=with_lower,
        )
        for i in range(N)
    ]


# ---------------------------------------------------------------------------
# coalesce primitives
# ---------------------------------------------------------------------------


def test_coalesce_key_matches_engine_bucket_math():
    rng = np.random.default_rng(0)
    for p in ragged_problems(rng, 6):
        b = ProblemBatch.from_problems([p])
        nb, Tb, Wb = request_bucket(b)
        assert coalesce_key(b, False) == (nb, Tb, Wb, False)
        assert coalesce_key(b, True) == (nb, Tb, Wb, True)
        for v in (nb, Tb, Wb):  # pow2 axes
            assert v & (v - 1) == 0 and v >= 1


def test_combine_batches_slices_and_padding_inert():
    rng = np.random.default_rng(1)
    groups = [ProblemBatch.from_problems(ragged_problems(rng, k)) for k in (1, 3, 2)]
    combined, slices = combine_batches(groups)
    assert combined.B == 6 and slices == [(0, 1), (1, 4), (4, 6)]
    X_all = solve_unbucketed(combined)
    for g, (lo, hi) in zip(groups, slices):
        np.testing.assert_array_equal(X_all[lo:hi, : g.n], solve_unbucketed(g))


def test_pow2_ladder_and_warm_batch():
    assert pow2_ladder(1) == [1]
    assert pow2_ladder(5) == [1, 2, 4, 8]
    assert pow2_ladder(16) == [1, 2, 4, 8, 16]
    wb = warm_batch(4, 12, 8, B=3, regime="arbitrary")
    wb.validate()
    assert wb.B == 3
    assert request_bucket(wb) == (4, 16, 8)  # lands in the spec's bucket
    mono = warm_batch(4, 12, 8, B=2, regime="increasing")
    assert request_bucket(mono) == (4, 16, 8)
    solve_unbucketed(wb)  # feasible by construction


# ---------------------------------------------------------------------------
# service: correctness of served results
# ---------------------------------------------------------------------------


def test_served_results_bit_identical_mixed_regimes_and_shapes():
    rng = np.random.default_rng(2)
    probs = ragged_problems(rng, 10)
    eng = SweepEngine()
    with SchedulerService(engine=eng, max_batch=4, max_delay_s=0.005) as svc:
        futs = [svc.submit(p) for p in probs]  # squeeze path
        multi = ProblemBatch.from_problems(probs[:3])
        f_multi = svc.submit(multi)
        f_split = [svc.submit(p, split_regimes=True) for p in probs[:4]]

        for p, f in zip(probs, futs):
            x = f.result(timeout=300)
            assert x.shape == (p.n,)
            np.testing.assert_array_equal(x, eng.solve([p])[0, : p.n])
        X_multi = f_multi.result(timeout=300)
        np.testing.assert_array_equal(
            X_multi[:, : multi.n], eng.solve(probs[:3])[:, : multi.n]
        )
        for p, f in zip(probs[:4], f_split):
            np.testing.assert_array_equal(
                f.result(timeout=300), eng.solve([p], split_regimes=True)[0, : p.n]
            )
    s = svc.stats()
    assert s["completed_requests"] == s["requests"] == 15
    assert s["flushes"] < s["requests"], "nothing coalesced"
    assert s["inflight_rows"] == 0 and s["pending_rows"] == 0


def test_future_demuxes_k_last_and_objectives():
    rng = np.random.default_rng(3)
    probs = ragged_problems(rng, 5, with_lower=False)
    eng = SweepEngine()
    with SchedulerService(engine=eng, max_batch=8, max_delay_s=0.005) as svc:
        futs = [svc.submit(p) for p in probs]
        # probs[1] is linear (monotone): under split_regimes it rides the
        # marginal path, whose handle has no free-T Pareto row
        f_split = svc.submit(probs[1], split_regimes=True)
        for p, f in zip(probs, futs):
            solo = eng.dispatch(ProblemBatch.from_problems([p]))
            np.testing.assert_array_equal(f.k_last(timeout=300), solo.k_last()[0])
            assert f.objectives() == pytest.approx(float(solo.objectives()[0]))
        # regime-split requests expose objectives but no free-T Pareto row,
        # exactly like the engine's split-dispatch handles
        assert f_split.objectives(timeout=300) == pytest.approx(
            float(eng.dispatch(ProblemBatch.from_problems([probs[1]]),
                               split_regimes=True).objectives()[0])
        )
        with pytest.raises(Exception):
            f_split.k_last()


def test_lone_request_flushes_on_max_delay():
    rng = np.random.default_rng(4)
    p = random_problem(rng, n=3, T=8, regime="linear")
    eng = SweepEngine()
    eng.solve([p])  # trace outside the timed window
    with SchedulerService(engine=eng, max_batch=64, max_delay_s=0.05) as svc:
        t0 = time.monotonic()
        x = svc.submit(p).result(timeout=300)
        waited = time.monotonic() - t0
    np.testing.assert_array_equal(x, eng.solve([p])[0, : p.n])
    assert waited >= 0.04, f"flushed before the max-delay window ({waited:.3f}s)"
    assert svc.stats()["delay_flushes"] == 1 and svc.stats()["size_flushes"] == 0


# ---------------------------------------------------------------------------
# backpressure + shutdown (stub engine: no XLA in the loop)
# ---------------------------------------------------------------------------


class _GatedHandle:
    def __init__(self, gate, B, n):
        self._gate, self._B, self._n = gate, B, n

    def result(self):
        assert self._gate.wait(timeout=60), "test gate never opened"
        return np.zeros((self._B, self._n), dtype=np.int64)

    def objectives(self):
        return np.zeros(self._B)

    def k_last(self):
        return np.zeros((self._B, 1), dtype=np.int64)


class _GatedEngine:
    """Engine stand-in whose solves block until the test opens the gate."""

    def __init__(self):
        self.gate = threading.Event()
        self.dispatched_rows = []

    def dispatch(self, batch, split_regimes=False):
        self.dispatched_rows.append(batch.B)
        return _GatedHandle(self.gate, batch.B, batch.n)


def _tiny(rng):
    return random_problem(rng, n=2, T=4, regime="linear")


def test_backpressure_blocks_then_rejects_then_drains():
    rng = np.random.default_rng(5)
    eng = _GatedEngine()
    svc = SchedulerService(engine=eng, max_batch=2, max_delay_s=0.001, max_pending=4)
    try:
        held = [svc.submit(_tiny(rng)) for _ in range(4)]  # fills the bound
        deadline = time.monotonic() + 30  # flushed (inflight) but unfinished
        while svc.stats()["flushes"] < 2 and time.monotonic() < deadline:
            time.sleep(0.002)
        with pytest.raises(ServiceOverloaded):
            svc.submit(_tiny(rng), timeout=0.05)
        assert svc.stats()["rejected"] == 1

        # a submitter ALREADY blocked on admission gets served on release
        late = {}
        t = threading.Thread(
            target=lambda: late.__setitem__("f", svc.submit(_tiny(rng), timeout=30))
        )
        t.start()
        time.sleep(0.05)
        assert "f" not in late  # still blocked: bound is honest
        eng.gate.set()
        t.join(timeout=30)
        assert not t.is_alive()
        for f in held + [late["f"]]:
            assert f.result(timeout=30).shape == (2,)
    finally:
        eng.gate.set()
        svc.close()


def test_flushes_never_exceed_max_batch_rows():
    """Rows arriving while a bucket is ripe must NOT grow a flush past
    max_batch — an overflow would leave the warmed pow2-B ladder and pay a
    cold trace in steady state (the bench gates this end-to-end)."""
    rng = np.random.default_rng(6)
    eng = _GatedEngine()
    eng.gate.set()
    with SchedulerService(engine=eng, max_batch=4, max_delay_s=0.5, max_pending=512) as svc:
        futs = [svc.submit(_tiny(rng)) for _ in range(37)]
        for f in futs:
            f.result(timeout=60)
    assert max(eng.dispatched_rows) <= 4
    assert sum(eng.dispatched_rows) == 37


def test_close_serves_in_flight_then_refuses():
    rng = np.random.default_rng(7)
    eng = _GatedEngine()
    svc = SchedulerService(engine=eng, max_batch=64, max_delay_s=30.0, max_pending=512)
    futs = [svc.submit(_tiny(rng)) for _ in range(5)]  # parked: no trigger ripe
    assert not any(f.done() for f in futs)
    eng.gate.set()
    svc.close(timeout=60)  # close must flush + serve them, then stop
    for f in futs:
        assert f.result(timeout=1).shape == (2,)
    s = svc.stats()
    assert s["close_flushes"] >= 1 and s["completed_requests"] == 5
    with pytest.raises(ServiceClosed):
        svc.submit(_tiny(rng))
    svc.close()  # idempotent


def test_engine_failure_propagates_to_futures():
    class _BoomEngine:
        def dispatch(self, batch, split_regimes=False):
            raise RuntimeError("boom")

    rng = np.random.default_rng(8)
    with SchedulerService(engine=_BoomEngine(), max_batch=2, max_delay_s=0.001) as svc:
        f = svc.submit(_tiny(rng))
        with pytest.raises(RuntimeError, match="boom"):
            f.result(timeout=30)
    assert svc.stats()["inflight_rows"] == 0  # failed rows retire too


# ---------------------------------------------------------------------------
# warm(): steady state pays zero cold traces
# ---------------------------------------------------------------------------


def test_warm_covers_steady_state_zero_traces():
    rng = np.random.default_rng(9)
    probs = [random_problem(rng, n=3, T=11, regime=REGIMES[i % 4], with_lower=False)
             for i in range(12)]
    batches = [ProblemBatch.from_problems([p]) for p in probs]
    buckets = sorted(set(request_bucket(b) for b in batches))

    eng = SweepEngine()
    with SchedulerService(engine=eng, max_batch=4, max_delay_s=0.002) as svc:
        traced = svc.warm(buckets)
        assert traced > 0  # cold cache: the ladder really traced
        assert svc.warm(buckets) == 0  # idempotent: everything warm
        before = eng.cache_stats()["compiles"]
        futs = [svc.submit(b) for b in batches]
        for b, f in zip(batches, futs):
            np.testing.assert_array_equal(f.result(timeout=300), eng.dispatch(b).result())
        assert eng.cache_stats()["compiles"] == before, "steady state paid a cold trace"
    per_bucket = eng.cache_stats()["per_bucket_hits"]
    assert sum(per_bucket.values()) > 0 and all(":T16:" in k for k in per_bucket)


def test_warm_refuses_plans_larger_than_the_lru():
    """Warming more executables than the engine LRU holds would evict the
    oldest warm entries and steady state would pay cold traces anyway —
    warm() must refuse up front instead of silently thrashing."""
    eng = SweepEngine(max_entries=4)
    with SchedulerService(engine=eng, max_batch=4) as svc:  # ladder [1,2,4]
        with pytest.raises(ValueError, match="max_entries"):
            svc.warm([(2, 8, 8), (4, 16, 16)])  # 2 specs x 3 sizes = 6 > 4
        svc.warm([(2, 8, 8)])  # 3 executables: fits
    assert eng.cache_stats()["compiles"] == 3


# ---------------------------------------------------------------------------
# FL campaign planning through the service
# ---------------------------------------------------------------------------


def test_campaign_scenarios_via_service_match_engine_path():
    from repro.fl import EnergyEstimator, FederatedServer, make_fleet

    rng = np.random.default_rng(10)
    fleet = make_fleet(rng, 4, max_batches=6)
    est = EnergyEstimator(fleet)
    est.calibrate(rng)
    cap = sum(d.max_batches for d in fleet)

    def mk_server(**kw):
        return FederatedServer(
            None, None, None, est,
            round_T=cap // 2, scenario_T_candidates=[cap // 3, cap // 2],
            scenario_dropouts=[(0,), (1,)], **kw,
        )

    srv = mk_server(engine=SweepEngine())
    direct = srv.solve_scenarios(*srv.build_scenarios(cap // 2))
    with SchedulerService(engine=SweepEngine(), max_batch=8, max_delay_s=0.005) as svc:
        srv2 = mk_server(service=svc)
        assert srv2.engine is svc.engine  # service's engine becomes the default
        served = srv2.solve_scenarios(*srv2.build_scenarios(cap // 2))
    np.testing.assert_array_equal(direct.assignments, served.assignments)
    np.testing.assert_array_equal(direct.energies, served.energies)
    assert svc.stats()["requests"] == 1 and svc.stats()["flushes"] == 1
