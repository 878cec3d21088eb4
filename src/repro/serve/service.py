"""Scheduler-as-a-service: coalescing request batcher over the sweep engine.

The engine (DESIGN.md §10–§13) already looks like an inference server —
shape-bucketed compile cache, non-blocking ``dispatch()``, regime-split
routing. :class:`SchedulerService` finishes the job for heavy served
traffic (ROADMAP: "scheduler-as-a-service"): a persistent front-end that
admits a stream of heterogeneous :class:`~repro.core.problem.Problem` /
:class:`~repro.core.problem.ProblemBatch` requests and serves each from a
COALESCED dispatch instead of one kernel launch per request.

Pipeline (DESIGN.md §14)::

    submit() ──▶ admission (bounded, backpressure)
             ──▶ coalescer thread: group by pow2 bucket key, flush a bucket
                 as ONE SweepEngine.dispatch() on a max-batch or max-delay
                 trigger
             ──▶ completer thread: materialize the batched handle, demux
                 per-request rows into ScheduleFuture results

  * **Admission** is bounded by ``max_pending`` rows admitted-but-not-yet-
    completed: overload blocks producers (or raises
    :class:`ServiceOverloaded` past their timeout) — latency degrades,
    memory does not.
  * **Coalescing** groups requests by :func:`~repro.serve.coalesce.
    coalesce_key` — the engine's own bucket math — so merging requests
    never changes which executable solves them, and results stay
    bit-identical to solving each request alone (inert padding).
  * **Warmup**: :meth:`SchedulerService.warm` pre-traces the hot buckets
    over the whole pow2 batch-size ladder, so steady-state traffic never
    hits a cold XLA trace no matter which trigger fires a flush.
  * **Demux**: each :class:`ScheduleFuture` slices its rows (and, for
    pure-DP flushes, ``k_last``/``objectives``) out of the shared batched
    handle; handle materialization is thread-safe (lock-guarded in
    ``core/sweep.py``), so many requesters can drain one flush at once.
  * **Observability**: each request and each flush gets an integer id, and
    every stage above is a named span (``repro.serve.*``, and the engine's
    ``repro.engine.*`` inside each flush) carrying the ids as ``request=`` /
    ``flush=`` metadata, recorded on the profiler's clock only while a
    profile runs; :meth:`SchedulerService.stats` sums the stages' host time
    whether or not one does (DESIGN.md §14, "Observability").
"""

from __future__ import annotations

import itertools
import queue
import threading
import time
from typing import Optional

import numpy as np
from jax.profiler import TraceAnnotation

from ..core.pareto import assemble_frontier, candidate_deadlines, tightened_instances
from ..core.problem import Problem, ProblemBatch, total_cost
from ..core.resilience import CircuitBreaker, RetryPolicy, is_transient
from ..core.scheduler import _schedule
from ..core.sweep import DISPATCH_PHASES, SweepEngine, _next_pow2, default_engine
from .coalesce import coalesce_key, combine_batches, pow2_ladder, warm_batch

__all__ = [
    "FleetFuture",
    "FrontierFuture",
    "ScheduleFuture",
    "SchedulerService",
    "ServiceClosed",
    "ServiceOverloaded",
]


class ServiceClosed(RuntimeError):
    """Raised by :meth:`SchedulerService.submit` after :meth:`close`."""


class ServiceOverloaded(RuntimeError):
    """The bounded admission queue stayed full past the submit timeout."""


class ScheduleFuture:
    """Per-request handle to an in-flight (possibly coalesced) solve.

    :meth:`result` blocks until the request's flush materialized and
    returns this request's schedule rows — ``(B, n)`` int64 for batch
    requests, ``(n,)`` for a single-:class:`Problem` submission —
    bit-identical to solving the request alone. :meth:`objectives` and
    (for ``split_regimes=False`` requests) :meth:`k_last` demux the same
    per-request views out of the batched handle with no extra dispatch.

    ``submitted_at`` / ``completed_at`` are the service's ``time.monotonic()``
    stamps of admission and of the completer landing the flush: the same
    stamps its ``queue_wait_s`` and ``land_s`` counters are measured from.
    """

    def __init__(self, rows: int, n: int, squeeze: bool):
        self._rows = rows
        self._n = n
        self._squeeze = squeeze
        self._event = threading.Event()
        self._X: Optional[np.ndarray] = None
        self._handle = None  # the flush's SweepHandle / RegimeSplitHandle
        self._lo = self._hi = 0  # this request's rows in the flushed batch
        self._exc: Optional[BaseException] = None
        self.submitted_at: Optional[float] = None
        self.completed_at: Optional[float] = None

    def done(self) -> bool:
        return self._event.is_set()

    def _resolve(self, X: np.ndarray, handle, lo: int, hi: int, t_done: float) -> None:
        self._X, self._handle, self._lo, self._hi = X, handle, lo, hi
        self.completed_at = t_done
        self._event.set()

    def _fail(self, exc: BaseException) -> None:
        self._exc = exc
        self.completed_at = time.monotonic()
        self._event.set()

    def _wait(self, timeout: Optional[float]):
        if not self._event.wait(timeout):
            raise TimeoutError(f"request not served within {timeout}s")
        if self._exc is not None:
            raise self._exc

    def result(self, timeout: Optional[float] = None) -> np.ndarray:
        """This request's schedule(s); blocks until served."""
        self._wait(timeout)
        return self._X[0] if self._squeeze else self._X

    def objectives(self, timeout: Optional[float] = None):
        """Per-instance 0-lower-limit objectives (float for a single-Problem
        request), demuxed from the batched handle — same convention as
        :meth:`repro.core.sweep.SweepHandle.objectives`."""
        self._wait(timeout)
        obj = np.asarray(self._handle.objectives(), np.float64)[self._lo : self._hi]
        return float(obj[0]) if self._squeeze else obj

    def k_last(self, timeout: Optional[float] = None) -> np.ndarray:
        """This request's final DP row(s) — the free workload-Pareto curve.
        Only defined for ``split_regimes=False`` (pure-DP) requests; the
        regime-split handle raises, exactly as engine callers see."""
        self._wait(timeout)
        k = self._handle.k_last()[self._lo : self._hi]
        return k[0] if self._squeeze else k


class FrontierFuture:
    """A served Pareto-frontier request (PR 7, DESIGN.md §15): wraps the
    underlying ε-constraint sweep's :class:`ScheduleFuture` and assembles
    the pruned :class:`~repro.core.pareto.ParetoFrontier` on :meth:`result`.
    The sweep itself is ONE coalescable request — every tightened instance
    shares the base problem's bucket, so frontier traffic merges with any
    other same-bucket traffic exactly like plain solves do."""

    def __init__(self, future: ScheduleFuture, problem, time_tables, deadlines):
        self._future = future
        self._problem = problem
        self._time_tables = time_tables
        self._deadlines = deadlines
        self._frontier = None

    def done(self) -> bool:
        return self._future.done()

    @property
    def submitted_at(self):
        return self._future.submitted_at

    @property
    def completed_at(self):
        return self._future.completed_at

    def result(self, timeout: Optional[float] = None):
        """The :class:`~repro.core.pareto.ParetoFrontier`; blocks until the
        sweep is served. Repeated calls return the same object."""
        if self._frontier is None:
            X = self._future.result(timeout)
            self._frontier = assemble_frontier(
                self._problem, self._time_tables, self._deadlines, X
            )
        return self._frontier


class FleetFuture:
    """A served two-level fleet solve (PR 8, DESIGN.md §16): wraps a
    :class:`~repro.core.fleet.FleetRun` whose stage-1 curve dispatch was
    admitted as ONE coalescable request at submit time. :meth:`result` runs
    the remaining stages — the top-level allocation and the per-cluster
    schedule batch also go through the service, merging with any same-bucket
    traffic — and returns the :class:`~repro.core.fleet.FleetSolution`.
    Repeated calls return the same object."""

    def __init__(self, run):
        self._run = run

    def done(self) -> bool:
        """True once the stage-1 curve request has been served (the
        remaining stages are small and run inside :meth:`result`)."""
        return self._run.done()

    def result(self, timeout: Optional[float] = None):
        """The :class:`~repro.core.fleet.FleetSolution`; ``timeout`` is a
        real deadline enforced across ALL remaining staged solves (each
        staged served request gets the budget left on the clock), raising
        :class:`TimeoutError` exactly like :meth:`ScheduleFuture.result`.
        A timed-out call may be retried — later stages re-run from the
        memoized stage-1 curves, and a completed solve is cached."""
        return self._run.finish(timeout=timeout)


class _DegradedHandle:
    """Stand-in flush handle for the circuit breaker's degraded direct-solve
    path (DESIGN.md §17): schedules were host-solved — bit-identical to the
    engine path — so ``result()``/``objectives()`` demux normally; only
    ``k_last()`` is unavailable (no fused-DP dispatch ran), and raises with
    the same flavor of error as a regime-split handle."""

    def __init__(self, X: np.ndarray, objectives: np.ndarray):
        self._X = X
        self._obj = objectives

    def done(self) -> bool:
        return True

    def result(self) -> np.ndarray:
        return self._X

    def objectives(self) -> np.ndarray:
        return self._obj

    def k_last(self) -> np.ndarray:
        raise ValueError(
            "k_last() is unavailable: this flush was served by the degraded "
            "direct-solve path (circuit breaker open) — no fused-DP row "
            "exists. Retry once the breaker closes, or solve directly "
            "against a healthy engine."
        )


class _Request:
    __slots__ = ("rid", "batch", "future", "t_submit")

    def __init__(self, rid: int, batch: ProblemBatch, future: ScheduleFuture, t_submit: float):
        self.rid = rid  # the request's id in its spans
        self.batch = batch
        self.future = future
        self.t_submit = t_submit  # admission: the future's submitted_at


class _Flush:
    """One coalesced dispatch, from the coalescer taking its requests to the
    completer landing it."""

    __slots__ = ("fid", "reqs", "split", "trigger", "t_take", "combined", "slices", "t_launched")

    def __init__(self, fid: int, reqs, split: bool, trigger: str, t_take: float):
        self.fid = fid  # the flush's id in its spans
        self.reqs = reqs
        self.split = split
        self.trigger = trigger
        self.t_take = t_take
        self.combined = self.slices = None
        self.t_launched = None  # the engine dispatch returned (None: degraded)


class SchedulerService:
    """Persistent coalescing front-end over one :class:`SweepEngine`.

    Args:
      engine: the engine all flushes dispatch through (``None``: the
        process-wide default — sharing it means FL campaign planning and
        external traffic warm ONE cache).
      max_batch: rows that trigger an immediate bucket flush. Requests are
        atomic (never split), so a flush can exceed this by the last
        request's rows.
      max_delay_s: oldest-request age that triggers a flush even when the
        bucket is not full — the latency bound under light traffic.
      max_pending: admission bound, in rows admitted but not yet completed.
        Full ⇒ ``submit`` blocks (backpressure); past its ``timeout`` ⇒
        :class:`ServiceOverloaded`. An oversize request (> ``max_pending``
        rows) is admitted only once the service is drained, alone.
      name: thread-name prefix (observability).
      retry: a :class:`~repro.core.resilience.RetryPolicy` — flushes whose
        engine dispatch/materialization raises a TRANSIENT error
        (:func:`~repro.core.resilience.is_transient`) are re-dispatched with
        exponential backoff + deterministic jitter. Non-transient errors
        always propagate to the affected futures unchanged.
      breaker: a :class:`~repro.core.resilience.CircuitBreaker` — after K
        consecutive engine failures the breaker opens and flushes are served
        by the DEGRADED direct-solve path (host algorithms, bit-identical
        schedules, no ``k_last``) instead of hammering the engine, until a
        half-open probe succeeds. With a breaker configured, transient
        failures that exhaust their retries also degrade rather than fail.
    """

    def __init__(
        self,
        engine: Optional[SweepEngine] = None,
        max_batch: int = 32,
        max_delay_s: float = 0.002,
        max_pending: int = 1024,
        name: str = "sched-serve",
        retry: Optional[RetryPolicy] = None,
        breaker: Optional[CircuitBreaker] = None,
    ):
        if max_batch < 1 or max_pending < 1:
            raise ValueError("max_batch and max_pending must be >= 1")
        self.engine = engine if engine is not None else default_engine()
        self.max_batch = int(max_batch)
        self.max_delay_s = float(max_delay_s)
        self.max_pending = int(max_pending)
        self.retry = retry
        self.breaker = breaker
        self._retry_rng = retry.make_rng() if retry is not None else None
        self._cond = threading.Condition()
        self._pending: dict = {}  # coalesce key -> [_Request]
        self._pending_rows = 0  # admitted, not yet flushed
        self._inflight_rows = 0  # admitted, not yet completed (the bound)
        self._closed = False
        self._request_ids = itertools.count()
        self._flush_ids = itertools.count()
        self._stats = {
            "requests": 0,
            "rows": 0,
            "completed_requests": 0,
            "flushes": 0,
            "flushed_rows": 0,
            "size_flushes": 0,
            "delay_flushes": 0,
            "close_flushes": 0,
            "rejected": 0,
            "warmed_executables": 0,
            "retries": 0,
            "flush_failures": 0,
            "degraded_flushes": 0,
            "degraded_rows": 0,
            "submit_s": 0.0,
            "queue_wait_s": 0.0,
            "taken_requests": 0,
            "land_s": 0.0,
            "landed_flushes": 0,
            **dict.fromkeys(DISPATCH_PHASES, 0),
        }
        self._done_q: queue.SimpleQueue = queue.SimpleQueue()
        self._coalescer = threading.Thread(
            target=self._coalesce_loop, name=f"{name}-coalescer", daemon=True
        )
        self._completer = threading.Thread(
            target=self._complete_loop, name=f"{name}-completer", daemon=True
        )
        self._coalescer.start()
        self._completer.start()

    # ---- client API ----------------------------------------------------

    def submit(
        self,
        problems,
        split_regimes: bool = False,
        timeout: Optional[float] = None,
    ) -> ScheduleFuture:
        """Admits one request — a single :class:`Problem`, a sequence of
        them, or a prebuilt :class:`ProblemBatch` — and returns its
        :class:`ScheduleFuture`. ``split_regimes`` selects the regime-split
        solve path (DESIGN.md §13) and is part of the coalescing key: split
        and plain requests never share a flush. Blocks while the admission
        bound is full; ``timeout`` seconds later raises
        :class:`ServiceOverloaded` instead.
        """
        t0 = time.monotonic()
        rid = next(self._request_ids)
        with TraceAnnotation("repro.serve.submit", request=rid):
            with TraceAnnotation("repro.serve.pack", request=rid):
                squeeze = isinstance(problems, Problem)
                if squeeze:
                    batch = ProblemBatch.from_problems([problems])
                elif isinstance(problems, ProblemBatch):
                    batch = problems
                else:
                    batch = ProblemBatch.from_problems(problems)
                batch.validate()
                key = coalesce_key(batch, split_regimes)  # cheap numpy, outside the lock
                future = ScheduleFuture(batch.B, batch.n, squeeze)
            with self._cond:
                if not (self._closed or self._has_room(batch.B)):
                    with TraceAnnotation("repro.serve.admit", request=rid):
                        self._wait_for_room(batch.B, timeout)
                if self._closed:
                    raise ServiceClosed("submit() after close()")
                t_now = time.monotonic()
                future.submitted_at = t_now
                was_idle = not self._pending
                bucket = self._pending.setdefault(key, [])
                bucket.append(_Request(rid, batch, future, t_now))
                self._pending_rows += batch.B
                self._inflight_rows += batch.B
                self._stats["requests"] += 1
                self._stats["rows"] += batch.B
                # Wake the coalescer only when this submit changes its
                # schedule: a new deadline (queue was idle) or a size-ripe
                # bucket. A later arrival never shortens an existing delay
                # deadline, so skipping the notify here avoids a context
                # switch per request on the saturated path (the coalescer
                # wakes on its own timer).
                if was_idle or sum(r.batch.B for r in bucket) >= self.max_batch:
                    self._cond.notify_all()
                self._stats["submit_s"] += time.monotonic() - t0
        return future

    def _has_room(self, rows: int) -> bool:
        return (
            self._inflight_rows + rows <= self.max_pending
            or self._inflight_rows == 0  # oversize request, alone
        )

    def _wait_for_room(self, rows: int, timeout: Optional[float]) -> None:
        """Blocks (lock held) until admission has room for ``rows`` or the
        service closes; raises :class:`ServiceOverloaded` past ``timeout``."""
        deadline = None if timeout is None else time.monotonic() + timeout
        while not (self._closed or self._has_room(rows)):
            remaining = None if deadline is None else deadline - time.monotonic()
            if remaining is not None and remaining <= 0:
                self._stats["rejected"] += 1
                raise ServiceOverloaded(
                    f"admission queue full ({self._inflight_rows}/"
                    f"{self.max_pending} rows in flight) past timeout"
                )
            self._cond.wait(remaining)

    def submit_frontier(
        self,
        problem: Problem,
        time_tables,
        deadlines=None,
        split_regimes: bool = True,
        timeout: Optional[float] = None,
    ) -> FrontierFuture:
        """Admits a Pareto-frontier request: the ε-constraint sweep of
        ``problem`` over ``deadlines`` (``None``: the exact candidate set —
        :func:`~repro.core.pareto.candidate_deadlines`) as ONE coalescable
        request. Returns a :class:`FrontierFuture` whose ``result()`` is the
        pruned :class:`~repro.core.pareto.ParetoFrontier`. Same admission /
        backpressure semantics as :meth:`submit`."""
        if deadlines is None:
            deadlines = candidate_deadlines(problem, time_tables)
        deadlines = np.asarray(list(deadlines), dtype=np.float64)
        tight = tightened_instances(problem, time_tables, deadlines)
        future = self.submit(tight, split_regimes=split_regimes, timeout=timeout)
        return FrontierFuture(future, problem, time_tables, deadlines)

    def submit_fleet(
        self,
        problem: Problem,
        *,
        clusters=None,
        quantum: Optional[int] = None,
        seed: int = 0,
        time_tables=None,
        check: bool = True,
    ) -> FleetFuture:
        """Admits a two-level fleet solve (DESIGN.md §16): clusters the
        clients on the calling thread (deterministic k-means), submits the
        per-cluster curve batch as ONE coalescable request, and returns a
        :class:`FleetFuture`. The top-level allocation and per-cluster
        schedule stages run through the service too when ``result()`` is
        called. Same knobs as
        :meth:`repro.core.solver.Solver.solve_fleet`."""
        from ..core.fleet import FleetRun  # lazy: fleet sits above the engine

        return FleetFuture(
            FleetRun(
                problem,
                service=self,
                clusters=clusters,
                quantum=quantum,
                seed=seed,
                time_tables=time_tables,
                check=check,
            )
        )

    def warm(self, specs, batch_sizes=None, split_regimes: bool = False) -> int:
        """Ahead-of-time traces the executables that traffic of the given
        shapes will hit, so steady-state serving never pays a cold XLA
        trace.

        ``specs``: iterable of ``(n, T, W)`` shapes — actual request shapes
        (``T`` in 0-lower-limit terms, i.e. ``T - sum(L)``) or bucket axes
        straight from :func:`~repro.core.sweep.request_bucket`; both round
        to the same buckets. ``batch_sizes`` defaults to the full pow2
        ladder up to ``max_batch`` (:func:`~repro.serve.coalesce.
        pow2_ladder`), covering every batch bucket a size- OR delay-
        triggered flush can produce. With ``split_regimes=True`` each spec
        additionally warms the ``("marginal", ...)`` selection bucket
        (best-effort: a mixed-regime flush splits into sub-batches of
        data-dependent size, so only full-batch buckets are guaranteed).

        Returns the number of fresh XLA tracings performed (0 = everything
        was already warm). Runs synchronously on the caller's thread,
        directly against the engine — intended before opening the doors.

        Raises ``ValueError`` when the warm plan holds more executables
        than the engine's LRU (``max_entries``): warming past capacity
        would silently evict the oldest warm entries and steady-state
        traffic would pay cold traces anyway — construct the engine with a
        larger ``max_entries`` (or warm fewer buckets) instead.
        """
        sizes = list(batch_sizes) if batch_sizes is not None else pow2_ladder(self.max_batch)
        specs = [tuple(int(v) for v in spec) for spec in specs]
        planned = {
            ("dp", _next_pow2(B), _next_pow2(n), _next_pow2(T), _next_pow2(W))
            for n, T, W in specs
            for B in sizes
        }
        if split_regimes:
            planned |= {
                ("marginal", _next_pow2(B), _next_pow2(n), _next_pow2(W))
                for n, _T, W in specs
                for B in sizes
            }
        if len(planned) > self.engine.max_entries:
            raise ValueError(
                f"warm plan needs {len(planned)} executables but the engine LRU "
                f"holds max_entries={self.engine.max_entries} — the oldest warm "
                f"entries would be evicted before serving. Use "
                f"SweepEngine(max_entries>={len(planned)}) or warm fewer buckets."
            )
        def solve(batch, split: bool) -> float:  # its compile seconds
            handle = self.engine.dispatch(batch, split_regimes=split)
            handle.result()
            return _phases(handle).get("compile_s", 0.0)

        before = self.engine.cache_stats()["compiles"]
        compile_s = 0.0
        with TraceAnnotation("repro.serve.warm"):
            for n, T, W in specs:
                for B in sizes:
                    compile_s += solve(warm_batch(n, T, W, B, regime="arbitrary"), split_regimes)
                    if split_regimes:
                        compile_s += solve(warm_batch(n, T, W, B, regime="increasing"), True)
        traced = self.engine.cache_stats()["compiles"] - before
        with self._cond:
            self._stats["warmed_executables"] += traced
            self._stats["compile_s"] += compile_s
        return traced

    def stats(self) -> dict:
        """Service counters plus live queue depths (rows).

        Counts: ``requests`` / ``rows`` admitted, ``completed_requests``,
        ``flushes`` (engine dispatches, retries included) and
        ``flushed_rows``, flushes by trigger (``size_flushes``,
        ``delay_flushes``, ``close_flushes``), ``rejected`` submits,
        ``warmed_executables``, and the failure paths' ``retries``,
        ``flush_failures``, ``degraded_flushes``, ``degraded_rows``.

        Host time, in seconds summed over events, each with the count that
        divides it into a mean (``time.monotonic`` / ``perf_counter``;
        always on, a few stamps per request):

        * ``submit_s`` / ``requests``: the whole :meth:`submit` call,
          admission waits included;
        * ``queue_wait_s`` / ``taken_requests``: admission to the coalescer
          taking the request into a flush (the ``max_delay_s`` hold and any
          wait behind the coalescer);
        * ``dispatch_s`` / ``flushes``: the engine call, split into
          ``classify_s`` (regime split, ``split_regimes`` flushes only),
          ``pack_s`` (validation, padding, packing, small input arrays) and
          ``launch_s`` (the executable's enqueue); see
          :meth:`~repro.core.sweep.SweepEngine.dispatch`;
        * ``land_s`` / ``landed_flushes``: the engine call returning to the
          flush's futures resolved (the wait behind earlier device work, the
          device time, the transfer back and the demux);
        * ``compile_s``: this service's dispatches, :meth:`warm` included,
          that traced and compiled (the engine's own total is
          ``cache_stats()["compile_s"]``).

        DP padding, as cell counts over this service's flushes:
        ``dp_band_cells`` is the min-plus work the instances need,
        ``(T' + 1) * (U_i - L_i + 1)`` summed over real rows and clients,
        and ``dp_computed_cells`` what the DP executables compute over their
        buckets, ``Bb * nb * (Tb + 1) * Wb`` per flush; their ratio is the
        share of the kernel's cells that is not padding. ``dp_run_cells``
        is the cells the kernel's band loops run: on the Pallas TPU kernel
        each row's loop ends at its band ``U' + 1`` rounded up to the
        kernel's unroll, elsewhere it is ``dp_computed_cells``. By rows:
        ``dp_rows`` is the rows the class scan runs, ``Bb * nb`` per flush,
        and ``dp_live_rows`` the real clients among them with work to place.

        The same stages are spans on the profiler's clock while a profile
        runs: ``repro.serve.submit`` (its ``pack``, and ``admit`` where
        admission blocks), ``idle`` / ``hold`` on the coalescer,
        ``flush`` with one ``take`` per request and the engine's
        ``repro.engine.*`` spans inside, ``materialize`` / ``demux`` on the
        completer, ``recover`` / ``degraded`` on the failure paths and
        ``warm``, each with its ``request=`` / ``flush=`` ids."""
        with self._cond:
            out = dict(self._stats)
            out["pending_rows"] = self._pending_rows
            out["inflight_rows"] = self._inflight_rows
            out["mean_flush_rows"] = (
                out["flushed_rows"] / out["flushes"] if out["flushes"] else 0.0
            )
        if self.breaker is not None:
            out["breaker"] = self.breaker.stats()
        return out

    def close(self, timeout: Optional[float] = None) -> None:
        """Clean shutdown: flush everything pending, serve every in-flight
        request, then stop both threads. Idempotent; later submits raise
        :class:`ServiceClosed`."""
        with self._cond:
            self._closed = True
            self._cond.notify_all()
        self._coalescer.join(timeout)
        self._completer.join(timeout)

    def __enter__(self) -> "SchedulerService":
        return self

    def __exit__(self, *exc_info) -> None:
        self.close()

    # ---- coalescer thread ----------------------------------------------

    def _ripe(self, key, reqs, now: float) -> Optional[str]:
        """The flush trigger a bucket has hit, if any."""
        if sum(r.batch.B for r in reqs) >= self.max_batch:
            return "size"
        if now - reqs[0].t_submit >= self.max_delay_s:
            return "delay"
        return None

    def _coalesce_loop(self) -> None:
        while True:
            flushes = []
            with self._cond:
                while not self._closed:
                    now = time.monotonic()
                    if any(self._ripe(k, rs, now) for k, rs in self._pending.items()):
                        break
                    if self._pending:
                        oldest = min(rs[0].t_submit for rs in self._pending.values())
                        with TraceAnnotation("repro.serve.hold"):
                            self._cond.wait(max(oldest + self.max_delay_s - now, 0.0))
                    else:
                        with TraceAnnotation("repro.serve.idle"):
                            self._cond.wait()
                now = time.monotonic()
                for key in list(self._pending):
                    trigger = (
                        "close" if self._closed else self._ripe(key, self._pending[key], now)
                    )
                    if trigger is None:
                        continue
                    # Cap a flush at max_batch rows (requests stay atomic):
                    # rows that arrived since the bucket went ripe stay
                    # pending, so the flushed batch-axis bucket never
                    # exceeds the pow2 ladder warm() pre-traced. A single
                    # oversize request still flushes alone. When closing,
                    # drain the bucket in capped chunks too.
                    while self._pending.get(key):
                        queued = self._pending[key]
                        take, rows = [], 0
                        for r in queued:
                            if take and rows + r.batch.B > self.max_batch:
                                break
                            take.append(r)
                            rows += r.batch.B
                        if len(take) == len(queued):
                            self._pending.pop(key)
                        else:
                            self._pending[key] = queued[len(take) :]
                        self._pending_rows -= rows
                        self._stats[f"{trigger}_flushes"] += 1
                        self._stats["queue_wait_s"] += sum(now - r.t_submit for r in take)
                        self._stats["taken_requests"] += len(take)
                        flushes.append(
                            _Flush(next(self._flush_ids), take, key[3], trigger, now)
                        )
                        if not self._closed:
                            break
                drained = self._closed and not self._pending
                self._cond.notify_all()
            for fl in flushes:
                self._flush(fl)
            if drained:
                self._done_q.put(None)  # completer: nothing further is coming
                return

    def _flush(self, fl: _Flush) -> None:
        """ONE engine dispatch for a ripe bucket (async — the executable is
        launched, not materialized), handed to the completer. Failure
        handling (retry / breaker / degraded solve) runs on the completer
        thread so the coalescer's flush cadence never blocks on backoff."""
        with TraceAnnotation(
            "repro.serve.flush",
            flush=fl.fid,
            rows=sum(r.batch.B for r in fl.reqs),
            requests=len(fl.reqs),
            trigger=fl.trigger,
        ):
            for r in fl.reqs:
                wait_us = int((fl.t_take - r.t_submit) * 1e6)
                with TraceAnnotation("repro.serve.take", request=r.rid, flush=fl.fid, wait_us=wait_us):
                    pass
            fl.combined, fl.slices = combine_batches([r.batch for r in fl.reqs])
            if self.breaker is not None and not self.breaker.allow():
                # breaker open: route straight to the degraded direct-solve path
                self._done_q.put(("degraded", None, fl))
                return
            try:
                handle = self.engine.dispatch(fl.combined, split_regimes=fl.split)
            except BaseException as e:
                self._done_q.put(("failed", e, fl))
                return
            fl.t_launched = time.monotonic()
            self._count_flush(fl, handle)
            self._done_q.put(("ok", handle, fl))

    def _count_flush(self, fl: _Flush, handle) -> None:
        """Counts a flush the engine dispatched, with the host time of its
        dispatch phases (an engine that reports none adds none)."""
        with self._cond:
            self._stats["flushes"] += 1
            self._stats["flushed_rows"] += fl.combined.B
            for k, v in _phases(handle).items():
                self._stats[k] += v

    # ---- completer thread ----------------------------------------------

    def _complete_loop(self) -> None:
        while True:
            item = self._done_q.get()
            if item is None:
                return
            kind, payload, fl = item
            if kind == "ok":
                try:
                    with TraceAnnotation("repro.serve.materialize", flush=fl.fid):
                        X = payload.result()  # blocks until the device solve lands
                except BaseException as e:
                    self._recover_flush(fl, e)
                    continue
                if self.breaker is not None:
                    self.breaker.record_success()
                self._land(fl, payload, X)
            elif kind == "failed":
                self._recover_flush(fl, payload)
            else:  # "degraded": breaker was open at flush time
                self._serve_degraded(fl)

    def _recover_flush(self, fl: _Flush, exc) -> None:
        with TraceAnnotation("repro.serve.recover", flush=fl.fid):
            self._recover(fl, exc)

    def _recover(self, fl: _Flush, exc) -> None:
        """A flush's engine attempt failed (at dispatch or materialization):
        retry transient errors under the policy, feed the breaker, and — with
        a breaker configured — serve exhausted-transient flushes from the
        degraded path instead of failing them. Non-transient errors always
        propagate to the futures unchanged (real bugs are not retried)."""
        with self._cond:
            self._stats["flush_failures"] += 1
        if self.breaker is not None:
            self.breaker.record_failure()
        if is_transient(exc) and self.retry is not None:
            attempt = 1
            while attempt < self.retry.max_attempts:
                if self.breaker is not None and not self.breaker.allow():
                    break  # opened mid-retry: stop hammering, degrade below
                time.sleep(self.retry.delay(attempt, self._retry_rng))
                attempt += 1
                with self._cond:
                    self._stats["retries"] += 1
                try:
                    handle = self.engine.dispatch(fl.combined, split_regimes=fl.split)
                    fl.t_launched = time.monotonic()
                    X = handle.result()
                except BaseException as e:
                    exc = e
                    with self._cond:
                        self._stats["flush_failures"] += 1
                    if self.breaker is not None:
                        self.breaker.record_failure()
                    if not is_transient(exc):
                        break
                    continue
                if self.breaker is not None:
                    self.breaker.record_success()
                self._count_flush(fl, handle)
                self._land(fl, handle, X)
                return
        fl.t_launched = None  # no engine dispatch landed
        if is_transient(exc) and self.breaker is not None:
            self._serve_degraded(fl)
        else:
            self._abort(fl.reqs, exc)

    def _serve_degraded(self, fl: _Flush) -> None:
        with TraceAnnotation("repro.serve.degraded", flush=fl.fid):
            self._solve_degraded(fl)

    def _solve_degraded(self, fl: _Flush) -> None:
        """The circuit breaker's fallback: solve every instance of the flush
        with the host algorithms (``auto`` regime dispatch for split flushes,
        the reference DP otherwise) — engine-free, slower, but bit-identical
        schedules (asserted in tests/test_service_resilience.py), so callers
        cannot tell a degraded flush from a served one except by latency and
        the absence of ``k_last``."""
        combined, split = fl.combined, fl.split
        try:
            X = np.zeros((combined.B, combined.n), dtype=np.int64)
            obj = np.zeros(combined.B, dtype=np.float64)
            for b in range(combined.B):
                p = combined.instance(b)
                x, _ = _schedule(p, "auto" if split else "dp", check=False)
                X[b, : p.n] = x
                fixed = float(
                    sum(p.cost_tables[i][int(p.lower[i])] for i in range(p.n))
                )
                obj[b] = total_cost(p, x) - fixed  # 0-lower-limit convention
        except BaseException as e:
            self._abort(fl.reqs, e)
            return
        with self._cond:
            self._stats["degraded_flushes"] += 1
            self._stats["degraded_rows"] += combined.B
        self._land(fl, _DegradedHandle(X, obj), X)

    def _land(self, fl: _Flush, handle, X) -> None:
        with TraceAnnotation("repro.serve.demux", flush=fl.fid):
            t_done = time.monotonic()
            for r, (lo, hi) in zip(fl.reqs, fl.slices):
                # each request sees only ITS rows, trimmed to its own n
                r.future._resolve(X[lo:hi, : r.batch.n].copy(), handle, lo, hi, t_done)
            self._retire(fl.reqs, None if fl.t_launched is None else t_done - fl.t_launched)

    def _abort(self, reqs, exc: BaseException) -> None:
        for r in reqs:
            r.future._fail(exc)
        self._retire(reqs)

    def _retire(self, reqs, land_s: Optional[float] = None) -> None:
        """Releases the requests' admission rows; ``land_s`` is the landing
        time of an engine-served flush."""
        with self._cond:
            self._inflight_rows -= sum(r.batch.B for r in reqs)
            self._stats["completed_requests"] += len(reqs)
            if land_s is not None:
                self._stats["land_s"] += land_s
                self._stats["landed_flushes"] += 1
            self._cond.notify_all()  # wake producers blocked on admission


def _phases(handle) -> dict:
    """The host-time phases an engine dispatch reports on its handle
    (:data:`~repro.core.sweep.DISPATCH_PHASES`); empty for an engine that
    reports none."""
    return getattr(handle, "phases", {})
