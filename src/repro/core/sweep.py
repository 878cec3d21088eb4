"""Sweep engine: shape-bucketed compile cache + device-sharded batches
(DESIGN.md §10).

The batched DP (§9) amortizes kernel launches across one sweep, but every
new padded shape ``(B, n, T_max, W_max)`` still pays a fresh XLA compile
(~4 s cold vs ~25 ms warm on CPU, see BENCH_batch.json). Production traffic
— multi-round campaigns with drifting energy estimates, 100-point deadline
sweeps, what-if grids — re-solves *near*-identical shapes constantly, so the
engine:

  1. **bucketizes** shapes: each of ``B``/``n``/``T_max``/``W_max`` is
     rounded up to the next power of two, and the padded program for a
     bucket is kept in an LRU of jitted callables. Any solve landing in a
     warm bucket reuses the compiled executable — a campaign compiles once
     on round 1 and never again. Padding is *inert* (phantom instances /
     resources / BIG table entries; see :meth:`ProblemBatch.pad_to`), so
     bucketed solves are bit-identical to the unbucketed fused program
     :func:`~repro.core.jax_dp.solve_fused_batch_jax` on the unpadded
     pack. The bucket executables are the one way the DP runs on a device.
  2. **shards** the batch axis: with a ``mesh``, inputs are placed with
     ``jax.sharding.NamedSharding`` over ``B`` (rounded up to a multiple of
     the axis size) and a ``shard_map`` runs the fused solve on each
     device's rows — the DP has no cross-instance dependence, so sharded
     schedules are also bit-identical. Testable on CPU via
     ``XLA_FLAGS=--xla_force_host_platform_device_count=8``.

``cache_stats()`` exposes hits/misses/compiles/evictions; ``compiles`` is
counted by a trace-time side effect, so it reflects actual XLA tracings
(one per bucket entry), not just cache misses.

Every :meth:`SweepEngine.dispatch` is traced as named spans on the
profiler's clock (``repro.engine.dispatch`` with its children
``classify`` / ``pack`` / ``launch`` or ``compile`` / ``host_solve``;
recorded only while a profile runs), and its handle carries the same
phases' host seconds and the DP cells it launched in ``handle.phases``
(DESIGN.md §14, "Observability").

Regime-split solves (``split_regimes=True``, DESIGN.md §13) add a second
executable kind to the same LRU: ``("marginal", B, n, W)`` buckets hold the
jitted MarIn/MarCo selection kernel (no ``T`` in the key — workloads are
traced inputs there), so monotone slices of a sweep warm independently of
the DP buckets while sharing one cache budget and one set of counters.

The engine is thread-safe (cache and counters are lock-guarded) and, beyond
the blocking :meth:`SweepEngine.solve`, offers :meth:`SweepEngine.dispatch`:
the bucket executable is *launched* (JAX async dispatch, no
``block_until_ready``) and a :class:`SweepHandle` materializes the schedule
only when asked. The async round pipeline (DESIGN.md §11) gets its overlap
from running whole solves on a background planner thread; the
launch/materialize split here is the seam for callers that want to hold an
in-flight solve across other work (e.g. deeper pipeline lookahead).
"""

from __future__ import annotations

import functools
import threading
import time
from collections import OrderedDict
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation
from jax.sharding import AxisType, NamedSharding, PartitionSpec

from ..kernels.minplus import band_steps
from ..kernels.ops import resolve_backend
from ._deprecation import warn_deprecated
from .jax_dp import _solve_fused_batch, pack_problem
from .marginal_jax import (
    MARGINAL_BATCH_ALGORITHMS,
    marginal_select,
    select_algorithm_batch,
)
from .problem import (
    ProblemBatch,
    remove_lower_limits,
    restore_lower_limits,
    total_cost_batch,
)

__all__ = [
    "DISPATCH_PHASES",
    "RegimeSplitHandle",
    "SweepEngine",
    "SweepHandle",
    "bucket_shape",
    "default_engine",
    "make_sweep_mesh",
    "request_bucket",
    "reset_default_engines",
    "solve_dp_batch_cached",
    "solve_schedule_batch_cached",
]


def _next_pow2(x: int) -> int:
    return 1 if x <= 1 else 1 << (int(x) - 1).bit_length()


def bucket_shape(B: int, n: int, T: int, W: int):
    """The compile-cache bucket for an actual packed shape: every dim rounds
    up to the next power of two. Worst-case padding is <2x per dim (~16x
    FLOPs in the T*W-dominated DP), bought once per bucket; in exchange all
    nearby shapes share one compiled executable."""
    return (_next_pow2(B), _next_pow2(n), _next_pow2(T), _next_pow2(W))


# the keys of ``handle.phases``: host seconds of one dispatch and of its
# phases, and what its DP launched: useful band cells against computed cells
# and the cells its band loops run, and the class-scan rows with the real
# clients among them
DISPATCH_PHASES = (
    "dispatch_s",
    "classify_s",
    "pack_s",
    "launch_s",
    "compile_s",
    "dp_band_cells",
    "dp_computed_cells",
    "dp_run_cells",
    "dp_rows",
    "dp_live_rows",
)


class _Phase:
    """A named span (``TraceAnnotation``) whose host seconds are also added
    to ``phases[key]``."""

    __slots__ = ("_phases", "_key", "_span", "_t0")

    def __init__(self, phases: dict, key: str, name: str):
        self._phases, self._key = phases, key
        self._span = TraceAnnotation(name)

    def __enter__(self):
        self._t0 = time.perf_counter()
        self._span.__enter__()

    def __exit__(self, *exc_info):
        self._span.__exit__(*exc_info)
        self._phases[self._key] += time.perf_counter() - self._t0


def _band_cells(b0: ProblemBatch) -> int:
    """Cells of the banded min-plus work a 0-lower-limit batch needs:
    ``(T' + 1) * (U'_i + 1)`` summed over every client with work to place
    (``U'_i > 0``; a client pinned at its lower limit, the padding phantoms
    among them, needs no min-plus). Padding to the bucket adds none."""
    widths = np.where(b0.upper > 0, b0.upper + 1, 0).sum(axis=1)
    return int(((b0.T + 1) * widths).sum())


def _run_cells(b0: ProblemBatch, Bb: int, nb: int, Tb: int, Wb: int, backend: str) -> int:
    """Cells the min-plus row updates of a ``(Bb, nb, Tb, Wb)`` bucket run:
    ``(Tb + 1)`` times the band steps of each of the ``Bb * nb`` rows. The
    Pallas TPU kernel runs :func:`~repro.kernels.minplus.band_steps` of each
    row's band ``U'_i + 1`` (the padding rows' is 1), the other backends the
    whole bucket. Counted from the limits, so on the Pallas kernel it is the
    loop's trip count wherever a client's last table entry is finite."""
    if backend not in ("pallas", "pallas_tpu"):
        return Bb * nb * (Tb + 1) * Wb
    band = np.ones((Bb, nb), dtype=np.int64)
    band[: b0.B, : b0.n] = b0.upper + 1
    return (Tb + 1) * int(band_steps(band, Wb).sum())


def _bucket_axes(b0: ProblemBatch):
    """``(n, T, W)`` pow2 bucket axes of an already-0-lower-limit batch."""
    _, nb, Tb, Wb = bucket_shape(1, b0.n, int(b0.T.max()), b0.W)
    return nb, Tb, Wb


def request_bucket(batch: ProblemBatch):
    """The non-batch pow2 bucket axes ``(n, T, W)`` that the engine's DP
    executable for ``batch`` compiles under (lower limits are shifted out
    first, exactly as :meth:`SweepEngine.dispatch` does).

    THE shared bucket math between the engine and the serve-layer coalescer
    (``repro.serve.coalesce``): requests with equal axes can merge along
    ``B`` into one dispatch without changing which executable runs — only
    the pow2-``B`` ladder varies with flush size.

    Computed in closed form — the shift preserves ``n`` and the table
    width ``W`` and maps ``T -> T - sum(L)`` — so the serve layer's
    per-request keying is O(B*n), not a full O(B*n*W) table shift.
    """
    Tp = int((batch.T - batch.lower.sum(axis=1)).max())
    return _next_pow2(batch.n), _next_pow2(Tp), _next_pow2(batch.W)


def make_sweep_mesh(axis: str = "sweep"):
    """1-D mesh over ALL visible devices, for sharding sweep batches.

    On CPU test hosts, force multiple devices *before* importing jax:
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (same pattern as
    tests/test_distribution.py — the flag binds at first jax init).
    """
    # Auto, not make_mesh's default Explicit: the engine's programs place
    # inputs by NamedSharding and split work by shard_map, not by typed shardings
    return jax.make_mesh((len(jax.devices()),), (axis,), axis_types=(AxisType.Auto,))


class _DeviceSchedulePart:
    """Launch/materialize seam shared by the DP and selection-kernel
    handles: a padded ``(Bb, nb)`` schedule array still computing on the
    device, plus the ORIGINAL (unpadded) batch to unpad against.

    Materialization is lock-guarded: handles are handed across threads by
    the serve layer (many requesters demux one flushed dispatch), and
    without the lock two concurrent first calls to :meth:`result` would
    race the transfer-and-cache sequence and could hand different array
    objects to different callers.
    """

    def __init__(self, raw, batch):
        self._raw = raw  # (Bb, nb) device array, still possibly computing
        self._batch = batch  # the ORIGINAL (unpadded) ProblemBatch
        self._out: Optional[np.ndarray] = None
        self._mat_lock = threading.Lock()  # guards every host-side cache

    def done(self) -> bool:
        """True once the device computation has finished."""
        return self._out is not None or self._raw.is_ready()

    def result(self) -> np.ndarray:
        """The ``(B, n)`` int64 schedules — blocks until the solve lands.
        Thread-safe: concurrent callers all receive the SAME array."""
        with self._mat_lock:
            if self._out is None:
                X0 = np.asarray(jax.device_get(self._raw))[: self._batch.B, : self._batch.n]
                self._out = restore_lower_limits(self._batch, X0.astype(np.int64))
            return self._out


class SweepHandle(_DeviceSchedulePart):
    """An in-flight batched solve: the bucket executable has been dispatched
    (JAX async dispatch — no ``block_until_ready`` issued), but the schedule
    is not yet on the host. :meth:`result` blocks on the device transfer,
    unpads, and restores lower limits; repeated calls return the same array.

    The fused executable (DESIGN.md §12) also returns the final DP row:
    :meth:`k_last` / :meth:`objectives` expose it without any extra
    dispatch. Both are in 0-lower-limit terms (Section 5.2) — add each
    instance's fixed cost ``sum_i C_i(L_i)`` to recover original-instance
    energies.
    """

    def __init__(self, raw, k_last, batch, t_star):
        super().__init__(raw, batch)
        self._k_last = k_last  # (Bb, Tb+1) final DP row, also in flight
        self._t_star = t_star  # (Bb,) filled capacities of the padded batch
        self._k_host: Optional[np.ndarray] = None  # cached k_last transfer

    def k_last(self) -> np.ndarray:
        """The ``(B, T_bucket+1)`` final DP row of the real instances:
        ``k_last()[b, t]`` is the minimal cost of assigning exactly ``t``
        units in 0-lower-limit instance ``b`` (BIG where infeasible) — a
        free workload-Pareto curve per solve. The device transfer happens
        once; repeated calls (and :meth:`objectives`) reuse it, from any
        thread."""
        with self._mat_lock:
            if self._k_host is None:
                self._k_host = np.asarray(jax.device_get(self._k_last))[: self._batch.B]
            return self._k_host

    def objectives(self) -> np.ndarray:
        """Per-instance optimal objective ``K_last[b, t*_b]`` of the
        0-lower-limit instances, shape ``(B,)`` float32 — what the returned
        schedules cost, with no extra dispatch or host-side re-evaluation."""
        k = self.k_last()
        t = np.asarray(self._t_star)
        return k[np.arange(self._batch.B), t[: self._batch.B]]

    def frontier(self, b: int = 0):
        """The pruned (workload, energy) Pareto set of instance ``b``,
        extracted from the final DP row with no extra dispatch: ``(t, e)``
        arrays, workload ascending / energy strictly increasing, in
        0-lower-limit terms (add ``t += sum(L_b)`` and the fixed cost
        ``sum_i C_i(L_i)`` to recover original-instance points). The
        workload-axis sibling of the deadline-axis frontier built by
        :func:`repro.core.pareto.pareto_frontier`."""
        from .pareto import workload_frontier  # leaf-ward: pareto imports sweep

        return workload_frontier(self.k_last()[int(b)])


class _SelectionPart(_DeviceSchedulePart):
    """An in-flight batched marginal-selection solve (MarIn/MarCo slice of a
    regime-split dispatch): like :class:`SweepHandle`, the jitted kernel has
    been launched async and :meth:`result` blocks, unpads, and restores
    lower limits."""

    def __init__(self, raw_x, raw_obj, batch):
        super().__init__(raw_x, batch)
        self._raw_obj = raw_obj  # (Bb,) float32 0-lower-limit objectives
        self._obj_host: Optional[np.ndarray] = None

    def objectives(self) -> np.ndarray:
        with self._mat_lock:
            if self._obj_host is None:
                self._obj_host = np.asarray(jax.device_get(self._raw_obj), np.float64)[
                    : self._batch.B
                ]
            return self._obj_host


class _HostPart:
    """An already-materialized host-solved slice (MarDecUn argmin /
    MarDec packing enumeration) of a regime-split dispatch."""

    def __init__(self, X, obj):
        self._X = X
        self._obj = obj

    def done(self) -> bool:
        return True

    def result(self) -> np.ndarray:
        return self._X

    def objectives(self) -> np.ndarray:
        return self._obj


class RegimeSplitHandle:
    """A mixed-regime in-flight solve: each regime sub-batch ran on its own
    path (selection kernel / host marginal algorithms / fused DP) and this
    handle reassembles rows in the ORIGINAL problem order.

    :meth:`objectives` returns per-instance 0-lower-limit objectives (same
    convention as :meth:`SweepHandle.objectives`; device-solved entries are
    float32-precise). :meth:`k_last` is undefined — only the fused DP
    produces a full final row, and pure-DP dispatches return a plain
    :class:`SweepHandle` which does expose it.
    """

    def __init__(self, B: int, n: int, parts):
        self._B, self._n = B, n
        self._parts = parts  # list of (original-index list, part/handle)
        self._out: Optional[np.ndarray] = None
        self._mat_lock = threading.Lock()

    def done(self) -> bool:
        return self._out is not None or all(p.done() for _, p in self._parts)

    def result(self) -> np.ndarray:
        with self._mat_lock:
            if self._out is None:
                X = np.zeros((self._B, self._n), dtype=np.int64)
                for idx, part in self._parts:
                    X[idx] = part.result()
                self._out = X
            return self._out

    def objectives(self) -> np.ndarray:
        obj = np.zeros(self._B, dtype=np.float64)
        for idx, part in self._parts:
            obj[idx] = np.asarray(part.objectives(), np.float64)
        return obj

    def k_last(self) -> np.ndarray:
        raise ValueError(
            "k_last() is only defined for pure-DP dispatches (the fused DP's "
            "final row); this batch was regime-split — use objectives(), or "
            "dispatch with split_regimes=False for the full Pareto row"
        )


class SweepEngine:
    """Compile-cached, optionally device-sharded batched (MC)^2MKP solver.

    Args:
      backend: min-plus kernel backend, forwarded to
        :func:`~repro.kernels.ops.minplus_step_batch`. The default "auto"
        resolves per hardware at construction (cpu -> "blocked",
        tpu -> "pallas_tpu"); "ref" forces the dense oracle.
      max_entries: LRU capacity — distinct shape buckets kept warm.
      mesh: optional ``jax.sharding.Mesh``; when set, the batch axis is
        sharded over ``mesh_axis`` and ``B`` buckets round up to a multiple
        of that axis size.
      mesh_axis: mesh axis name to shard ``B`` over (default: the mesh's
        first axis).
    """

    def __init__(
        self,
        backend: str = "auto",
        max_entries: int = 64,
        mesh=None,
        mesh_axis: Optional[str] = None,
    ):
        if max_entries < 1:
            raise ValueError("max_entries must be >= 1")
        self.backend = resolve_backend(backend)
        self.max_entries = int(max_entries)
        self.mesh = mesh
        self.mesh_axis = mesh_axis or (mesh.axis_names[0] if mesh is not None else None)
        self._ndev = int(mesh.shape[self.mesh_axis]) if mesh is not None else 1
        self._cache: OrderedDict = OrderedDict()
        self._hits = self._misses = self._compiles = self._evictions = 0
        self._compile_s = 0.0  # host seconds in calls that traced + compiled
        self._bucket_hits: dict = {}  # bucket key -> warm-hit count
        # Guards cache + counters: solves may come from a background planner
        # thread (fl/pipeline.py) or the serve-layer coalescer concurrently
        # with main-thread callers.
        self._lock = threading.Lock()

    # ---- cache ---------------------------------------------------------

    @staticmethod
    def _bucket_label(key) -> str:
        """JSON-friendly bucket name, e.g. ``"dp:B8:n16:T128:W64"``."""
        kind, *dims = key
        names = ("B", "n", "T", "W") if kind == "dp" else ("B", "n", "W")
        return ":".join([kind] + [f"{a}{d}" for a, d in zip(names, dims)])

    def cache_stats(self) -> dict:
        """Counters since construction (or the last :meth:`clear`).
        ``compiles`` counts actual jit tracings — with a warm cache it stays
        flat no matter how many solves run. ``per_bucket_hits`` breaks the
        warm hits down by bucket (keyed by :meth:`_bucket_label`; counts
        survive eviction — they describe traffic, not cache residency), the
        serve layer's per-shape traffic telemetry. ``compile_s`` is the host
        time of the calls that built an executable (a cache miss traces and
        compiles, or loads from the persistent compilation cache)."""
        with self._lock:
            return {
                "hits": self._hits,
                "misses": self._misses,
                "compiles": self._compiles,
                "compile_s": self._compile_s,
                "evictions": self._evictions,
                "entries": len(self._cache),
                "max_entries": self.max_entries,
                "per_bucket_hits": {
                    self._bucket_label(k): v for k, v in self._bucket_hits.items()
                },
            }

    def clear(self) -> None:
        """Drops all cached executables and zeroes the counters."""
        with self._lock:
            self._cache.clear()
            self._hits = self._misses = self._compiles = self._evictions = 0
            self._compile_s = 0.0
            self._bucket_hits = {}

    def _entry(self, key):
        """``(fn, built)``: the bucket's jitted executable, and whether this
        call built it (a miss: its first call traces and compiles)."""
        with self._lock:
            fn = self._cache.get(key)
            if fn is not None:
                self._hits += 1
                self._bucket_hits[key] = self._bucket_hits.get(key, 0) + 1
                self._cache.move_to_end(key)
                return fn, False
            self._misses += 1
            fn = self._build(key)
            self._cache[key] = fn
            while len(self._cache) > self.max_entries:
                self._cache.popitem(last=False)
                self._evictions += 1
            return fn, True

    def _launch(self, key, phases: dict, *args):
        """Calls the bucket's executable (JAX async dispatch: the enqueue, not
        the device time) as span ``repro.engine.launch``, or, where the call
        traces and compiles, as ``repro.engine.compile``; its host seconds,
        the entry lookup included, go to ``launch_s`` or ``compile_s``."""
        t0 = time.perf_counter()
        fn, built = self._entry(key)
        name = "repro.engine.compile" if built else "repro.engine.launch"
        with TraceAnnotation(name, bucket=self._bucket_label(key)):
            out = fn(*args)
        dt = time.perf_counter() - t0
        phases["compile_s" if built else "launch_s"] += dt
        if built:
            with self._lock:
                self._compile_s += dt
        return out

    def _build(self, key):
        backend = self.backend
        if key[0] == "marginal":

            def run_sel(costs, upper, t_star):
                with self._lock:
                    self._compiles += 1
                # monotone fast path (DESIGN.md §13): top-T' marginal-unit
                # selection — no DP table, O(B·nW·log nW)
                return marginal_select(costs, upper, t_star)

            return jax.jit(run_sel)

        _, _, _, Tb, _ = key
        solve = functools.partial(_solve_fused_batch, T=Tb, backend=backend)
        if self.mesh is not None:
            # each device solves its own rows (instances are independent);
            # shard_map, because GSPMD cannot partition a Mosaic kernel
            rows = PartitionSpec(self.mesh_axis)
            solve = jax.shard_map(
                solve,
                mesh=self.mesh,
                in_specs=(rows, rows),
                out_specs=(rows, rows),
                check_vma=False,  # pallas_call outputs carry no vma types
            )

        def run(costs, t_star):
            # Trace-time side effect: executes once per XLA compilation of
            # this entry (shapes are fixed per bucket, so exactly once
            # unless the entry is evicted and rebuilt).
            with self._lock:
                self._compiles += 1
            # fused DP + backtrack (DESIGN.md §12): one dispatch, and only
            # (X, K_last) leave the program — never the (n, B, T+1) argmins
            return solve(costs, t_star)

        return jax.jit(run)

    # ---- solving -------------------------------------------------------

    def _dispatch_dp(self, batch: ProblemBatch, phases: dict) -> SweepHandle:
        with _Phase(phases, "pack_s", "repro.engine.pack"):
            b0 = remove_lower_limits(batch)
            nb, Tb, Wb = _bucket_axes(b0)  # same math the coalescer keys on
            Bb = _next_pow2(b0.B)
            if Bb % self._ndev:
                Bb = ((Bb + self._ndev - 1) // self._ndev) * self._ndev
            phases["dp_band_cells"] += _band_cells(b0)
            phases["dp_computed_cells"] += Bb * nb * (Tb + 1) * Wb
            phases["dp_run_cells"] += _run_cells(b0, Bb, nb, Tb, Wb, self.backend)
            phases["dp_rows"] += Bb * nb
            phases["dp_live_rows"] += int(np.count_nonzero(b0.upper))
            padded = b0.pad_to(B=Bb, n=nb, W=Wb)
            costs = pack_problem(padded)  # (Bb, nb, Wb) float32, BIG-saturated
            t_star = jnp.asarray(padded.T, dtype=jnp.int32)
            if self.mesh is not None:
                P = PartitionSpec
                costs = jax.device_put(
                    costs, NamedSharding(self.mesh, P(self.mesh_axis, None, None))
                )
                t_star = jax.device_put(
                    t_star, NamedSharding(self.mesh, P(self.mesh_axis))
                )
        X_raw, k_last = self._launch(("dp", Bb, nb, Tb, Wb), phases, costs, t_star)
        return SweepHandle(X_raw, k_last, batch, np.asarray(padded.T, dtype=np.int32))

    def _dispatch_selection(self, batch: ProblemBatch, phases: dict) -> _SelectionPart:
        """Launches the MarIn/MarCo slice on the jitted selection kernel
        from its own shape bucket (``("marginal", B, n, W)`` — no ``T`` in
        the key: the workload is a traced input, not a shape). Marginal
        buckets share the engine's LRU and counters with the DP buckets.
        Inputs are not mesh-sharded: selection solves are orders of
        magnitude smaller than the DPs they replace."""
        with _Phase(phases, "pack_s", "repro.engine.pack"):
            b0 = remove_lower_limits(batch)
            if b0.W < 2:  # every resource pinned at its lower limit: T' == 0
                zeros = np.zeros((batch.B, batch.n), dtype=np.int64)
                return _HostPart(
                    restore_lower_limits(batch, zeros), np.zeros(batch.B)
                )
            Bb, nb, _, Wb = bucket_shape(b0.B, b0.n, 1, b0.W)
            padded = b0.pad_to(B=Bb, n=nb, W=Wb)
            args = (
                pack_problem(padded),
                jnp.asarray(padded.upper, jnp.int32),
                jnp.asarray(padded.T, jnp.int32),
            )
        x_raw, obj_raw = self._launch(("marginal", Bb, nb, Wb), phases, *args)
        return _SelectionPart(x_raw, obj_raw, batch)

    @staticmethod
    def _host_part(batch: ProblemBatch, algorithm: str) -> _HostPart:
        """MarDecUn / MarDec slice: solved eagerly on the host (numpy) at
        dispatch time, as span ``repro.engine.host_solve``."""
        with TraceAnnotation("repro.engine.host_solve"):
            X = MARGINAL_BATCH_ALGORITHMS[algorithm](batch)
            b0 = remove_lower_limits(batch)
            obj = total_cost_batch(b0, X - batch.lower)
        return _HostPart(X, obj)

    @staticmethod
    def _take(batch: ProblemBatch, idx) -> ProblemBatch:
        """Row-slices a batch, keeping the (n, W) envelope — padding is
        inert on every path, so sub-batch solves are bit-identical to
        solving the instances alone."""
        idx = np.asarray(idx, dtype=np.int64)
        return ProblemBatch(
            T=batch.T[idx],
            lower=batch.lower[idx],
            upper=batch.upper[idx],
            costs=batch.costs[idx],
        )

    def dispatch(self, problems, split_regimes: bool = False):
        """Launches the batched solve WITHOUT materializing the result.

        Packing/padding happens eagerly (cheap numpy), the bucket executable
        is invoked once — JAX async dispatch returns immediately with the
        computation in flight — and the returned :class:`SweepHandle` does
        the blocking ``device_get`` only on :meth:`SweepHandle.result`, so
        a caller can keep working while the solve computes.

        ``split_regimes=True`` enables the monotone fast path (DESIGN.md
        §13): each instance's marginal-cost regime picks its algorithm
        (paper Table 2, via
        :func:`~repro.core.marginal_jax.select_algorithm_batch`), the batch
        is partitioned into per-algorithm sub-batches (MarIn/MarCo ->
        selection kernel, MarDecUn/MarDec -> host numpy, arbitrary -> fused
        DP), and a :class:`RegimeSplitHandle` reassembles rows in original
        order — bit-identical to dispatching each sub-batch alone. Batches
        that classify as pure-DP take exactly the default path (same
        buckets, same counters, plain :class:`SweepHandle`). The default
        ``False`` keeps the documented contract of bit-identity with
        :func:`~repro.core.jax_dp.solve_fused_batch_jax` on the unpadded
        pack, for every instance. MarDec sub-batches compute at dispatch
        time (host code has no async seam).

        The returned handle's ``phases`` dict (keys
        :data:`DISPATCH_PHASES`) holds this call's host seconds in all
        (``dispatch_s``) and by phase: regime classification
        (``classify_s``), validation, slicing, padding and packing
        (``pack_s``), the executable calls (``launch_s``; a call that
        compiled counts under ``compile_s`` instead), and the DP's useful
        band cells (``dp_band_cells``) against the cells its executables
        compute over their buckets (``dp_computed_cells``) and the cells
        their band loops run (``dp_run_cells``: the Pallas kernel ends each
        row's loop at its band, other backends run the whole bucket); the
        rows the class scan runs, ``Bb * nb`` (``dp_rows``), and the real clients
        among them with work to place (``dp_live_rows``)."""
        t0 = time.perf_counter()
        phases = dict.fromkeys(DISPATCH_PHASES, 0)
        with TraceAnnotation("repro.engine.dispatch"):
            handle = self._dispatch(problems, split_regimes, phases)
        phases["dispatch_s"] = time.perf_counter() - t0
        handle.phases = phases
        return handle

    def _dispatch(self, problems, split_regimes: bool, phases: dict):
        with _Phase(phases, "pack_s", "repro.engine.pack"):
            batch = (
                problems
                if isinstance(problems, ProblemBatch)
                else ProblemBatch.from_problems(problems)
            )
            batch.validate()
        if not split_regimes:
            return self._dispatch_dp(batch, phases)
        with _Phase(phases, "classify_s", "repro.engine.classify"):
            algs = select_algorithm_batch(batch)
            groups: dict = {}
            for b, alg in enumerate(algs):
                key = "selection" if alg in ("marin", "marco") else alg
                groups.setdefault(key, []).append(b)
        if set(groups) == {"dp"}:
            return self._dispatch_dp(batch, phases)

        def take(key):
            with _Phase(phases, "pack_s", "repro.engine.pack"):
                return self._take(batch, groups[key])

        parts = []
        # DP first: its executable is the slowest, let it compute while the
        # host parts run
        if "dp" in groups:
            parts.append((groups["dp"], self._dispatch_dp(take("dp"), phases)))
        if "selection" in groups:
            parts.append(
                (groups["selection"], self._dispatch_selection(take("selection"), phases))
            )
        for alg in ("mardecun", "mardec"):
            if alg in groups:
                parts.append((groups[alg], self._host_part(take(alg), alg)))
        return RegimeSplitHandle(batch.B, batch.n, parts)

    def solve(self, problems, split_regimes: bool = False) -> np.ndarray:
        """Blocking batched solve: a sequence of :class:`Problem` or a
        prebuilt :class:`ProblemBatch` in, ``(B, n)`` int64 schedules out,
        bit-identical to :func:`~repro.core.jax_dp.solve_fused_batch_jax`
        on the unpadded pack — but warm buckets skip compilation entirely.
        With ``split_regimes=True``, monotone instances ride the marginal
        fast path instead of the DP (see :meth:`dispatch`)."""
        return self.dispatch(problems, split_regimes=split_regimes).result()


# ---------------------------------------------------------------------------
# Process-wide default engines: schedule_batch / deadline_sweep / FL servers
# all share these, so ANY repeated shape anywhere in the process is warm.
# ---------------------------------------------------------------------------

_DEFAULT_ENGINES: dict = {}


def default_engine(backend: str = "auto") -> SweepEngine:
    """The shared per-backend engine (created on first use). Keyed on the
    RESOLVED backend, so "auto" and its hardware-resolved name (e.g.
    "blocked" on CPU) share one engine and one warm cache."""
    backend = resolve_backend(backend)
    eng = _DEFAULT_ENGINES.get(backend)
    if eng is None:
        eng = _DEFAULT_ENGINES[backend] = SweepEngine(backend=backend)
    return eng


def reset_default_engines() -> None:
    """Drops the shared engines (test isolation)."""
    _DEFAULT_ENGINES.clear()


def _resolve_engine(backend: Optional[str], engine):
    """The engine a cached solve runs on: the given one (after checking it
    does not contradict an explicitly named backend — its executables are
    compiled for ITS backend, so we raise rather than silently running the
    wrong kernel; backends compare after "auto" resolution), else the shared
    default for ``backend`` (``None`` -> "auto": per-hardware dispatch)."""
    if engine is not None:
        if backend is not None and resolve_backend(backend) != engine.backend:
            raise ValueError(
                f"backend {backend!r} conflicts with engine.backend "
                f"{engine.backend!r}; pass an engine built for that backend"
            )
        return engine
    return default_engine(backend or "auto")


def _solve_cached(
    problems, backend: Optional[str], engine, split_regimes: bool
) -> np.ndarray:
    """THE cached batched solve every public path shares: resolves the
    engine (:func:`_resolve_engine`) and runs one blocking solve. Private —
    callers go through :class:`repro.core.solver.Solver` (or the deprecated
    shims below, which delegate here unchanged)."""
    return _resolve_engine(backend, engine).solve(problems, split_regimes=split_regimes)


def solve_dp_batch_cached(
    problems, backend: Optional[str] = None, engine=None
) -> np.ndarray:
    """Deprecated shim: use ``Solver(engine=...).solve(problems,
    algorithm="dp_batch")`` (the facade, DESIGN.md §15).

    Batched DP solve through a sweep engine (the given one, else the shared
    default for ``backend``); delegates to the same private implementation
    the facade calls, so behavior — including the backend-vs-engine conflict
    ValueError — is bit-identical."""
    warn_deprecated(
        "solve_dp_batch_cached", 'Solver(engine=...).solve(problems, algorithm="dp_batch")'
    )
    return _solve_cached(problems, backend, engine, split_regimes=False)


def solve_schedule_batch_cached(
    problems, backend: Optional[str] = None, engine=None
) -> np.ndarray:
    """Deprecated shim: use ``Solver(engine=...).solve(problems)`` (the
    facade, DESIGN.md §15).

    Regime-dispatched batched solve (DESIGN.md §13): monotone instances ride
    the marginal fast path, only arbitrary-regime instances pay the DP. Same
    engine/backend conventions (and conflict check) as
    :func:`solve_dp_batch_cached`; returns ``(B, n)`` int64 schedules in
    original problem order — bit-identical to the pre-facade behavior."""
    warn_deprecated(
        "solve_schedule_batch_cached", "Solver(engine=...).solve(problems)"
    )
    return _solve_cached(problems, backend, engine, split_regimes=True)
