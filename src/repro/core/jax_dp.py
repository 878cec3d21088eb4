"""JAX implementation of the (MC)^2MKP dynamic program for scheduling
instances (contiguous classes), built on the min-plus convolution kernel.

The DP row update over classes is a ``lax.scan``; each step is one banded
min-plus convolution (``repro.kernels``, ``backend="auto"`` dispatches per
hardware). Backtracking is a reverse ``lax.scan`` over the stacked argmin
matrix, fused into the SAME jitted program as the class scan
(:func:`solve_fused_batch_jax`): one dispatch returns only the ``(B, n)``
schedules plus the final DP row ``K_last`` — the ``(n, B, T+1)`` argmin
matrix never crosses a program boundary, so nothing bigger than the answer
is ever transferred. Argmins are band offsets ``j < W``, so the matrix is
stored in the narrowest integer dtype that holds them
(:func:`argmin_dtype`). This is what runs server-side every FL round when
schedules are recomputed from refreshed energy estimates.

Inputs are the 0-lower-limit equivalent instance (Section 5.2) as dense
arrays: ``costs (n, W)`` padded with BIG beyond each ``U_i``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels.ops import BIG, minplus_step_batch, resolve_backend
from .problem import (
    Problem,
    ProblemBatch,
    remove_lower_limits,
    restore_lower_limits,
)

__all__ = [
    "argmin_dtype",
    "band_bounds",
    "solve_schedule_dp_jax",
    "solve_schedule_dp_batch",
    "solve_fused_batch_jax",
    "solve_fused_batch_ring",
    "dp_tables_jax",
    "dp_tables_batch_jax",
    "pack_problem",
]


def pack_problem(p0):
    """Dense BIG-padded cost array for 0-lower-limit instance(s).

    A :class:`Problem` packs to ``(n, W)``; a :class:`ProblemBatch` packs to
    ``(B, n, W)`` (its stacked tables are already dense — they are saturated
    to BIG and downcast). Entries beyond each ``U_i`` are BIG so those item
    sizes are never selected.
    """
    if isinstance(p0, ProblemBatch):
        return jnp.asarray(np.minimum(p0.costs, float(BIG)).astype(np.float32))
    W = int(p0.upper.max()) + 1
    n = p0.n
    lens = p0.upper.astype(np.int64) + 1  # valid prefix per class: 0..U_i
    costs = np.full((n, W), float(BIG), dtype=np.float32)
    # one masked scatter instead of a per-class assignment loop (this sits
    # on the cold path of every single-instance solve)
    mask = np.arange(W)[None, :] < lens[:, None]
    costs[mask] = np.concatenate(
        [np.asarray(t[:l], dtype=np.float32) for t, l in zip(p0.cost_tables, lens)]
    )
    return jnp.asarray(costs)


@functools.partial(jax.jit, static_argnames=("T", "backend"))
def dp_tables_jax(costs: jnp.ndarray, T: int, backend: str = "ref"):
    """Scans the DP over classes for ONE instance: the ``B = 1`` slice of
    :func:`dp_tables_batch_jax`. Returns (K_last (T+1,), I (n, T+1))."""
    # slice the unjitted body: jit-of-jit would trace the batch wrapper a
    # second time per shape for zero caching benefit
    k_last, I = _dp_tables_batch(costs[None], T, backend=backend)
    return k_last[0], I[:, 0]


@functools.partial(jax.jit, static_argnames=("T",))
def backtrack_jax(I: jnp.ndarray, t_star: jnp.ndarray, T: int):
    """Reverse scan: x_i = I[i, t]; t -= x_i (weights == item index). The
    ``B = 1`` slice of :func:`backtrack_batch_jax` (unjitted body — see
    :func:`dp_tables_jax`)."""
    return _backtrack_batch(I[:, None], jnp.asarray(t_star)[None], T)[0]


def solve_schedule_dp_jax(problem: Problem, backend: str = "auto") -> np.ndarray:
    """Drop-in replacement for :func:`repro.core.mc2mkp.solve_schedule_dp`
    running as ONE jitted JAX program (DP scan + fused backtrack) on the
    hardware-dispatched kernel backend."""
    problem.validate()
    p0 = remove_lower_limits(problem)
    costs = pack_problem(p0)
    # Scheduling instances always fill the knapsack: T* == T.
    t_star = jnp.asarray([p0.T], dtype=jnp.int32)
    X, _ = solve_fused_batch_jax(
        costs[None], t_star, int(p0.T), backend=resolve_backend(backend)
    )
    x0 = np.asarray(jax.device_get(X))[0]
    return restore_lower_limits(problem, x0.astype(np.int64))


# ---------------------------------------------------------------------------
# Batched solver: B instances in one jitted program (DESIGN.md §9, §12)
# ---------------------------------------------------------------------------


def argmin_dtype(W: int):
    """The dtype of the argmin matrix for band width ``W``. Its entries are
    band offsets ``0 <= j < W``: int8 holds them up to ``W = 128``, int16 up
    to ``W = 32768``, int32 beyond. At the cross-device bucket ``(B, n, T, W)
    = (8, 4096, 65536, 64)`` int8 keeps the matrix at 2.1 GB, not 8.6."""
    W = int(W)
    if W <= 1 << 7:
        return jnp.int8
    if W <= 1 << 15:
        return jnp.int16
    return jnp.int32


def band_bounds(costs: jnp.ndarray) -> jnp.ndarray:
    """``(B, n)`` int32 band bound of each packed cost row ``costs (B, n,
    W)``: one past its last entry below BIG, 0 for a row of BIG alone. The
    last finite index, not the count of finite entries, since a table may
    hold an interior BIG."""
    j = jnp.arange(1, costs.shape[2] + 1, dtype=jnp.int32)
    return jnp.max(jnp.where(costs < BIG, j, 0), axis=2)


def _dp_scan_from(k0: jnp.ndarray, costs: jnp.ndarray, backend: str = "ref"):
    """Continues the class scan from an arbitrary DP row ``k0 (B, T+1)`` over
    the classes in ``costs (B, n, W)``. Factored out of
    :func:`_dp_tables_batch` so the ring-sharded solver (below) can run each
    device's local classes through the IDENTICAL op sequence — bit-identity
    of the sharded path reduces to handing the row around the ring. Each
    row's band bound (:func:`band_bounds`) is computed once, before the
    scan, and rides beside its cost row. Each argmin row is narrowed to
    :func:`argmin_dtype` as it is stacked."""
    narrow = argmin_dtype(costs.shape[2])

    def step(krow, xs):
        cost_i, band_i = xs
        kout, iout = minplus_step_batch(krow, cost_i, backend=backend, band=band_i)
        return kout, iout.astype(narrow)

    # scan over the class axis: xs must lead with n
    xs = (jnp.swapaxes(costs, 0, 1), jnp.swapaxes(band_bounds(costs), 0, 1))
    return jax.lax.scan(step, k0, xs)


def _dp_tables_batch(costs: jnp.ndarray, T: int, backend: str = "ref"):
    """Unjitted body of :func:`dp_tables_batch_jax` — the fused solver and
    the sweep engine (``core/sweep.py``) close over this inside their own
    per-bucket jits."""
    B = costs.shape[0]
    k0 = jnp.full((B, T + 1), BIG, jnp.float32).at[:, 0].set(0.0)
    return _dp_scan_from(k0, costs, backend=backend)


@functools.partial(jax.jit, static_argnames=("T", "backend"))
def dp_tables_batch_jax(costs: jnp.ndarray, T: int, backend: str = "ref"):
    """Scans the DP over classes for a whole batch at once.

    Args:
      costs: ``(B, n, W)`` packed tables (0-lower-limit instances).
      T: static row width — the max ``T'`` across the batch; rows are shared,
        per-instance workloads only enter at backtracking via ``t_star``.

    Returns (K_last ``(B, T+1)``, I ``(n, B, T+1)`` in
    :func:`argmin_dtype` of ``W``). Production solves use
    :func:`solve_fused_batch_jax` instead, which never lets ``I`` escape the
    program; this two-dispatch path remains as the oracle the fused solver
    is validated against.
    """
    return _dp_tables_batch(costs, T, backend=backend)


def _backtrack_batch(I: jnp.ndarray, t_star: jnp.ndarray, T: int):
    """Unjitted body of :func:`backtrack_batch_jax` (see above). The scan
    walks ``I`` from its last class in place (``reverse=True``), so no
    reversed copy of the argmin matrix is made; schedules are int32 whatever
    the argmins' dtype."""

    def step(t, irow):  # t: (B,), irow: (B, T+1)
        j = jnp.take_along_axis(irow, t[:, None], axis=1)[:, 0].astype(jnp.int32)
        return t - j, j

    _, xs = jax.lax.scan(step, t_star.astype(jnp.int32), I, reverse=True)
    return jnp.swapaxes(xs, 0, 1)  # (B, n)


@functools.partial(jax.jit, static_argnames=("T",))
def backtrack_batch_jax(I: jnp.ndarray, t_star: jnp.ndarray, T: int):
    """Batched reverse scan: per instance, x_i = I[i, b, t_b]; t_b -= x_i.

    ``t_star`` is ``(B,)`` — each instance starts from its own filled
    capacity, so ragged workloads coexist in one padded program.
    """
    return _backtrack_batch(I, t_star, T)


def _solve_fused_batch(costs: jnp.ndarray, t_star: jnp.ndarray, T: int, backend: str = "ref"):
    """Unjitted fused DP + backtrack (the sweep engine's per-bucket
    executables close over this). Returns ``(X (B, n), K_last (B, T+1))``:
    the argmin matrix ``I`` lives only inside the program — XLA keeps it
    device-resident between the class scan and the reverse scan, and only
    the schedules and the final DP row come back."""
    k_last, I = _dp_tables_batch(costs, T, backend=backend)
    X = _backtrack_batch(I, t_star, T)
    return X, k_last


@functools.partial(jax.jit, static_argnames=("T", "backend"))
def solve_fused_batch_jax(costs: jnp.ndarray, t_star: jnp.ndarray, T: int, backend: str = "ref"):
    """Fused batched solver: class scan + reverse backtrack in ONE jitted
    call (DESIGN.md §12).

    Args:
      costs: ``(B, n, W)`` packed tables (0-lower-limit instances).
      t_star: ``(B,)`` int32 filled capacities to backtrack from.
      T: static row width (max ``T'`` across the batch).

    Returns ``(X, K_last)``: ``(B, n)`` int32 schedules and the ``(B, T+1)``
    final DP row (``K_last[b, t]`` = minimal cost of assigning exactly ``t``
    units across the 0-lower-limit instance ``b`` — a free Pareto curve over
    workloads). Compared to chaining :func:`dp_tables_batch_jax` +
    :func:`backtrack_batch_jax`, the ``(n, B, T+1)`` argmin matrix never
    crosses a dispatch boundary and the second trace/launch disappears.
    """
    return _solve_fused_batch(costs, t_star, T, backend=backend)


# ---------------------------------------------------------------------------
# Class-axis ring sharding (DESIGN.md §16): the DP scan is sequential in n,
# so the row is handed around a device ring instead of split — device d holds
# classes [d*n_loc, (d+1)*n_loc) and, on its turn, continues the row through
# them with the SAME op sequence as the unsharded scan (bit-identical rows),
# then passes the row on via ppermute. What shards is the per-device state:
# each device keeps only ITS (n_loc, B, T+1) argmin slab — the memory wall of
# very wide flat problems — and backtracking walks the ring in reverse,
# handing the workload carry back. Compute is pipelined, not divided: every
# turn is one device's scan segment, so wall-clock matches the unsharded scan
# while peak argmin memory per device drops by the ring size.
# ---------------------------------------------------------------------------


def _ring_dp_body(costs_l, k0, t_star, *, T, backend, axis, ndev):
    """Per-device shard_map body: ``costs_l (B, n_loc, W)`` local classes,
    ``k0 (B, T+1)`` / ``t_star (B,)`` replicated. Returns the local schedule
    columns ``(B, n_loc)`` and the replicated final row ``(B, T+1)``."""
    d = jax.lax.axis_index(axis)
    fwd = [(i, (i + 1) % ndev) for i in range(ndev)]
    bwd = [(i, (i - 1) % ndev) for i in range(ndev)]
    row = k0
    I_loc = None
    for r in range(ndev):  # static unroll: one turn per ring position
        new_row, I_r = _dp_scan_from(row, costs_l, backend=backend)
        mine = d == r
        I_loc = I_r if I_loc is None else jnp.where(mine, I_r, I_loc)
        row = jnp.where(mine, new_row, row)
        row = jax.lax.ppermute(row, axis, fwd)
    # after n/ndev turns the ring hands the final row to device 0; masked
    # psum broadcasts it (adding exact zeros — f32-exact) to every device
    k_last = jax.lax.psum(jnp.where(d == 0, row, jnp.zeros_like(row)), axis)
    # reverse ring: the workload carry t walks back through the devices,
    # each backtracking through its own retained argmin slab
    t = t_star.astype(jnp.int32)
    x_loc = jnp.zeros(costs_l.shape[:2], jnp.int32)
    for r in range(ndev - 1, -1, -1):
        xb = _backtrack_batch(I_loc, t, T)
        mine = d == r
        x_loc = jnp.where(mine, xb, x_loc)
        t = jnp.where(mine, t - xb.sum(axis=1).astype(jnp.int32), t)
        t = jax.lax.ppermute(t, axis, bwd)
    return x_loc, k_last


def solve_fused_batch_ring(costs, t_star, T: int, backend: str, mesh, axis: str):
    """Fused DP + backtrack with the CLASS axis sharded over ``mesh[axis]``
    as a ring (see module comment above). Drop-in for
    :func:`solve_fused_batch_jax` — same ``(X (B, n), K_last (B, T+1))``
    contract, bit-identical results — with ``n`` divisible by the ring size
    (the engine pads its n-bucket up to a multiple). Call under ``jax.jit``
    (the sweep engine's bucket executables do)."""
    ndev = int(mesh.shape[axis])
    B = costs.shape[0]
    k0 = jnp.full((B, T + 1), BIG, jnp.float32).at[:, 0].set(0.0)
    P = jax.sharding.PartitionSpec
    body = functools.partial(
        _ring_dp_body, T=T, backend=backend, axis=axis, ndev=ndev
    )
    fn = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(None, axis, None), P(None, None), P(None)),
        out_specs=(P(None, axis), P(None, None)),
        check_vma=False,
    )
    return fn(costs, k0, t_star.astype(jnp.int32))


def solve_schedule_dp_batch(problems, backend: str = "auto") -> np.ndarray:
    """Solves ``B`` scheduling instances with ONE fused jitted batched DP.

    Accepts a sequence of :class:`Problem` (ragged ``n``/``U_i``/``T`` are
    padded into a dense stack) or a prebuilt :class:`ProblemBatch`. Returns a
    ``(B, n)`` int64 array of schedules — row ``b`` solves instance ``b``;
    columns past an instance's own ``n`` are 0.

    The whole sweep is one jit call (DP scan + fused backtrack) specialized
    on the padded shape ``(B, n, W, T_max)``, so closely-related what-if
    instances (deadline sweeps, candidate workloads, dropout subsets) share
    one compilation and one kernel launch instead of ``B`` — and only the
    ``(B, n)`` schedules are transferred to the host.
    """
    batch = problems if isinstance(problems, ProblemBatch) else ProblemBatch.from_problems(problems)
    batch.validate()
    b0 = remove_lower_limits(batch)
    costs = pack_problem(b0)
    Tmax = int(b0.T.max())
    # Scheduling instances always fill the knapsack: T*_b == T'_b.
    t_star = jnp.asarray(b0.T, dtype=jnp.int32)
    X, _ = solve_fused_batch_jax(costs, t_star, Tmax, backend=resolve_backend(backend))
    X0 = np.asarray(jax.device_get(X))
    return restore_lower_limits(batch, X0.astype(np.int64))
