"""JAX implementation of the (MC)^2MKP dynamic program for scheduling
instances (contiguous classes), built on the min-plus convolution kernel.

The DP row update over classes is a ``lax.scan``; each step is one banded
min-plus convolution (``repro.kernels``, ``backend="auto"`` dispatches per
hardware). Backtracking is a reverse ``lax.scan`` over the stacked argmin
matrix, fused into the SAME jitted program as the class scan
(:func:`_solve_fused_batch`): one dispatch returns only the ``(B, n)``
schedules plus the final DP row ``K_last`` — the ``(n, B, T+1)`` argmin
matrix never crosses a program boundary, so nothing bigger than the answer
is ever transferred. Argmins are band offsets ``j < W``, so the matrix is
stored in the narrowest integer dtype that holds them
(:func:`argmin_dtype`).

This fused program is the one way the DP runs on a device: the sweep
engine's bucket executables (``core/sweep.py``) close over
:func:`_solve_fused_batch`, and every solve that reaches the device goes
through the engine. :func:`solve_fused_batch_jax` is the same body under
``jax.jit`` without the engine's bucketing, for callers that pack their
own inputs.

Inputs are the 0-lower-limit equivalent instance (Section 5.2) as dense
arrays: ``costs (n, W)`` padded with BIG beyond each ``U_i``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels.ops import BIG, minplus_step_batch
from .problem import ProblemBatch

__all__ = [
    "argmin_dtype",
    "band_bounds",
    "solve_fused_batch_jax",
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


def _dp_tables_batch(costs: jnp.ndarray, T: int, backend: str = "ref"):
    """Scans the DP over the classes of ``costs (B, n, W)`` (0-lower-limit
    instances) from the row ``[0, BIG, ...]`` of width ``T + 1``: the max
    ``T'`` across the batch, since rows are shared and per-instance
    workloads only enter at backtracking. Returns (K_last ``(B, T+1)``, I
    ``(n, B, T+1)``). Each row's band bound (:func:`band_bounds`) is
    computed once, before the scan, and rides beside its cost row. Each
    argmin row is narrowed to :func:`argmin_dtype` as it is stacked."""
    B = costs.shape[0]
    k0 = jnp.full((B, T + 1), BIG, jnp.float32).at[:, 0].set(0.0)
    narrow = argmin_dtype(costs.shape[2])

    def step(krow, xs):
        cost_i, band_i = xs
        kout, iout = minplus_step_batch(krow, cost_i, backend=backend, band=band_i)
        return kout, iout.astype(narrow)

    # scan over the class axis: xs must lead with n
    xs = (jnp.swapaxes(costs, 0, 1), jnp.swapaxes(band_bounds(costs), 0, 1))
    return jax.lax.scan(step, k0, xs)


def _backtrack_batch(I: jnp.ndarray, t_star: jnp.ndarray, T: int):
    """Reverse scan: per instance, ``x_i = I[i, b, t_b]; t_b -= x_i``, from
    ``t_star (B,)``, each instance's own filled capacity, so ragged
    workloads coexist in one padded program. The scan walks ``I`` from its
    last class in place (``reverse=True``), so no reversed copy of the
    argmin matrix is made; schedules are int32 whatever the argmins'
    dtype."""

    def step(t, irow):  # t: (B,), irow: (B, T+1)
        j = jnp.take_along_axis(irow, t[:, None], axis=1)[:, 0].astype(jnp.int32)
        return t - j, j

    _, xs = jax.lax.scan(step, t_star.astype(jnp.int32), I, reverse=True)
    return jnp.swapaxes(xs, 0, 1)  # (B, n)


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
    call (DESIGN.md §12), with no shape bucketing: each distinct
    ``(costs.shape, T)`` traces and compiles its own program.

    Args:
      costs: ``(B, n, W)`` packed tables (0-lower-limit instances).
      t_star: ``(B,)`` int32 filled capacities to backtrack from.
      T: static row width (max ``T'`` across the batch).

    Returns ``(X, K_last)``: ``(B, n)`` int32 schedules and the ``(B, T+1)``
    final DP row (``K_last[b, t]`` = minimal cost of assigning exactly ``t``
    units across the 0-lower-limit instance ``b`` — a free Pareto curve over
    workloads). The ``(n, B, T+1)`` argmin matrix never leaves the
    program.
    """
    return _solve_fused_batch(costs, t_star, T, backend=backend)
