"""Pallas TPU kernel: banded min-plus (tropical) convolution for the
(MC)^2MKP dynamic program.

TPU adaptation (see DESIGN.md §3): the DP relaxation is not a matmul, so the
MXU is of no use — this is a VPU kernel. The row of ``Tp = T + 1`` entries
is folded onto the eight sublanes of a vreg: it is cut into eight segments
of ``S`` entries (an eighth of the row, in whole ``BT`` tiles), one per
sublane, and each segment carries the ``Wp`` entries before it as a halo
(``W`` rounded up to the 128-lane width; BIG before the row's start), so
every band read of sublane ``r`` is in bounds, starts on a lane-tile
boundary, and uses the offsets of the unfolded row. The output is tiled
into ``(8, BT)`` blocks: lanes ``[base, base + BT)`` of all eight segments.

The fold is over ``T`` and not over the batch: nearly all the kernel's time
in served traffic is at ``B = 1`` (95% in the cross-silo cell, PERF.md §5),
and ``Solver`` and the round driver solve one instance, so packing eight
instances into the sublanes would leave them as empty as before.

The inner loop walks the band ``j = 0 .. W-1`` in ascending order,
``BAND_UNROLL`` steps per iteration, and ends at the batch element's band
bound ``band[b]`` (one past its last finite cost entry), rounded up to
whole groups of ``BAND_UNROLL``: a step whose cost entry is BIG cannot win
(its candidate saturates to BIG and the argmin test is strict), so the
steps past the bound change neither value nor argmin (DESIGN.md §3).
For ``j = 128q + s`` each sublane's window
``halo[r, Wp + base - j : Wp + base - j + BT]`` is built from one ALIGNED
``(8, BT + 128)`` load that starts ``128 (q + 1)`` before the tile,
lane-rotated by ``s`` (``pltpu.roll``) and sliced at the static, aligned
offset 128 — Mosaic refuses unaligned dynamic lane slices. The cost entry
``C_i[j]`` is a scalar read from SMEM, and the running min / argmin carries
are ``(8, BT)`` tiles. Each output is the same float32 sum, chosen by the
same strict first-minimum rule, as :mod:`repro.kernels.ref`.

Layout (per batch element; the batch axis is a squeezed grid block, so the
last two block dimensions always equal the array's or are lane multiples —
the (8, 128) rule):
  band      : (B,)            SMEM, scalar prefetch: band bound per element
  cost      : (1, W)          SMEM, class cost table padded with BIG
  halo      : (8, Wp + S)     VMEM, ``halo[r] = kprev_pad[r*S : r*S + Wp + S]``
              where ``kprev_pad`` is the row behind ``Wp`` BIG entries and
              padded with BIG to ``Wp + 8 S``
  out tiles : (8, BT) values + (8, BT) int32 argmin; the ``(8, S)`` result
              read row-major is the row, cut back to ``Tp``

The batched engine (DESIGN.md §9) is the source of truth: one ``(b, ot)``
grid over independent batch elements. The single-instance entry point is
its ``B = 1`` slice. On a TPU the kernel is compiled (``interpret=False``);
``interpret=True`` runs the same body through the Pallas interpreter and is
meant for CPU tests only.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ref import BIG

__all__ = [
    "minplus_pallas",
    "minplus_pallas_batch",
    "tpu_tuned_bt",
    "band_steps",
    "DEFAULT_BT",
    "BAND_UNROLL",
]

LANES = 128  # f32 lane width of a vreg: the alignment unit of every slice
SUBLANES = 8  # f32 sublanes of a vreg: the row is folded into this many segments
DEFAULT_BT = 1024  # 8 vregs per (8, BT) carry; tiles stay within the register file
# band steps per iteration of the band loop: one step's load, roll and
# compare-select chain is latency-bound, and unrolled steps overlap
# (TPU v5e, (B, T+1, W) = (8, 16385, 1024): 2.86 ms at 1, 0.74 at 8, 0.51 at 32)
BAND_UNROLL = 32

# VMEM the kernel may hold per grid program: 16 MiB, which every current TPU
# generation has. The compiler for v5e accepts rows far longer than this
# budget admits (a row 12x longer still compiles at W = 1024);
# tests/test_tpu_compile.py compiles the longest admitted row.
TPU_VMEM_BYTES = 16 * 2**20


def _round_up(x: int, m: int) -> int:
    return -(-int(x) // m) * m


def _segment(Tp: int, BT: int) -> int:
    """Entries per sublane of the folded row: an eighth of ``Tp``, in whole
    ``BT`` tiles."""
    return _round_up(max(-(-int(Tp) // SUBLANES), 1), BT)


def tpu_tuned_bt(Tp: int, W: int) -> int:
    """Output-tile width for the compiled TPU kernel, checked against VMEM.

    The row is folded onto the eight sublanes in segments of ``S`` entries
    (:func:`_segment`). ``BT`` is ``DEFAULT_BT``, or the segment rounded up
    to whole lane tiles when it is shorter. Against the unfolded layout
    (``(1, min(1024, Tp))`` tiles) a band step loads no more vregs and a row
    takes no more band steps, for every ``Tp``: ``(8, BT + 128)`` is
    ``BT / 128 + 1`` vregs, and ``S / BT`` is at most ``ceil(Tp / 1024)``.
    Per grid program the kernel keeps in VMEM, all 4-byte entries, each
    buffer doubled because the pipeline prefetches the next block:

      * the halo block, ``2 * 4 * 8 * (Wp + S)`` bytes,
      * the value and argmin output tiles, ``2 * 2 * 4 * 8 * BT`` bytes.

    The cost row lives in SMEM. Raises ``ValueError`` when the row does not
    fit ``TPU_VMEM_BYTES``: such rows need a segmented layout with a running-min
    carry, which this kernel does not have.
    """
    bt = min(DEFAULT_BT, _segment(Tp, LANES))
    resident = 2 * 4 * SUBLANES * (_round_up(W, LANES) + _segment(Tp, bt) + 2 * bt)
    if resident > TPU_VMEM_BYTES:
        raise ValueError(
            f"DP row of {int(Tp)} entries with band {int(W)} needs {resident} bytes "
            f"of VMEM, over the {TPU_VMEM_BYTES}-byte budget of the TPU min-plus kernel"
        )
    return bt


def band_steps(band, W: int):
    """Band steps the kernel runs for a row whose band bound is ``band``
    (one past its last finite cost entry) in a table of width ``W``:
    ``min(W, band rounded up to BAND_UNROLL)``. Works on numpy arrays."""
    return np.minimum(W, -(-np.asarray(band) // BAND_UNROLL) * BAND_UNROLL)


def _minplus_batch_kernel(
    band_ref, cost_ref, halo_ref, kout_ref, iout_ref, *, BT: int, W: int, Wp: int
):
    """Grid is ``(b, ot)``; each program owns one ``(8, BT)`` output tile of
    one batch element: lanes ``[base, base + BT)`` of all eight segments, with
    that element's whole halo block resident. The band loop runs
    :func:`band_steps` of ``band_ref[b]``, in ascending ``j``."""
    band = band_ref[pl.program_id(0)]
    base = pl.program_id(1) * BT  # offset of this tile's first element in its segment

    def step(j, carry):
        best, best_idx = carry
        q = j // LANES
        s = j - q * LANES
        # chunk[r, c] = halo[r, a + c]; sublane r's window needs halo[r, Wp + base - j + dt]
        # = chunk[r, 128 - s + dt], i.e. the chunk rotated right by s, from lane 128 on
        a = pl.multiple_of(Wp + base - (q + 1) * LANES, LANES)
        chunk = halo_ref[:, pl.ds(a, BT + LANES)]
        window = pltpu.roll(chunk, s, 1)[:, LANES:]
        cand = window + cost_ref[0, j]
        cand = jnp.where(cand >= BIG, BIG, cand)
        improved = cand < best  # strict: the first minimum over ascending j wins
        best = jnp.where(improved, cand, best)
        best_idx = jnp.where(improved, j, best_idx)
        return best, best_idx

    def unrolled(i, carry):
        for u in range(BAND_UNROLL):
            carry = step(i * BAND_UNROLL + u, carry)
        return carry

    whole = W - W % BAND_UNROLL  # band steps in whole unrolled groups
    groups = jnp.minimum(whole // BAND_UNROLL, (band + BAND_UNROLL - 1) // BAND_UNROLL)
    carry = (jnp.full((SUBLANES, BT), BIG, jnp.float32), jnp.zeros((SUBLANES, BT), jnp.int32))
    best, best_idx = jax.lax.fori_loop(0, groups, unrolled, carry)
    if whole < W:
        # the last W % BAND_UNROLL band steps, in order, where the bound
        # reaches past the whole groups
        end = jnp.where(band > whole, W, whole)
        best, best_idx = jax.lax.fori_loop(whole, end, step, (best, best_idx))
    kout_ref[...] = best
    iout_ref[...] = best_idx


def _minplus_pallas_call(kprev, cost, band, BT: int, interpret: bool) -> tuple:
    """Unjitted body shared by both entry points (jit-of-jit would trace a
    second wrapper per shape for zero caching benefit). ``band`` is ``(B,)``
    or ``None`` for the whole width."""
    if BT % LANES:
        raise ValueError(f"BT={BT} must be a multiple of {LANES}")
    kprev = kprev.astype(jnp.float32)
    cost = cost.astype(jnp.float32)
    B, Tp = kprev.shape
    W = cost.shape[1]
    band = jnp.full((B,), W, jnp.int32) if band is None else band.astype(jnp.int32)
    Wp = _round_up(W, LANES)
    S = _segment(Tp, BT)
    kprev_pad = jnp.concatenate(
        [
            jnp.full((B, Wp), BIG, jnp.float32),
            kprev,
            jnp.full((B, SUBLANES * S - Tp), BIG, jnp.float32),
        ],
        axis=1,
    )
    # halo[b, r] = kprev_pad[b, r*S : r*S + Wp + S]: segment r behind the Wp
    # entries before it, so sublane r's band reads use the unfolded row's offsets
    halo = jnp.stack([kprev_pad[:, r * S : r * S + Wp + S] for r in range(SUBLANES)], axis=1)
    tile = pl.BlockSpec((None, SUBLANES, BT), lambda b, ot, band: (b, 0, ot))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,  # band: one bound per batch element, in SMEM
        grid=(B, S // BT),
        in_specs=[
            pl.BlockSpec((None, 1, W), lambda b, ot, band: (b, 0, 0), memory_space=pltpu.SMEM),
            # the halo's block index ignores ot: it stays resident while ot walks it
            pl.BlockSpec((None, SUBLANES, Wp + S), lambda b, ot, band: (b, 0, 0)),
        ],
        out_specs=[tile, tile],
    )
    kout, iout = pl.pallas_call(
        functools.partial(_minplus_batch_kernel, BT=BT, W=W, Wp=Wp),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, SUBLANES, S), jnp.float32),
            jax.ShapeDtypeStruct((B, SUBLANES, S), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(band, cost[:, None, :], halo)
    return kout.reshape(B, SUBLANES * S)[:, :Tp], iout.reshape(B, SUBLANES * S)[:, :Tp]


@functools.partial(jax.jit, static_argnames=("BT", "interpret"))
def minplus_pallas_batch(
    kprev: jnp.ndarray,
    cost: jnp.ndarray,
    band=None,
    *,
    BT: int = DEFAULT_BT,
    interpret: bool = False,
) -> tuple:
    """Batched DP row update via Pallas. Same contract as
    :func:`repro.kernels.ref.minplus_step_ref_batch`: ``kprev (B, T+1)``,
    ``cost (B, W)`` -> ``(B, T+1)`` values + int32 argmins.

    ``band (B,)``, where given, is each element's band bound: one past the
    last ``j`` with ``cost[b, j] < BIG``, or more. The band loop stops
    there (:func:`band_steps`); the answer is the same as without it.
    ``None`` walks the whole width ``W``.

    One ``(b, ot)`` grid; batch elements are independent, so the grid is
    embarrassingly parallel across both axes. ``BT`` is a multiple of 128.
    The kernel compiles for a TPU; ``interpret=True`` runs it through the
    Pallas interpreter instead, for tests on the CPU.
    """
    return _minplus_pallas_call(kprev, cost, band, BT, interpret)


@functools.partial(jax.jit, static_argnames=("BT", "interpret"))
def minplus_pallas(
    kprev: jnp.ndarray,
    cost: jnp.ndarray,
    band=None,
    *,
    BT: int = DEFAULT_BT,
    interpret: bool = False,
) -> tuple:
    """One DP row update via Pallas: the ``B = 1`` slice of the batched
    kernel. Same contract as :func:`repro.kernels.ref.minplus_step_ref`;
    ``band`` is a scalar bound or ``None``."""
    band = None if band is None else jnp.reshape(band, (1,))
    kout, iout = _minplus_pallas_call(
        jnp.asarray(kprev)[None], jnp.asarray(cost)[None], band, BT, interpret
    )
    return kout[0], iout[0]
