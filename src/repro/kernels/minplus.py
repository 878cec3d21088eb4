"""Pallas TPU kernel: banded min-plus (tropical) convolution for the
(MC)^2MKP dynamic program.

TPU adaptation (see DESIGN.md §3): the DP relaxation is not a matmul, so the
MXU is of no use — this is a VPU kernel. The *output* row is tiled into
``BT``-wide blocks; the previous DP row stays whole in VMEM, behind a
``Wp``-entry BIG prefix (``W`` rounded up to the 128-lane width) so every
band read is in bounds and starts on a lane-tile boundary.

The inner ``fori_loop`` walks the band ``j = 0 .. W-1``. For ``j = 128q + s``
the window ``K_{i-1}[base - j : base - j + BT]`` is built from one ALIGNED
load of ``BT + 128`` entries that starts ``128 (q + 1)`` before the tile,
lane-rotated by ``s`` (``pltpu.roll``) and sliced at the static, aligned
offset 128 — Mosaic refuses unaligned dynamic lane slices. The cost entry
``C_i[j]`` is a scalar read from SMEM, and the running min / argmin carries
are ``(1, BT)`` vectors.

Layout (per batch element; the batch axis is a squeezed grid block, so the
last two block dimensions always equal the array's or are lane multiples —
the (8, 128) rule):
  cost      : (1, W)        SMEM, class cost table padded with BIG
  kprev_pad : (1, Wp + Tpad) VMEM, previous row behind the BIG prefix
  out tiles : (1, BT) values + (1, BT) int32 argmin
Eight instances could later share one (8, L) sublane tile with the same
loop (ROADMAP item 2); one instance per row keeps this kernel simple.

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
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ref import BIG

__all__ = ["minplus_pallas", "minplus_pallas_batch", "tpu_tuned_bt", "DEFAULT_BT"]

LANES = 128  # f32 lane width of a vreg: the alignment unit of every slice
DEFAULT_BT = 1024  # 8 vregs per (1, BT) carry; tiles stay within the register file

# VMEM the kernel may hold per grid program: 16 MiB, which every current TPU
# generation has. The compiler for v5e accepts rows about 3x longer than this
# budget admits (tests/test_tpu_compile.py compiles the longest admitted row).
TPU_VMEM_BYTES = 16 * 2**20


def _round_up(x: int, m: int) -> int:
    return -(-int(x) // m) * m


def tpu_tuned_bt(Tp: int, W: int) -> int:
    """Output-tile width for the compiled TPU kernel, checked against VMEM.

    ``BT`` is ``DEFAULT_BT``, or the row rounded up to whole lane tiles when
    the row is shorter (a wider tile only computes discarded outputs). Per
    grid program the kernel keeps in VMEM, all 4-byte entries, each buffer
    doubled because the pipeline prefetches the next block:

      * the padded previous row, ``2 * 4 * (Wp + Tpad)`` bytes,
      * the value and argmin output tiles, ``2 * 2 * 4 * BT`` bytes.

    The cost row lives in SMEM. Raises ``ValueError`` when the row does not
    fit ``TPU_VMEM_BYTES``: such rows need a segmented layout with a running-min
    carry, which this kernel does not have.
    """
    bt = min(DEFAULT_BT, _round_up(max(int(Tp), 1), LANES))
    resident = 8 * (_round_up(W, LANES) + _round_up(Tp, bt)) + 16 * bt
    if resident > TPU_VMEM_BYTES:
        raise ValueError(
            f"DP row of {int(Tp)} entries with band {int(W)} needs {resident} bytes "
            f"of VMEM, over the {TPU_VMEM_BYTES}-byte budget of the TPU min-plus kernel"
        )
    return bt


def _minplus_batch_kernel(cost_ref, kprev_ref, kout_ref, iout_ref, *, BT: int, W: int, Wp: int):
    """Grid is ``(b, ot)``; each program owns one output tile of one batch
    element, with that element's whole padded previous row resident."""
    base = pl.program_id(1) * BT  # absolute t of this tile's first element

    def body(j, carry):
        best, best_idx = carry
        q = j // LANES
        s = j - q * LANES
        # chunk[c] = kprev_pad[a + c]; the window needs kprev_pad[Wp + base - j + dt]
        # = chunk[128 - s + dt], i.e. the chunk rotated right by s, from lane 128 on
        a = pl.multiple_of(Wp + base - (q + 1) * LANES, LANES)
        chunk = kprev_ref[:, pl.ds(a, BT + LANES)]
        window = pltpu.roll(chunk, s, 1)[:, LANES:]
        cand = window + cost_ref[0, j]
        cand = jnp.where(cand >= BIG, BIG, cand)
        improved = cand < best  # strict: the first minimum over ascending j wins
        best = jnp.where(improved, cand, best)
        best_idx = jnp.where(improved, j, best_idx)
        return best, best_idx

    init = (jnp.full((1, BT), BIG, jnp.float32), jnp.zeros((1, BT), jnp.int32))
    best, best_idx = jax.lax.fori_loop(0, W, body, init)
    kout_ref[...] = best
    iout_ref[...] = best_idx


def _minplus_pallas_call(kprev, cost, BT: int, interpret: bool) -> tuple:
    """Unjitted body shared by both entry points (jit-of-jit would trace a
    second wrapper per shape for zero caching benefit)."""
    if BT % LANES:
        raise ValueError(f"BT={BT} must be a multiple of {LANES}")
    kprev = kprev.astype(jnp.float32)
    cost = cost.astype(jnp.float32)
    B, Tp = kprev.shape
    W = cost.shape[1]
    Wp = _round_up(W, LANES)
    Tpad = _round_up(Tp, BT)
    kprev_pad = jnp.concatenate(
        [
            jnp.full((B, Wp), BIG, jnp.float32),
            kprev,
            jnp.full((B, Tpad - Tp), BIG, jnp.float32),
        ],
        axis=1,
    )
    tile = pl.BlockSpec((None, 1, BT), lambda b, ot: (b, 0, ot))
    kout, iout = pl.pallas_call(
        functools.partial(_minplus_batch_kernel, BT=BT, W=W, Wp=Wp),
        grid=(B, Tpad // BT),
        in_specs=[
            pl.BlockSpec((None, 1, W), lambda b, ot: (b, 0, 0), memory_space=pltpu.SMEM),
            # the row's block index ignores ot: it stays resident while ot walks it
            pl.BlockSpec((None, 1, Wp + Tpad), lambda b, ot: (b, 0, 0)),
        ],
        out_specs=[tile, tile],
        out_shape=[
            jax.ShapeDtypeStruct((B, 1, Tpad), jnp.float32),
            jax.ShapeDtypeStruct((B, 1, Tpad), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(cost[:, None, :], kprev_pad[:, None, :])
    return kout[:, 0, :Tp], iout[:, 0, :Tp]


@functools.partial(jax.jit, static_argnames=("BT", "interpret"))
def minplus_pallas_batch(
    kprev: jnp.ndarray,
    cost: jnp.ndarray,
    *,
    BT: int = DEFAULT_BT,
    interpret: bool = False,
) -> tuple:
    """Batched DP row update via Pallas. Same contract as
    :func:`repro.kernels.ref.minplus_step_ref_batch`: ``kprev (B, T+1)``,
    ``cost (B, W)`` -> ``(B, T+1)`` values + int32 argmins.

    One ``(b, ot)`` grid; batch elements are independent, so the grid is
    embarrassingly parallel across both axes. ``BT`` is a multiple of 128.
    The kernel compiles for a TPU; ``interpret=True`` runs it through the
    Pallas interpreter instead, for tests on the CPU.
    """
    return _minplus_pallas_call(kprev, cost, BT, interpret)


@functools.partial(jax.jit, static_argnames=("BT", "interpret"))
def minplus_pallas(
    kprev: jnp.ndarray,
    cost: jnp.ndarray,
    *,
    BT: int = DEFAULT_BT,
    interpret: bool = False,
) -> tuple:
    """One DP row update via Pallas: the ``B = 1`` slice of the batched
    kernel. Same contract as :func:`repro.kernels.ref.minplus_step_ref`."""
    kout, iout = _minplus_pallas_call(
        jnp.asarray(kprev)[None], jnp.asarray(cost)[None], BT, interpret
    )
    return kout[0], iout[0]
