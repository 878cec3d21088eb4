"""Pallas TPU flash attention (FlashAttention-2 style), fwd + bwd.

TPU adaptation: q/k/v blocks tiled into VMEM; the (Bq, Bk) score tile lives
only in registers/VMEM — attention probabilities NEVER touch HBM, which is
the dominant memory-roofline term of the naive XLA path at train shapes
(EXPERIMENTS.md §Perf: ~65 of 84 GB/layer on deepseek-7b train_4k).

Supported: causal / sliding-window / bidirectional masks, GQA (kv heads
indexed ``h // G`` in the BlockSpec index maps), optional score softcap
(gemma2), fp32 accumulation. Shapes: q (B, H, Sq, D), k/v (B, Hkv, Sk, D).

Backward follows FlashAttention-2: a precomputed row term
``delta = rowsum(dO * O)``, a dq kernel (grid over q blocks) and a dk/dv
kernel (grid over kv blocks, inner loop over q blocks x GQA group).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["flash_attention"]

NEG_INF = -1e30


def _mask_tile(q_ids, k_ids, kind: str, window: int):
    qi = q_ids[:, None]
    ki = k_ids[None, :]
    if kind == "bidirectional":
        return jnp.ones((q_ids.shape[0], k_ids.shape[0]), jnp.bool_)
    if kind == "causal":
        return ki <= qi
    if kind == "sliding":
        return (ki <= qi) & (ki > qi - window)
    raise ValueError(kind)


def _apply_softcap(s, softcap):
    if softcap:
        return softcap * jnp.tanh(s / softcap)
    return s


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, Bq, Bk, Sk, D, kind,
                window, softcap, scale):
    qi = pl.program_id(2)
    q = q_ref[0, 0].astype(jnp.float32) * scale  # (Bq, D)
    q_ids = qi * Bq + jax.lax.broadcasted_iota(jnp.int32, (Bq,), 0)

    nk = Sk // Bk
    if kind in ("causal", "sliding"):
        # blocks strictly above the diagonal band contribute nothing
        hi = jnp.minimum(((qi + 1) * Bq + Bk - 1) // Bk, nk)
    else:
        hi = nk

    def body(j, carry):
        acc, m, l = carry
        k = k_ref[0, 0, pl.dslice(j * Bk, Bk)].astype(jnp.float32)  # (Bk, D)
        v = v_ref[0, 0, pl.dslice(j * Bk, Bk)].astype(jnp.float32)
        s = q @ k.T  # (Bq, Bk)
        s = _apply_softcap(s, softcap)
        k_ids = j * Bk + jax.lax.broadcasted_iota(jnp.int32, (Bk,), 0)
        mask = _mask_tile(q_ids, k_ids, kind, window)
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m - m_new)
        l = l * alpha + p.sum(axis=1)
        acc = acc * alpha[:, None] + p @ v
        return acc, m_new, l

    acc0 = jnp.zeros((Bq, D), jnp.float32)
    m0 = jnp.full((Bq,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((Bq,), jnp.float32)
    acc, m, l = jax.lax.fori_loop(0, hi, body, (acc0, m0, l0))
    l_safe = jnp.maximum(l, 1e-30)
    o_ref[0, 0] = (acc / l_safe[:, None]).astype(o_ref.dtype)
    lse_ref[0, 0] = m + jnp.log(l_safe)


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, *,
               Bq, Bk, Sk, D, kind, window, softcap, scale):
    qi = pl.program_id(2)
    q = q_ref[0, 0].astype(jnp.float32) * scale
    do = do_ref[0, 0].astype(jnp.float32)
    lse = lse_ref[0, 0]
    delta = delta_ref[0, 0]
    q_ids = qi * Bq + jax.lax.broadcasted_iota(jnp.int32, (Bq,), 0)
    nk = Sk // Bk
    hi = jnp.minimum(((qi + 1) * Bq + Bk - 1) // Bk, nk) if kind in ("causal", "sliding") else nk

    def body(j, dq):
        k = k_ref[0, 0, pl.dslice(j * Bk, Bk)].astype(jnp.float32)
        v = v_ref[0, 0, pl.dslice(j * Bk, Bk)].astype(jnp.float32)
        s_raw = q @ k.T
        s = _apply_softcap(s_raw, softcap)
        k_ids = j * Bk + jax.lax.broadcasted_iota(jnp.int32, (Bk,), 0)
        mask = _mask_tile(q_ids, k_ids, kind, window)
        s = jnp.where(mask, s, NEG_INF)
        p = jnp.exp(s - lse[:, None])
        dp = do @ v.T
        ds = p * (dp - delta[:, None])
        if softcap:
            ds = ds * (1.0 - jnp.square(jnp.tanh(s_raw / softcap)))
        ds = jnp.where(mask, ds, 0.0)
        return dq + ds @ k

    dq = jax.lax.fori_loop(0, hi, body, jnp.zeros((Bq, D), jnp.float32))
    dq_ref[0, 0] = (dq * scale).astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref, *,
                Bq, Bk, Sq, D, G, kind, window, softcap, scale):
    ki = pl.program_id(2)
    k = k_ref[0, 0].astype(jnp.float32)  # (Bk, D)
    v = v_ref[0, 0].astype(jnp.float32)
    k_ids = ki * Bk + jax.lax.broadcasted_iota(jnp.int32, (Bk,), 0)
    nq = Sq // Bq
    lo = (ki * Bk) // Bq if kind in ("causal", "sliding") else 0

    def outer(g, carry):
        def body(i, carry2):
            dk, dv = carry2
            q = q_ref[0, 0, g, pl.dslice(i * Bq, Bq)].astype(jnp.float32) * scale
            do = do_ref[0, 0, g, pl.dslice(i * Bq, Bq)].astype(jnp.float32)
            lse = lse_ref[0, 0, g, pl.dslice(i * Bq, Bq)]
            delta = delta_ref[0, 0, g, pl.dslice(i * Bq, Bq)]
            s_raw = q @ k.T  # (Bq, Bk)
            s = _apply_softcap(s_raw, softcap)
            q_ids = i * Bq + jax.lax.broadcasted_iota(jnp.int32, (Bq,), 0)
            mask = _mask_tile(q_ids, k_ids, kind, window)
            s = jnp.where(mask, s, NEG_INF)
            p = jnp.exp(s - lse[:, None])
            dv = dv + p.T @ do
            dp = do @ v.T
            ds = p * (dp - delta[:, None])
            if softcap:
                ds = ds * (1.0 - jnp.square(jnp.tanh(s_raw / softcap)))
            ds = jnp.where(mask, ds, 0.0)
            dk = dk + ds.T @ q
            return dk, dv

        return jax.lax.fori_loop(lo, nq, body, carry)

    init = (jnp.zeros((Bk, D), jnp.float32), jnp.zeros((Bk, D), jnp.float32))
    dk, dv = jax.lax.fori_loop(0, G, outer, init)
    # q was loaded pre-scaled, so ds^T @ q already carries the one scale factor
    dk_ref[0, 0] = dk.astype(dk_ref.dtype)
    dv_ref[0, 0] = dv.astype(dv_ref.dtype)


# ---------------------------------------------------------------------------
# host-side wrappers
# ---------------------------------------------------------------------------


def _fwd(q, k, v, kind, window, softcap, scale, Bq, Bk, interpret):
    B, H, Sq, D = q.shape
    Hkv, Sk = k.shape[1], k.shape[2]
    G = H // Hkv
    grid = (B, H, Sq // Bq)
    kernel = functools.partial(
        _fwd_kernel, Bq=Bq, Bk=Bk, Sk=Sk, D=D, kind=kind, window=window,
        softcap=softcap, scale=scale,
    )
    o, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, Bq, D), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, Sk, D), lambda b, h, i: (b, h // G, 0, 0)),
            pl.BlockSpec((1, 1, Sk, D), lambda b, h, i: (b, h // G, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, Bq, D), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, Bq), lambda b, h, i: (b, h, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct((B, H, Sq), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
    return o, lse


def _bwd(q, k, v, o, lse, do, kind, window, softcap, scale, Bq, Bk, interpret):
    B, H, Sq, D = q.shape
    Hkv, Sk = k.shape[1], k.shape[2]
    G = H // Hkv
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)  # (B,H,Sq)

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, Bq=Bq, Bk=Bk, Sk=Sk, D=D, kind=kind,
                          window=window, softcap=softcap, scale=scale),
        grid=(B, H, Sq // Bq),
        in_specs=[
            pl.BlockSpec((1, 1, Bq, D), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, Sk, D), lambda b, h, i: (b, h // G, 0, 0)),
            pl.BlockSpec((1, 1, Sk, D), lambda b, h, i: (b, h // G, 0, 0)),
            pl.BlockSpec((1, 1, Bq, D), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, Bq), lambda b, h, i: (b, h, i)),
            pl.BlockSpec((1, 1, Bq), lambda b, h, i: (b, h, i)),
        ],
        out_specs=pl.BlockSpec((1, 1, Bq, D), lambda b, h, i: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
    )(q, k, v, do, lse, delta)

    # dk/dv: grid over kv heads/blocks; q/do/lse viewed with the GQA group
    # axis exposed: (B, Hkv, G, Sq, D) so index maps slice per kv head
    qg = q.reshape(B, Hkv, G, Sq, D)
    dog = do.reshape(B, Hkv, G, Sq, D)
    lseg = lse.reshape(B, Hkv, G, Sq)
    deltag = delta.reshape(B, Hkv, G, Sq)
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, Bq=Bq, Bk=Bk, Sq=Sq, D=D, G=G, kind=kind,
                          window=window, softcap=softcap, scale=scale),
        grid=(B, Hkv, Sk // Bk),
        in_specs=[
            pl.BlockSpec((1, 1, G, Sq, D), lambda b, h, j: (b, h, 0, 0, 0)),
            pl.BlockSpec((1, 1, Bk, D), lambda b, h, j: (b, h, j, 0)),
            pl.BlockSpec((1, 1, Bk, D), lambda b, h, j: (b, h, j, 0)),
            pl.BlockSpec((1, 1, G, Sq, D), lambda b, h, j: (b, h, 0, 0, 0)),
            pl.BlockSpec((1, 1, G, Sq), lambda b, h, j: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, G, Sq), lambda b, h, j: (b, h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, Bk, D), lambda b, h, j: (b, h, j, 0)),
            pl.BlockSpec((1, 1, Bk, D), lambda b, h, j: (b, h, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        interpret=interpret,
    )(qg, k, v, dog, lseg, deltag)
    return dq, dk, dv


@functools.partial(
    jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9)
)
def flash_attention(q, k, v, kind="causal", window=0, softcap=0.0,
                    scale=None, Bq=512, Bk=512, interpret=False):
    """q (B,H,Sq,D); k,v (B,Hkv,Sk,D). Returns (B,H,Sq,D)."""
    o, _ = _fwd(q, k, v, kind, window, softcap,
                scale if scale is not None else q.shape[-1] ** -0.5, Bq, Bk, interpret)
    return o


def _fa_fwd(q, k, v, kind, window, softcap, scale, Bq, Bk, interpret):
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    o, lse = _fwd(q, k, v, kind, window, softcap, scale, Bq, Bk, interpret)
    return o, (q, k, v, o, lse)


def _fa_bwd(kind, window, softcap, scale, Bq, Bk, interpret, res, do):
    q, k, v, o, lse = res
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    dq, dk, dv = _bwd(q, k, v, o, lse, do, kind, window, softcap, scale, Bq, Bk, interpret)
    return dq, dk, dv


flash_attention.defvjp(_fa_fwd, _fa_bwd)
