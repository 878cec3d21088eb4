"""Shared neural building blocks (pure JAX, functional params-as-pytrees).

Conventions:
  * params are nested dicts of jnp arrays; scanned layer stacks carry a
    leading ``L`` axis on every leaf.
  * activations default to bf16 compute with fp32 normalization/softmax.
  * weight names are stable — sharding rules in ``launch/sharding.py`` match
    on them (e.g. ``w_in``-like matrices shard (fsdp, tensor)).
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

__all__ = [
    "rms_norm",
    "make_rope",
    "apply_rope",
    "dense_init",
    "attention",
    "gqa_attention",
    "mlp_gated",
    "mlp_act",
    "softcap",
]


def dense_init(key, shape, fan_in: Optional[int] = None, dtype=jnp.float32, scale: float = 1.0):
    fan_in = fan_in if fan_in is not None else shape[0]
    std = scale / max(fan_in, 1) ** 0.5
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape) * std).astype(dtype)


def rms_norm(x: jnp.ndarray, gamma: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    out = x32 * jax.lax.rsqrt(var + eps) * (1.0 + gamma.astype(jnp.float32))
    return out.astype(dt)


def softcap(x: jnp.ndarray, cap: float) -> jnp.ndarray:
    """Gemma-2 style logit soft-capping: cap * tanh(x / cap)."""
    return cap * jnp.tanh(x.astype(jnp.float32) / cap)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------


def make_rope(positions: jnp.ndarray, head_dim: int, base: float = 10000.0):
    """Returns (sin, cos) of shape positions.shape + (head_dim//2,)."""
    half = head_dim // 2
    freqs = base ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    angles = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.sin(angles), jnp.cos(angles)


def apply_rope(x: jnp.ndarray, sin: jnp.ndarray, cos: jnp.ndarray) -> jnp.ndarray:
    """x: (..., S, H, D). sin/cos: (..., S, D/2) broadcast over heads."""
    dt = x.dtype
    x = x.astype(jnp.float32)
    x1, x2 = jnp.split(x, 2, axis=-1)
    s = sin[..., None, :]  # add head axis
    c = cos[..., None, :]
    out = jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)
    return out.astype(dt)


# ---------------------------------------------------------------------------
# Attention (GQA/MQA, causal / sliding-window / prefix-LM / bidirectional,
# optional logit softcap). Einsum formulation so GSPMD shards heads freely.
# ---------------------------------------------------------------------------


def _build_mask(
    q_pos: jnp.ndarray,  # (Sq,)
    kv_pos: jnp.ndarray,  # (Sk,)
    kind: str,
    window: int = 0,
    prefix_len: Optional[jnp.ndarray] = None,  # (B,) or scalar
):
    qp = q_pos[:, None]
    kp = kv_pos[None, :]
    if kind == "bidirectional":
        m = jnp.ones((q_pos.shape[0], kv_pos.shape[0]), bool)
    elif kind == "causal":
        m = kp <= qp
    elif kind == "sliding":
        m = (kp <= qp) & (kp > qp - window)
    elif kind == "prefix":
        causal = kp <= qp
        pl = 0 if prefix_len is None else prefix_len  # None at decode: pure causal
        in_prefix = kp < pl  # attendable by everyone
        m = causal | in_prefix
    else:
        raise ValueError(kind)
    return m


def attention(
    q: jnp.ndarray,  # (B, Sq, H, D)
    k: jnp.ndarray,  # (B, Sk, Hkv, D)
    v: jnp.ndarray,  # (B, Sk, Hkv, D)
    *,
    q_pos: jnp.ndarray,
    kv_pos: jnp.ndarray,
    kind: str = "causal",
    window: int = 0,
    prefix_len: Optional[jnp.ndarray] = None,
    attn_softcap: float = 0.0,
    kv_valid: Optional[jnp.ndarray] = None,  # (B, Sk) bool — cache validity
    scale: Optional[float] = None,
    block_q: int = 0,
    impl: str = "xla",
) -> jnp.ndarray:
    """Grouped-query attention. Returns (B, Sq, H, D).

    ``block_q > 0`` scans over query blocks so the logits tensor is bounded
    at (B, H, block_q, Sk) — the memory-bounded formulation used for the
    large train/prefill shapes (exact math, no online-softmax needed since
    each block sees the full key row).

    ``impl='pallas'`` routes full self-attention (train/prefill, causal /
    sliding / bidirectional, no cache) through the flash-attention Pallas
    kernel — probs never touch HBM. Falls back to XLA for decode/prefix.
    """
    B, Sq, H, D = q.shape
    if (
        impl == "pallas"
        and kind in ("causal", "sliding", "bidirectional")
        and prefix_len is None and kv_valid is None
        and Sq == k.shape[1] and Sq >= 128 and Sq % 128 == 0
        and D == v.shape[-1]
    ):
        from ..kernels.flash_attention import flash_attention

        if jax.default_backend() != "cpu":
            # its blocks break the TPU's (8, 128) tiling rule; only the
            # interpreter runs it
            raise NotImplementedError(
                "attn_impl='pallas' runs the flash-attention kernel in interpret "
                "mode on the CPU only; it does not compile for a TPU"
            )
        bq = min(block_q or 512, Sq)
        o = flash_attention(
            jnp.moveaxis(q, 1, 2), jnp.moveaxis(k, 1, 2), jnp.moveaxis(v, 1, 2),
            kind, window, attn_softcap, scale, bq, min(512, Sq), True,
        )
        return jnp.moveaxis(o, 2, 1)
    if block_q and Sq > block_q and Sq % block_q == 0:
        nb = Sq // block_q
        qb = q.reshape(B, nb, block_q, H, D)
        pb = q_pos.reshape(nb, block_q)

        def body(_, inp):
            qi, pi = inp
            out = attention(
                qi, k, v, q_pos=pi, kv_pos=kv_pos, kind=kind, window=window,
                prefix_len=prefix_len, attn_softcap=attn_softcap,
                kv_valid=kv_valid, scale=scale, block_q=0,
            )
            return None, out

        # checkpoint the block body: without this, scan AD stacks every
        # block's softmax probs/masks for backward (flash-attention-style
        # recompute instead)
        body = jax.checkpoint(body, policy=jax.checkpoint_policies.nothing_saveable)
        _, outs = jax.lax.scan(body, None, (jnp.moveaxis(qb, 1, 0), pb))
        return jnp.moveaxis(outs, 0, 1).reshape(B, Sq, H, v.shape[-1])
    Hkv = k.shape[2]
    G = H // Hkv
    scale = scale if scale is not None else D ** -0.5
    qf = (q * scale).astype(jnp.float32).reshape(B, Sq, Hkv, G, D)
    kf = k.astype(jnp.float32)
    logits = jnp.einsum("bqhgd,bkhd->bhgqk", qf, kf)
    if attn_softcap:
        logits = softcap(logits, attn_softcap)
    mask = _build_mask(q_pos, kv_pos, kind, window, prefix_len)  # (Sq, Sk)
    mask = mask[None, None, None]
    if kv_valid is not None:
        mask = mask & kv_valid[:, None, None, None, :]
    logits = jnp.where(mask, logits, -1e30)
    w = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", w.astype(v.dtype), v)
    return out.reshape(B, Sq, H, v.shape[-1])  # v head dim may differ (MLA)


def gqa_attention(params, x, cfg_heads, *, rope_sincos, kind="causal", window=0,
                  prefix_len=None, attn_softcap=0.0, query_pre_scale=None):
    """Projection + RoPE + attention + out-projection for the common case.

    params: {wq (d,H,hd), wk (d,Hkv,hd), wv (d,Hkv,hd), wo (H,hd,d)}.
    x: (B, S, d). Returns (B, S, d).
    """
    H, Hkv, hd = cfg_heads
    sin, cos = rope_sincos
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, params["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, params["wv"])
    q = apply_rope(q, sin, cos)
    k = apply_rope(k, sin, cos)
    S = x.shape[1]
    pos = jnp.arange(S)
    out = attention(
        q, k, v, q_pos=pos, kv_pos=pos, kind=kind, window=window,
        prefix_len=prefix_len, attn_softcap=attn_softcap, scale=query_pre_scale,
    )
    return jnp.einsum("bshk,hkd->bsd", out, params["wo"])


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------


def mlp_gated(params, x, act=jax.nn.silu):
    """SwiGLU-style: (act(x W_gate) * x W_in) W_out."""
    h = act(jnp.einsum("bsd,df->bsf", x, params["w_gate"])) * jnp.einsum(
        "bsd,df->bsf", x, params["w_in"]
    )
    return jnp.einsum("bsf,fd->bsd", h, params["w_out"])


def mlp_act(params, x, act):
    """Plain two-matrix MLP with activation (gelu / squared-relu / ...)."""
    h = act(jnp.einsum("bsd,df->bsf", x, params["w_in"]))
    return jnp.einsum("bsf,fd->bsd", h, params["w_out"])


def squared_relu(x):
    r = jax.nn.relu(x)
    return r * r
