"""Kernels for the framework's compute hot-spots, with per-hardware dispatch.

``blocked``: tiled jnp min-plus — the CPU production backend (cache-blocked
BT x BW walk of the banded tropical convolution). ``minplus``: the Pallas
TPU kernel (VMEM-budget-tuned output tiles). ``ref``: the dense jnp oracle
used by the correctness sweeps. ``ops`` exposes the dispatching wrappers — ``backend="auto"``
selects per ``jax.default_backend()``.

``flash_attention``: FlashAttention-2-style fused attention (fwd + bwd) —
attention probabilities never touch HBM; selected via ``attn_impl='pallas'``.
"""

from .blocked import auto_block_sizes, minplus_blocked, minplus_blocked_batch
from .flash_attention import flash_attention
from .minplus import minplus_pallas, minplus_pallas_batch, tpu_tuned_bt
from .ops import BIG, DISPATCH_TABLE, minplus_step, minplus_step_batch, resolve_backend
from .ref import minplus_step_ref, minplus_step_ref_batch

__all__ = [
    "minplus_step",
    "minplus_step_batch",
    "minplus_blocked",
    "minplus_blocked_batch",
    "minplus_pallas",
    "minplus_pallas_batch",
    "minplus_step_ref",
    "minplus_step_ref_batch",
    "auto_block_sizes",
    "tpu_tuned_bt",
    "resolve_backend",
    "DISPATCH_TABLE",
    "BIG",
    "flash_attention",
]
