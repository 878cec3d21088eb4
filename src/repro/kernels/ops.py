"""Public entry points for the kernels package: per-hardware dispatch.

``minplus_step(kprev, cost, backend=...)`` / ``minplus_step_batch`` select
the min-plus implementation. ``backend="auto"`` (the default everywhere —
``schedule_batch``, ``deadline_sweep``, the sweep engine, FL servers)
resolves through :data:`DISPATCH_TABLE` keyed on ``jax.default_backend()``:

  platform | backend        | implementation
  ---------|----------------|------------------------------------------------
  cpu      | ``blocked``    | tiled jnp (`kernels/blocked.py`) — cache-blocked
           |                | BT x BW walk, ~4-8x over the dense oracle
  tpu      | ``pallas_tpu`` | Pallas TPU kernel (`kernels/minplus.py`),
           |                | compiled by Mosaic, ``BT`` checked against VMEM

Any other platform, a GPU among them, raises: no backend is chosen for a
device nobody has compiled for. The dense reference (``backend="ref"``) is
retained as the small-shape oracle every backend is validated against.
``backend="pallas"`` names the TPU kernel without naming the mode: on the
CPU it runs in interpret mode (kernel debugging in tests), on a TPU it
resolves to the compiled ``pallas_tpu``, and elsewhere it raises.
Resolution happens at Python/trace time (``jax.default_backend()`` is not
a traced value), so "auto" and its resolved backend share jit caches when
callers resolve before specializing — see :func:`resolve_backend`.

Both entry points take an optional ``band``: each row's band bound, one
past its last finite cost entry (``None`` means the whole width ``W``). The
Pallas TPU kernel ends its band loop there
(:func:`~repro.kernels.minplus.band_steps`); the other backends walk the
whole width. Every backend gives the same answer either way, since a step
whose cost is BIG never wins.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .blocked import minplus_blocked, minplus_blocked_batch
from .minplus import minplus_pallas, minplus_pallas_batch, tpu_tuned_bt
from .ref import BIG, minplus_step_ref, minplus_step_ref_batch

__all__ = [
    "minplus_step",
    "minplus_step_batch",
    "resolve_backend",
    "DISPATCH_TABLE",
    "BACKENDS",
    "BIG",
]

# jax.default_backend() platform -> kernel backend
DISPATCH_TABLE = {"cpu": "blocked", "tpu": "pallas_tpu"}

BACKENDS = ("ref", "blocked", "pallas", "pallas_tpu")


def resolve_backend(backend: str | None = "auto") -> str:
    """Concrete backend name for ``backend`` (``None``/"auto" dispatch per
    hardware; "pallas" is the interpreted kernel on the CPU and the compiled
    one on a TPU). Callers that use the backend as a jit static argument or a
    cache key should resolve first so "auto" shares compilations with its
    resolved name."""
    platform = jax.default_backend()
    if backend is None or backend == "auto":
        if platform not in DISPATCH_TABLE:
            raise ValueError(
                f"no min-plus backend for platform {platform!r}; "
                f"known platforms: {sorted(DISPATCH_TABLE)}"
            )
        return DISPATCH_TABLE[platform]
    if backend not in BACKENDS:
        raise ValueError(f"unknown backend {backend!r}; options: auto, {BACKENDS}")
    if backend == "pallas" and platform != "cpu":
        # interpret mode is for CPU tests only; on a TPU the kernel compiles
        if platform == "tpu":
            return "pallas_tpu"
        raise ValueError(f'backend "pallas" runs on cpu or tpu, not {platform!r}')
    return backend


def minplus_step(kprev: jnp.ndarray, cost: jnp.ndarray, backend: str = "auto", band=None):
    """One DP row update: ``kprev (T+1,)``, ``cost (W,)``, ``band`` a scalar
    bound or ``None``."""
    backend = resolve_backend(backend)
    if backend == "ref":
        return minplus_step_ref(kprev, cost)
    if backend == "blocked":
        return minplus_blocked(kprev, cost)
    if backend in ("pallas", "pallas_tpu"):
        bt = tpu_tuned_bt(kprev.shape[0], cost.shape[0])
        return minplus_pallas(kprev, cost, band, BT=bt, interpret=backend == "pallas")
    raise ValueError(f"unknown backend {backend!r}")


def minplus_step_batch(
    kprev: jnp.ndarray, cost: jnp.ndarray, backend: str = "auto", band=None
):
    """Batched row update: ``kprev (B, T+1)``, ``cost (B, W)``, ``band (B,)``
    or ``None``."""
    backend = resolve_backend(backend)
    if backend == "ref":
        return minplus_step_ref_batch(kprev, cost)
    if backend == "blocked":
        return minplus_blocked_batch(kprev, cost)
    if backend in ("pallas", "pallas_tpu"):
        bt = tpu_tuned_bt(kprev.shape[1], cost.shape[1])
        return minplus_pallas_batch(kprev, cost, band, BT=bt, interpret=backend == "pallas")
    raise ValueError(f"unknown backend {backend!r}")
