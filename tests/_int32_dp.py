"""The fused DP as it was before its argmin matrix was narrowed: the min-plus
kernels' int32 argmins stacked as returned, and a backtrack over a reversed
copy. The oracle the narrowed argmins are held bit-identical to."""

import functools

import jax
import jax.numpy as jnp

from repro.kernels import BIG, minplus_step_batch


@functools.partial(jax.jit, static_argnames=("T", "backend"))
def fused_int32(costs, t_star, T, backend):
    """``(X, K_last)`` of the ``(B, n, W)`` cost stack at workloads ``t_star``."""
    B = costs.shape[0]
    k0 = jnp.full((B, T + 1), BIG, jnp.float32).at[:, 0].set(0.0)
    k_last, I = jax.lax.scan(
        lambda k, c: minplus_step_batch(k, c, backend=backend), k0, jnp.swapaxes(costs, 0, 1)
    )

    def back(t, irow):
        j = jnp.take_along_axis(irow, t[:, None], axis=1)[:, 0]
        return t - j, j

    _, xs_rev = jax.lax.scan(back, t_star, I[::-1])
    return jnp.swapaxes(xs_rev[::-1], 0, 1), k_last
