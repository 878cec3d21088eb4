"""The fused DP program without the sweep engine: pack, solve and restore
with no shape bucketing. The engine's bucketed solves are held bit-identical
to it, since padding to a bucket is inert."""

import jax.numpy as jnp
import numpy as np

from repro.core.jax_dp import pack_problem, solve_fused_batch_jax
from repro.core.problem import ProblemBatch, remove_lower_limits, restore_lower_limits
from repro.kernels import resolve_backend


def solve_unbucketed(problems, backend="auto"):
    """``(B, n)`` int64 schedules of a sequence of problems or a
    :class:`ProblemBatch`, from :func:`solve_fused_batch_jax` on the
    unpadded pack."""
    batch = problems if isinstance(problems, ProblemBatch) else ProblemBatch.from_problems(problems)
    batch.validate()
    b0 = remove_lower_limits(batch)
    X, _ = solve_fused_batch_jax(
        pack_problem(b0),
        jnp.asarray(b0.T, jnp.int32),
        int(b0.T.max()),
        backend=resolve_backend(backend),
    )
    return restore_lower_limits(batch, np.asarray(X).astype(np.int64))


def solve_one_unbucketed(problem, backend="auto"):
    """The ``(n,)`` schedule of one problem (:func:`solve_unbucketed`)."""
    return solve_unbucketed([problem], backend)[0]
