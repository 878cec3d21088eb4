"""Family ``arbitrary``: cross-silo requests with arbitrary (non-monotone)
integer cost curves, the general (MC)^2MKP case that only the DP solves.

Each request draws ``n`` clients, their upper limits (one at ``u_max``, so
every request fills the band width), lower limits, and a workload that is a
share ``f`` of the clients' spare batches, clipped to ``[t_min, t_max]``.
Each client's table is a fixed cost of ``0..fixed_max`` mJ followed by
integer marginals of ``marginal_min..marginal_max`` mJ, drawn uniformly, so
every DP sum is an integer below 2**24 and float32 arithmetic is exact.
"""

import numpy as np

from chipbench.traffic import Instance


def prepare(config):
    return None


def shapes(sizes: dict, rng, count: int):
    out = []
    for _ in range(count):
        n = int(rng.integers(sizes["n_min"], sizes["n_max"] + 1))
        upper = rng.integers(sizes["u_min"], sizes["u_max"] + 1, size=n)
        upper[int(rng.integers(0, n))] = sizes["u_max"]
        lower = np.minimum(rng.integers(0, sizes["lower_max"] + 1, size=n), upper)
        f = rng.uniform(sizes["f_min"], sizes["f_max"])
        Tp = int(np.clip(np.floor(f * (upper - lower).sum()), sizes["t_min"], sizes["t_max"]))
        out.append((Tp + int(lower.sum()), lower.astype(np.int64), upper.astype(np.int64)))
    return out


def instances(config: dict, shapes, rng, context):
    sizes = config["sizes"]
    out = []
    for T, lower, upper in shapes:
        tables = tuple(
            np.concatenate(
                [
                    [rng.integers(0, sizes["fixed_max"] + 1)],
                    rng.integers(sizes["marginal_min"], sizes["marginal_max"] + 1, size=int(u)),
                ]
            )
            .cumsum()
            .astype(np.float64)
            for u in upper
        )
        out.append(Instance(T=T, lower=lower, upper=upper, tables=tables))
    return out
