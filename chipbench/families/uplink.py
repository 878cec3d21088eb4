"""Family ``uplink``: the ``population`` family's phones, each of which also
uploads its model update once in any round it takes part in.

The population, its device classes and compute curves are the
``population`` family's, drawn from the same ``population_seed``. Beside
them ``prepare`` draws each phone's link rate, log-uniform over
``[rate_min_mbps, rate_max_mbps]`` from a stream of its own, so the phones
are the same as in a configuration without uploads. An upload sends
``model_params * bits_per_param`` bits at that rate with transmit power
``power_w``, after Yang et al. (arXiv:1911.02417): ``E_up = P * bits /
rate``, rounded to integer mJ. A phone's table is then ``C(0) = 0`` and
``C(j) = E_up + E_comp(j)`` for ``j >= 1``: its marginals fall at ``j = 2``
and rise after, so every request is of the arbitrary regime and only the
exact DP solves it. Requests are drawn as in ``population``.
"""

from pathlib import Path

import numpy as np

from chipbench.traffic import _rng, load

population = load("families", "population", Path(__file__).resolve().parents[1])
shapes, instances = population.shapes, population.instances


def upload_mj(sizes: dict, up: dict) -> np.ndarray:
    """Per phone of the population, the energy of one upload in integer mJ."""
    rng = _rng(sizes["population_seed"], 1)
    lo, hi = np.log(up["rate_min_mbps"]), np.log(up["rate_max_mbps"])
    rate_bps = 1e6 * np.exp(rng.uniform(lo, hi, size=sizes["population"]))
    bits = float(up["model_params"]) * float(up["bits_per_param"])
    return np.rint(1000.0 * up["power_w"] * bits / rate_bps)


def prepare(config: dict):
    """``(upper, tables)`` of every phone: the population's compute curves
    with the phone's upload added at every ``j >= 1``."""
    upper, compute = population.prepare(config)
    e_up = upload_mj(config["sizes"], config["uplink"])
    tables = [
        np.concatenate([[0.0], e + np.asarray(c[1:], np.float64)]) for c, e in zip(compute, e_up)
    ]
    return upper, tables

