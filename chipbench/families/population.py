"""Family ``population``: cross-device rounds over one fixed population of
clients with monotone cost curves.

The population (``prepare``) is drawn from the configuration's own
``population_seed``: per client its sample count, normal to the source's mean
and deviation and redrawn outside ``[samples_min, samples_max]``, its upper
limit ``ceil(samples / batch_size)`` batches, and a device class from
``class_mix``. A class's curve is its energy over ``0..u`` batches in integer
mJ with rounded marginals, so a class with non-decreasing marginals keeps
them. Each request plans the eligible share of the population, drawn per
request, and asks for a share ``f`` of their batches.
"""

import numpy as np

from chipbench.traffic import Instance, _rng


def _curve_mj(spec: dict, u: int) -> np.ndarray:
    j = np.arange(u + 1, dtype=np.float64)
    joules = spec["per_task"] * j
    if spec["regime"] == "superlinear":
        joules = joules + spec["b"] * np.power(j, spec["p"])
    elif spec["regime"] != "linear":
        raise ValueError(f"unsupported device regime {spec['regime']!r}")
    marg = np.rint(np.diff(joules) * 1000.0)
    return np.concatenate([[0.0], np.cumsum(marg)])


def prepare(config: dict):
    """``(upper, tables)`` of every client of the population. Clients of one
    class and limit share one table object."""
    sizes, classes, mix = config["sizes"], config["classes"], config["class_mix"]
    rng = _rng(sizes["population_seed"])
    P = sizes["population"]
    samples = rng.normal(sizes["samples_mean"], sizes["samples_std"], size=P)
    while np.any(bad := (samples < sizes["samples_min"]) | (samples > sizes["samples_max"])):
        samples[bad] = rng.normal(sizes["samples_mean"], sizes["samples_std"], size=int(bad.sum()))
    upper = np.ceil(np.rint(samples) / sizes["batch_size"]).astype(np.int64)
    names = list(mix)
    kind = rng.choice(len(names), size=P, p=[mix[k] for k in names])
    curves = {}
    tables = []
    for u, k in zip(upper, kind):
        key = (names[k], int(u))
        if key not in curves:
            curves[key] = _curve_mj(classes[names[k]], int(u))
        tables.append(curves[key])
    return upper, tables


def shapes(sizes: dict, rng, count: int):
    """Per request the number of eligible clients and the share of their
    batches the round asks for."""
    P = sizes["population"]
    return [
        (
            int(round(rng.uniform(sizes["eligible_min"], sizes["eligible_max"]) * P)),
            float(rng.uniform(sizes["f_min"], sizes["f_max"])),
        )
        for _ in range(count)
    ]


def instances(config: dict, shapes, rng, context):
    upper, tables = context
    out = []
    for m, f in shapes:
        idx = np.sort(rng.choice(len(upper), size=m, replace=False))
        u = upper[idx]
        out.append(
            Instance(
                T=int(np.floor(f * u.sum())),
                lower=np.zeros(m, dtype=np.int64),
                upper=u,
                tables=tuple(tables[i] for i in idx),
            )
        )
    return out
