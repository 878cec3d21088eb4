"""The comparison that decides ``correct``.

Every request due in the window is judged by what it says:

* ``missing``: requests with no answer a minute after the window closed, or
  whose answer was an error;
* ``infeasible``: answers that do not assign exactly ``T`` tasks within every
  client's limits (a request handed another request's rows fails here);
* ``objective_rel_gap``: the largest gap between the objective the service
  returned (plus the fixed cost of the lower limits, which its objectives
  leave out) and the float64 cost of the schedule it returned, relative to
  that cost;

and a sample drawn from the seed, with the largest request in it, against the
configuration's plain reference (:mod:`chipbench.reference`):

* ``cost_gap_mj``: the largest amount by which a served schedule costs more
  than the reference's optimum.

Beside the answers, :func:`path_numbers` holds a run to the path its
configuration names, so that a cell cannot stop exercising what it exists
for:

* ``compiles_in_window``: engine executables built inside the window;
* ``off_path_flushes``: flushes in the window that ran in a bucket of
  another kind than the configuration's ``buckets`` (``dp`` for the fused DP
  and its min-plus kernel, ``marginal`` for the selection kernel);
* ``regime_mismatch``: requests whose marginal-cost regime, by the plain
  :func:`~chipbench.reference.regime`, is not the configuration's
  ``regime``; with no off-path flush, an ``increasing`` request has been
  solved by MarIn on the selection kernel (the paper's Table 2).

Each number has its limit in the configuration's ``check.limits``.
"""

from __future__ import annotations

import numpy as np

from . import reference
from .traffic import _rng

NUMBERS = ("missing", "infeasible", "objective_rel_gap", "cost_gap_mj")
PATH_NUMBERS = ("compiles_in_window", "off_path_flushes", "regime_mismatch")


def sample(requests, instances, seed: int, size: int):
    """Indices of the answered requests to compare with the reference: a
    seeded draw of ``size`` of them, plus the one with the most work."""
    ok = [i for i, r in enumerate(requests) if r.get("ok")]
    if not ok:
        return []
    rng = _rng(seed, 2)
    pick = set(rng.choice(ok, size=min(size, len(ok)), replace=False).tolist())
    pick.add(max(ok, key=lambda i: instances[i].band_cells()))
    return sorted(pick)


def judge(config: dict, instances, requests, seed: int, here=None):
    """``(correct, numbers)``: each number is ``(name, value, limit)``.

    ``requests[i]`` holds the answer to ``instances[i]``: ``ok``, the
    schedule ``x`` and the returned ``objective``.
    """
    chk = config["check"]
    solve = reference.solver(chk["reference"], here)
    missing = infeasible = 0
    rel_gap = 0.0
    for inst, r in zip(instances, requests):
        if not r.get("ok"):
            missing += 1
            continue
        x = np.asarray(r["x"])
        if not reference.feasible(inst.T, inst.lower, inst.upper, x):
            infeasible += 1
            continue
        cost = reference.total_cost(inst.tables, x)
        # the service returns objectives with the lower limits shifted out
        fixed = sum(float(t[int(lo)]) for t, lo in zip(inst.tables, inst.lower))
        objective = float(r["objective"]) + fixed
        rel_gap = max(rel_gap, abs(objective - cost) / max(abs(cost), 1.0))
    cost_gap = 0.0
    for i in sample(requests, instances, seed, int(chk["sample"])):
        inst, x = instances[i], np.asarray(requests[i]["x"])
        if not reference.feasible(inst.T, inst.lower, inst.upper, x):
            continue  # counted above
        x_ref, _ = solve(inst.T, inst.lower, inst.upper, inst.tables)
        gap = reference.total_cost(inst.tables, x) - reference.total_cost(inst.tables, x_ref)
        cost_gap = max(cost_gap, gap)
    values = {
        "missing": missing,
        "infeasible": infeasible,
        "objective_rel_gap": rel_gap,
        "cost_gap_mj": cost_gap,
    }
    numbers = [(k, values[k], chk["limits"][k]) for k in NUMBERS]
    return all(v <= lim for _, v, lim in numbers), numbers


def path_numbers(config: dict, record: dict, instances):
    """The run's ``(name, value, limit)`` against the path its configuration
    names (see the module docstring)."""
    memo = {}

    def trend(t, lo, u):
        key = (id(t), int(lo), int(u))  # population clients share tables
        if key not in memo:
            memo[key] = reference.marginal_trend(t, lo, u)
        return memo[key]

    values = {
        "compiles_in_window": record["compiles"],
        "off_path_flushes": sum(
            v for k, v in record["bucket_hits"].items() if k.split(":")[0] != config["buckets"]
        ),
        "regime_mismatch": sum(
            reference.regime(i.lower, i.upper, i.tables, trend) != config["regime"]
            for i in instances
        ),
    }
    return [(k, values[k], config["check"]["limits"][k]) for k in PATH_NUMBERS]
