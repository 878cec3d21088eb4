"""The control of the comparison that decides ``correct``: the
configuration's plain reference, computed in bfloat16 (the precision below
the float32 the configurations state) and put in the program's place. Its
answers go through :func:`chipbench.check.judge` exactly as the program's
do, and must come out as not correct.

The control answers the requests that a run's check compares with the
reference (the seeded sample and the largest request); the program is not
involved, so it runs wherever the references run.
"""

from __future__ import annotations

import ml_dtypes
import numpy as np

from . import check, reference

BF16 = ml_dtypes.bfloat16


def answers(config: dict, plan, seed: int, here=None):
    """``(instances, requests)`` of the sampled requests of ``plan``, each
    answered by the reference in bfloat16."""
    solve = reference.solver(config["check"]["reference"], here)
    everyone = [{"ok": True} for _ in plan.instances]
    picked = check.sample(everyone, plan.instances, seed, int(config["check"]["sample"]))
    instances, requests = [], []
    for i in picked:
        inst = plan.instances[i]
        x, objective = solve(inst.T, inst.lower, inst.upper, inst.tables, dtype=BF16)
        fixed = sum(float(t[int(lo)]) for t, lo in zip(inst.tables, inst.lower))
        instances.append(inst)
        # objectives are returned with the lower limits shifted out
        requests.append({"ok": True, "x": np.asarray(x), "objective": objective - fixed})
    return instances, requests


def judge(config: dict, plan, seed: int, here=None):
    """The control's ``(correct, numbers)`` on ``plan``'s sampled requests."""
    instances, requests = answers(config, plan, seed, here)
    return check.judge(config, instances, requests, seed, here)
