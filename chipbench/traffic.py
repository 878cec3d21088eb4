"""The benchmark's traffic: requests from a configuration file and a mix file,
made by the modules those files name.

A configuration (``configs/<name>.json``) gives the deployment: its
``family`` names the module ``families/<family>.py`` that draws the clients,
their cost curves and their workloads. A mix (``traffic/<name>.json``) gives
the load: its ``arrivals`` names the module ``arrivals/<arrivals>.py`` that
sets when requests are due and drives the window's sending. A new deployment
or load that needs code adds such a module; one that needs none adds data
only.

A family module defines::

    prepare(config) -> context            # what every plan of a run shares, or None
    shapes(sizes, rng, count) -> list     # sizes of `count` requests
    instances(config, shapes, rng, context) -> list[Instance]

and an arrival module::

    due_times(mix, seconds, rng) -> np.ndarray   # one per request; NaN: due when sent
    drive(window, mix, due_s) -> None            # sends them (harness.Window)

Work is the same on every seed. The due times and the sizes of the requests
(clients, limits, workload) are drawn once from the mix's ``shape_seed``;
``--seed`` only deals the sizes to the arrivals in another order and draws the
cost values and, for a population, which clients each request plans. So two
seeds differ in what they ask, not in how much or when.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import re
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent


@dataclasses.dataclass(frozen=True)
class Instance:
    """One planning request: schedule ``T`` tasks over clients with limits
    ``[lower_i, upper_i]`` and cost tables ``tables[i][j] = C_i(j)`` in
    integer mJ, ``j = 0..upper_i``."""

    T: int
    lower: np.ndarray
    upper: np.ndarray
    tables: tuple

    @property
    def n(self) -> int:
        return len(self.tables)

    def band_cells(self) -> int:
        """Cells of the DP's banded min-plus work that this instance needs:
        ``(T' + 1) * (U_i - L_i + 1)`` summed over its clients, where ``T'``
        is the workload with the lower limits shifted out."""
        Tp = self.T - int(self.lower.sum())
        return int((Tp + 1) * (self.upper - self.lower + 1).sum())


@dataclasses.dataclass(frozen=True)
class Plan:
    """Requests in the order they are due, ``due_s`` seconds after the window
    opens (NaN where the arrival module sets the time when it sends)."""

    due_s: np.ndarray
    instances: list


def _rng(*keys) -> np.random.Generator:
    """A generator keyed by non-negative integers of any size."""
    return np.random.default_rng([int(k) for k in keys])


def load(kind: str, name: str, here: Path = HERE):
    """The module ``<here>/<kind>/<name>.py``; raises ``LookupError`` where
    there is none."""
    path = Path(here) / kind / f"{name}.py"
    if not path.is_file():
        raise LookupError(f"no {kind} module named {name!r} at {path}")
    key = re.sub(r"\W", "_", f"chipbench_{kind}_{name}")
    spec = importlib.util.spec_from_file_location(key, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Traffic:
    """A configuration and a mix, with the family and arrival modules they
    name, found under ``here``. The family's shared context (a population)
    is made once, in the constructor."""

    def __init__(self, config: dict, mix: dict, here: Path = HERE):
        self.config, self.mix = config, mix
        self.family = load("families", config["family"], here)
        self.arrivals = load("arrivals", mix["arrivals"], here)
        self.context = self.family.prepare(config)

    def plan(self, seed: int, seconds: float, mix: dict | None = None) -> Plan:
        """The requests of one run from ``seed``; ``mix`` overrides the mix's
        parameters (a rate sweep)."""
        mix = self.mix if mix is None else mix
        base = _rng(mix["shape_seed"])
        due = np.asarray(self.arrivals.due_times(mix, float(seconds), base), dtype=np.float64)
        K = len(due)
        shapes = self.family.shapes(self.config["sizes"], base, K)
        run = _rng(seed, 1)
        shapes = [shapes[i] for i in run.permutation(K)]
        instances = self.family.instances(self.config, shapes, run, self.context)
        return Plan(due_s=due, instances=instances)


def make_plan(config: dict, mix: dict, seed: int, seconds: float, here: Path = HERE) -> Plan:
    """One run's requests, for a caller that makes a single plan."""
    return Traffic(config, mix, here).plan(seed, seconds)
