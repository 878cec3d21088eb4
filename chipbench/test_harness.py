"""The harness at a tiny size on the CPU: cells, mixes and metrics found by
name from files alone, a cell added without editing a file, and the
comparison that decides ``correct`` failing when the timed path is broken."""

import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

from chipbench import harness

REPO = Path(__file__).resolve().parents[1]
HERE = Path(__file__).resolve().parent

TINY_SILO = {
    "name": "tiny-silo",
    "family": "arbitrary",
    "sizes": {
        "n_min": 3, "n_max": 4, "u_min": 8, "u_max": 15, "lower_max": 2, "fixed_max": 99,
        "marginal_min": 1, "marginal_max": 49, "f_min": 0.5, "f_max": 0.8,
        "t_min": 9, "t_max": 16,
    },
    "regime": "arbitrary",
    "buckets": "dp",
    "service": {"max_batch": 4, "max_delay_s": 0.002, "split_regimes": False},
    "check": {
        "reference": "dp", "sample": 8,
        "limits": {"missing": 0, "infeasible": 0, "objective_rel_gap": 0.0, "cost_gap_mj": 0.0,
                   "compiles_in_window": 0, "off_path_flushes": 0, "regime_mismatch": 0},
    },
}
TINY_POP = {
    "name": "tiny-pop",
    "family": "population",
    "sizes": {
        "population": 40, "population_seed": 0, "samples_mean": 60.0, "samples_std": 20.0,
        "samples_min": 1, "samples_max": 150, "batch_size": 10, "eligible_min": 0.6,
        "eligible_max": 0.75, "f_min": 0.3, "f_max": 0.7,
    },
    "classes": {
        "phone_lo": {"per_task": 8.0, "regime": "superlinear", "b": 0.35, "p": 1.6},
        "tablet": {"per_task": 2.2, "regime": "linear"},
    },
    "class_mix": {"phone_lo": 0.7, "tablet": 0.3},
    "regime": "increasing",
    "buckets": "marginal",
    "service": {"max_batch": 4, "max_delay_s": 0.002, "split_regimes": True},
    "check": {
        "reference": "marin", "sample": 8,
        "limits": {"missing": 0, "infeasible": 0, "objective_rel_gap": 1e-5, "cost_gap_mj": 0.0,
                   "compiles_in_window": 0, "off_path_flushes": 0, "regime_mismatch": 0},
    },
}
MIX = {"arrivals": "poisson", "rate_per_s": 30.0, "shape_seed": 7}


def _cell(name, config):
    return {"name": name, "config": config, "traffic": "tiny-mix", "chips": 1, "why": "test"}


def _spec(configs, cells):
    metric = {"better": "lower", "source": "host_clock"}
    return {
        "command": ["python3", "chipbench/run.py"],
        "paths": ["chipbench"],
        "run_seconds": 1,
        "configs": [
            {"name": c, "source": "test", "file": f"chipbench/configs/{c}.json", "reduced": [],
             "why": "test"}
            for c in configs
        ],
        "workloads": cells,
        "end_to_end": [
            {"name": "setup_s", "unit": "s", "bound": 0.25, **metric},
            {"name": "plan_p50_ms", "unit": "ms", "bound": 0.1, **metric},
            {"name": "plan_p95_ms", "unit": "ms", "bound": 0.1, **metric},
        ],
        "per_layer": [
            {"name": "submit_ms", "unit": "ms", "layer": "front door", "moves": "plan_p95_ms",
             **metric},
            {"name": "answered", "unit": "1", "layer": "service", "moves": "plan_p95_ms",
             **metric},
        ],
    }


def _write(path: Path, obj) -> None:
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(obj))


@pytest.fixture(autouse=True)
def no_chip_check(monkeypatch):
    """These runs use the CPU: the harness's look for a TPU is skipped."""
    monkeypatch.setattr(harness, "check_devices", lambda chips: None)


@pytest.fixture
def root(tmp_path):
    """A checkout holding one tiny cross-silo cell, its mix and the
    benchmark's modules; ``answered`` is a reader that exists only here."""
    for kind in ("metrics", "families", "arrivals"):
        shutil.copytree(HERE / kind, tmp_path / "chipbench" / kind)
    (tmp_path / "chipbench" / "metrics" / "answered.py").write_text(
        "def read(record):\n    return sum(1 for r in record['requests'] if r.get('ok'))\n"
    )
    _write(tmp_path / "chipbench" / "configs" / "tiny-silo.json", TINY_SILO)
    _write(tmp_path / "chipbench" / "traffic" / "tiny-mix.json", MIX)
    _write(tmp_path / "BENCHMARK.json", _spec(["tiny-silo"], [_cell("silo.steady", "tiny-silo")]))
    return tmp_path


def _run(root, cell, trace=False, seed=12345678901):
    with open(os.devnull, "w") as log:
        return harness.run(cell, seed, 1.0, trace, time.perf_counter(), root=root, log=log)


def test_cell_config_mix_and_metric_found_by_name(root):
    out = _run(root, "silo.steady")
    assert out["correct"] is True
    assert out["attempted"] == 30 and out["failed"] == 0
    assert set(out["metrics"]) == {"setup_s", "plan_p50_ms", "plan_p95_ms"}
    assert list(out)[-1] == "checks"
    traced = _run(root, "silo.steady", trace=True)
    assert traced["metrics"]["answered"]["value"] == traced["attempted"]
    assert traced["metrics"]["answered"]["unit"] == "1"


def test_a_cell_is_added_by_adding_files_only(root):
    """A second configuration, mix entry and metric: new files and new
    entries in BENCHMARK.json, no edit to any file of the harness."""
    _write(root / "chipbench" / "configs" / "tiny-pop.json", TINY_POP)
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"] += _spec(["tiny-pop"], [])["configs"]
    spec["workloads"].append(_cell("pop.steady", "tiny-pop"))
    _write(root / "BENCHMARK.json", spec)
    out = _run(root, "pop.steady")
    assert out["correct"] is True and out["attempted"] == 30
    assert out["metrics"]["plan_p95_ms"]["value"] >= out["metrics"]["plan_p50_ms"]["value"] > 0


def test_unknown_names_are_errors(root):
    with pytest.raises(harness.BenchmarkError):
        _run(root, "no.such.cell")
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["end_to_end"].append({"name": "no_reader", "unit": "s", "better": "lower",
                               "bound": 0.1, "source": "host_clock"})
    _write(root / "BENCHMARK.json", spec)
    with pytest.raises(harness.BenchmarkError):
        _run(root, "silo.steady")


def test_run_without_a_tpu_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("ALLOW_MULTIPLE_LIBTPU_LOAD", None)
    proc = subprocess.run(
        [sys.executable, str(HERE / "run.py"), "--workload", "xsilo.steady", "--seed", "3",
         "--seconds", "1", "--trace", "0"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert not any(line.startswith("{") for line in proc.stdout.splitlines())


class _FaultyHandle:
    """A flush whose answers are broken where they are produced."""

    def __init__(self, handle, fault, first_row):
        self._handle, self._fault, self._first = handle, fault, first_row

    def done(self):
        return self._handle.done()

    def objectives(self):
        return self._handle.objectives()

    def result(self):
        X = np.array(self._handle.result())
        if self._fault == "altered":  # one task more on the first client
            X[:, 0] += 1
        else:  # every other row of the stream left out
            X[(self._first + np.arange(len(X))) % 2 == 1] = 0
        return X


@pytest.mark.parametrize("fault", ["altered", "half_left_out"])
@pytest.mark.parametrize("cell,config", [("silo.steady", TINY_SILO), ("pop.steady", TINY_POP)])
def test_broken_timed_path_is_not_correct(root, monkeypatch, fault, cell, config):
    _write(root / "chipbench" / "configs" / f"{config['name']}.json", config)
    _write(root / "BENCHMARK.json", _spec([config["name"]], [_cell(cell, config["name"])]))
    real = harness.TimedEngine.dispatch
    rows = [0]

    def dispatch(self, problems, split_regimes=False):
        handle = real(self, problems, split_regimes=split_regimes)
        first, rows[0] = rows[0], rows[0] + len(problems.T)
        return _FaultyHandle(handle, fault, first)

    monkeypatch.setattr(harness.TimedEngine, "dispatch", dispatch)
    out = _run(root, cell)
    assert out["correct"] is False
    assert out["checks"]["infeasible"]["value"] > 0


def test_a_closed_loop_mix_is_added_by_files_only(root):
    """A mix that names the closed-loop arrival module: callers that send
    their next request when their last answer comes."""
    _write(root / "chipbench" / "traffic" / "tiny-closed.json",
           {"arrivals": "closed_loop", "callers": 3, "max_requests": 2000, "shape_seed": 7})
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["workloads"].append(dict(_cell("silo.closed", "tiny-silo"), traffic="tiny-closed"))
    _write(root / "BENCHMARK.json", spec)
    out = _run(root, "silo.closed")
    assert out["correct"] is True and 3 <= out["attempted"] < 2000 and out["failed"] == 0
    assert out["metrics"]["plan_p50_ms"]["value"] > 0


FAMILY = '''
import numpy as np
from chipbench.traffic import Instance


def prepare(config):
    return None


def shapes(sizes, rng, count):
    return [int(rng.integers(2, 2 * sizes["u"])) for _ in range(count)]


def instances(config, shapes, rng, context):
    u = config["sizes"]["u"]
    return [Instance(T=T, lower=np.zeros(2, np.int64), upper=np.full(2, u, np.int64),
                     tables=tuple(rng.integers(1, 50, size=u + 1).cumsum().astype(float)
                                  for _ in range(2)))
            for T in shapes]
'''
REFERENCE = '''
import numpy as np


def solve(T, lower, upper, tables, dtype=np.float64):
    best = min(range(max(0, T - int(upper[1])), min(T, int(upper[0])) + 1),
               key=lambda a: float(tables[0][a]) + float(tables[1][T - a]))
    x = np.array([best, T - best])
    return x, float(tables[0][best]) + float(tables[1][T - best])
'''
ARRIVALS = '''
import numpy as np


def due_times(mix, seconds, rng):
    bursts = int(seconds / mix["period_s"])
    return np.repeat(np.arange(bursts) * mix["period_s"], mix["burst"])


def drive(window, mix, due_s):
    for i, due in enumerate(due_s):
        window.wait_until(float(due))
        window.send(i)
'''


def test_a_family_reference_and_arrival_process_are_added_by_files_only(root):
    """Code that a new deployment or load needs goes into modules of its own,
    found by the names its files give."""
    here = root / "chipbench"
    (here / "families" / "pairs.py").write_text(FAMILY)
    (here / "references").mkdir()
    (here / "references" / "brute.py").write_text(REFERENCE)
    (here / "arrivals" / "burst.py").write_text(ARRIVALS)
    config = dict(TINY_SILO, name="tiny-pairs", family="pairs", sizes={"u": 7},
                  check=dict(TINY_SILO["check"], reference="brute"))
    _write(here / "configs" / "tiny-pairs.json", config)
    _write(here / "traffic" / "tiny-burst.json",
           {"arrivals": "burst", "period_s": 0.25, "burst": 6, "shape_seed": 3})
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"] += _spec(["tiny-pairs"], [])["configs"]
    spec["workloads"].append(dict(_cell("pairs.burst", "tiny-pairs"), traffic="tiny-burst"))
    _write(root / "BENCHMARK.json", spec)
    out = _run(root, "pairs.burst")
    assert out["correct"] is True and out["attempted"] == 24
    assert out["checks"]["cost_gap_mj"]["value"] == 0


@pytest.mark.parametrize("fault", ["compile_in_window", "off_path", "regime"])
def test_a_run_off_its_configured_path_is_not_correct(root, monkeypatch, fault):
    """A run that compiles inside its window, flushes into buckets of another
    kind than its configuration names, or is sent requests of another regime
    is not correct, even where every answer is."""
    config, cell, number = dict(TINY_POP), "pop.steady", "off_path_flushes"
    if fault == "compile_in_window":
        monkeypatch.setattr(harness.Session, "warm", lambda self, plan: None)
        number = "compiles_in_window"
    elif fault == "off_path":
        config["buckets"] = "dp"
    else:
        config, cell, number = dict(TINY_SILO, regime="increasing"), "silo.steady", "regime_mismatch"
    _write(root / "chipbench" / "configs" / f"{config['name']}.json", config)
    _write(root / "BENCHMARK.json", _spec([config["name"]], [_cell(cell, config["name"])]))
    out = _run(root, cell)
    assert out["correct"] is False
    assert out["checks"][number]["value"] > 0
    assert out["checks"]["missing"]["value"] == out["checks"]["cost_gap_mj"]["value"] == 0


def test_closed_loop_callers_send_each_request_once():
    """Many callers share the request order: none is lost or sent twice."""
    from chipbench.traffic import load

    closed = load("arrivals", "closed_loop")

    class FakeWindow:
        seconds = 10.0

        def __init__(self):
            self.sent, self.t0 = [], time.perf_counter()

        def now(self):
            return 0.0 if len(self.sent) < 3000 else self.seconds

        def call(self, i):
            self.sent.append(i)

    window, old = FakeWindow(), sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        closed.drive(window, {"callers": 32}, np.full(5000, np.nan))
    finally:
        sys.setswitchinterval(old)
    assert len(window.sent) == len(set(window.sent)) >= 3000
    assert set(window.sent) == set(range(len(window.sent)))


def test_a_refused_request_is_missing_not_a_crash(root, monkeypatch):
    from repro.serve import SchedulerService

    real, calls = SchedulerService.submit, [0]

    def submit(self, problems, split_regimes=False, timeout=None):
        calls[0] += 1
        if calls[0] == 5:
            raise RuntimeError("refused")
        return real(self, problems, split_regimes=split_regimes, timeout=timeout)

    monkeypatch.setattr(SchedulerService, "submit", submit)
    out = _run(root, "silo.steady")
    assert out["correct"] is False and out["failed"] == 1
    assert out["checks"]["missing"]["value"] == 1 and out["attempted"] == 30
