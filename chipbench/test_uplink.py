"""The uplink deployment, its exact reference and the DP row metric, on the
CPU: the family's phones and uploads, every request in the arbitrary regime,
the blocked reference against the plain DP, limits that follow the
configuration's own derivation, a served run that is correct, and a planted
fault and the bfloat16 control that are not."""

import json
import os
import shutil
import time
from pathlib import Path

import numpy as np
import pytest

from chipbench import control, harness, reference
from chipbench.traffic import load, make_plan

HERE = Path(__file__).resolve().parent
UPLINK = json.loads((HERE / "configs" / "xdevice-uplink.json").read_text())
DEVICE = json.loads((HERE / "configs" / "xdevice-femnist.json").read_text())
CLOSED = {"arrivals": "closed_loop", "callers": 2, "max_requests": 6, "shape_seed": 3}
U = 2.0**-24  # unit roundoff of float32


def _limits(config):
    """``(objective_rel_gap, cost_gap_mj)`` by the derivation the
    configuration states: gamma_n over at most n = population rows, and
    2 gamma_n / (1 - gamma_n) of the dearest optimum a request can have,
    at most ``f_max`` of the population's cost at its upper limits plus
    one phone's."""
    n = config["sizes"]["population"]
    gamma = n * U / (1 - n * U)
    _, tables = load("families", "uplink").prepare(config)
    full = [float(t[-1]) for t in tables]
    c_max = config["sizes"]["f_max"] * sum(full) + max(full)
    return gamma, 2 * gamma / (1 - gamma) * c_max


def _small(population: int):
    """The configuration over a population of ``population`` phones, with
    limits derived for it as the full configuration derives its own."""
    config = dict(UPLINK, sizes=dict(UPLINK["sizes"], population=population))
    rel, gap = _limits(config)
    limits = dict(UPLINK["check"]["limits"], objective_rel_gap=rel, cost_gap_mj=gap)
    return dict(config, check=dict(UPLINK["check"], limits=limits))


# ---- the deployment -----------------------------------------------------------


def test_phones_are_the_femnist_population_with_an_upload_each():
    fam = load("families", "uplink")
    upper, tables = fam.prepare(UPLINK)
    femnist_upper, compute = load("families", "population").prepare(DEVICE)
    np.testing.assert_array_equal(upper, femnist_upper)
    e_up = fam.upload_mj(UPLINK["sizes"], UPLINK["uplink"])
    up = UPLINK["uplink"]
    joules = up["power_w"] * up["model_params"] * up["bits_per_param"] / 1e6
    lo, hi = 1000 * joules / up["rate_max_mbps"], 1000 * joules / up["rate_min_mbps"]
    assert lo - 1 <= e_up.min() and e_up.max() <= hi + 1  # 12.7-253.6 J
    assert 12_000 < lo < 13_000 and 250_000 < hi < 260_000
    assert np.all(e_up == np.rint(e_up))
    assert abs(np.median(np.log(e_up / lo)) - np.log(20) / 2) < 0.1  # log-uniform rates
    for t, c, e in zip(tables, compute, e_up):
        assert t[0] == 0 and np.array_equal(t[1:], e + c[1:])
    assert max(float(t[-1]) for t in tables) < 2**24  # every entry exact in float32


def test_every_uplink_request_is_arbitrary_and_in_the_cells_buckets():
    from repro.core import Problem
    from repro.core.marginal_jax import select_algorithm_batch
    from repro.core.problem import ProblemBatch

    plan = make_plan(UPLINK, CLOSED, 2**33 + 7, 1.0)
    assert len(plan.instances) == 6 and np.all(np.isnan(plan.due_s))
    for inst in plan.instances:
        assert 2130 <= inst.n <= 3550 and int(inst.upper.max()) < 64
        assert 0.3 * inst.upper.sum() - 1 <= inst.T <= 0.7 * inst.upper.sum() <= 57_318.8
        assert reference.regime(inst.lower, inst.upper, inst.tables) == "arbitrary"
    batch = ProblemBatch.from_problems(
        [Problem(T=i.T, lower=i.lower, upper=i.upper, cost_tables=i.tables) for i in plan.instances]
    )
    assert set(select_algorithm_batch(batch)) == {"dp"}


def test_the_configured_limits_follow_its_derivation():
    rel, gap = _limits(UPLINK)
    limits = UPLINK["check"]["limits"]
    assert rel <= limits["objective_rel_gap"] < 1.001 * rel
    assert gap <= limits["cost_gap_mj"] < gap + 1
    assert f"{rel:.4e}"[:6] in UPLINK["objective_tolerance"]


# ---- the exact reference ------------------------------------------------------


def _random_instances(rng, count):
    """Instances with lower limits and band widths up to 64: arbitrary
    integer tables, as the plain DP takes them."""
    out = []
    for _ in range(count):
        n = int(rng.integers(3, 40))
        upper = rng.integers(1, 64, size=n)
        lower = np.minimum(rng.integers(0, 3, size=n), upper)
        tables = tuple(rng.integers(0, 5000, size=u + 1).astype(np.float64) for u in upper)
        T = int(rng.integers(lower.sum(), upper.sum() + 1))
        out.append((T, lower, upper, tables))
    return out


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_blocked_reference_equals_the_plain_dp(seed):
    solve = load("references", "uplink_dp").solve
    rng = np.random.default_rng(seed)
    cases = _random_instances(rng, 4)
    small = _small(int(rng.integers(50, 301)))
    plan = make_plan(small, CLOSED, seed, 1.0)
    cases += [(i.T, i.lower, i.upper, i.tables) for i in plan.instances[:2]]
    for T, lower, upper, tables in cases:
        x, objective = solve(T, lower, upper, tables)
        x_plain, obj_plain = reference.dp_schedule(T, lower, upper, tables)
        np.testing.assert_array_equal(x, x_plain)
        assert objective == obj_plain == reference.total_cost(tables, x)


def test_reference_is_found_by_the_configurations_name():
    solve = reference.solver(UPLINK["check"]["reference"], HERE)
    tables = ([0.0, 50.0, 60.0], [0.0, 40.0, 90.0])
    x, objective = solve(3, np.zeros(2, np.int64), np.array([2, 2]), tables)
    assert list(x) == [2, 1] and objective == 100.0


# ---- served runs, a planted fault and the control --------------------------------


@pytest.fixture
def root(tmp_path, monkeypatch):
    """A checkout holding one small uplink cell on the closed-loop mix."""
    monkeypatch.setattr(harness, "check_devices", lambda chips: None)
    for kind in ("metrics", "families", "arrivals", "references"):
        shutil.copytree(HERE / kind, tmp_path / "chipbench" / kind)
    config = dict(_small(200), name="small-uplink", service=dict(UPLINK["service"], max_batch=2))
    (tmp_path / "chipbench" / "configs").mkdir()
    (tmp_path / "chipbench" / "configs" / "small-uplink.json").write_text(json.dumps(config))
    (tmp_path / "chipbench" / "traffic").mkdir()
    (tmp_path / "chipbench" / "traffic" / "small-closed.json").write_text(
        json.dumps(dict(CLOSED, max_requests=400))
    )
    spec = json.loads((HERE.parent / "BENCHMARK.json").read_text())
    spec["configs"] = [{"name": "small-uplink", "source": "test", "reduced": [], "why": "test",
                        "file": "chipbench/configs/small-uplink.json"}]
    spec["workloads"] = [{"name": "xuplink.closed", "config": "small-uplink",
                          "traffic": "small-closed", "chips": 1, "why": "test"}]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    return tmp_path


def _run(root, trace=False, seed=98765432101):
    with open(os.devnull, "w") as log:
        return harness.run(
            "xuplink.closed", seed, 1.0, trace, time.perf_counter(), root=root, log=log
        )


def test_a_served_uplink_run_is_correct_and_reads_its_row_metrics(root):
    out = _run(root, trace=True)
    assert out["correct"] is True, out["checks"]
    assert out["failed"] == 0 and out["attempted"] >= 2
    assert out["checks"]["cost_gap_mj"]["value"] == 0
    metrics = out["metrics"]
    assert 50 < metrics["dp_row_use_pct"]["value"] <= 100
    assert "dp_cell_use_pct" in metrics and "classify_ms" in metrics
    assert "select_ms" not in metrics  # not a metric of this cell


def test_an_upload_left_out_of_the_served_tables_fails_cost_gap(root, monkeypatch):
    """The served tables lose one phone's upload in every request: the phone
    with the dearest upload then looks free to take part."""
    from repro.core import Problem

    def upload(t):  # C(1) less the compute marginal at j = 2
        return t[1] - (t[2] - t[1]) if len(t) > 2 else 0.0

    def problems(plan):
        out = []
        for inst in plan.instances:
            tables = list(inst.tables)
            k = max(range(inst.n), key=lambda i: upload(tables[i]))
            tables[k] = np.concatenate([[0.0], tables[k][1:] - upload(tables[k])])
            out.append(Problem(T=inst.T, lower=inst.lower, upper=inst.upper, cost_tables=tables))
        return out

    monkeypatch.setattr(harness.Session, "problems", staticmethod(problems))
    out = _run(root)
    assert out["correct"] is False
    assert out["checks"]["cost_gap_mj"]["value"] > out["checks"]["cost_gap_mj"]["limit"]


@pytest.mark.parametrize("population", [200, 3550])
def test_the_bfloat16_control_fails_both_limits(population):
    config = UPLINK if population == 3550 else _small(population)
    config = dict(config, check=dict(config["check"], sample=1))
    plan = make_plan(config, CLOSED, 4, 1.0)
    ok, numbers = control.judge(config, plan, 4, HERE)
    got = {k: (v, lim) for k, v, lim in numbers}
    assert not ok
    for name in ("objective_rel_gap", "cost_gap_mj"):
        value, limit = got[name]
        assert value > limit, (name, value, limit)


# ---- the DP row metric --------------------------------------------------------------


def _metric(name, record):
    return load("metrics", name, HERE).read(record)


def test_dp_row_use_reads_its_counters_and_nothing_without_them():
    service = {"dp_rows": 8192, "dp_live_rows": 6144, "flushes": 2}
    assert _metric("dp_row_use_pct", {"service": service}) == pytest.approx(75.0)
    older = {"service": {"flushes": 2, "dp_band_cells": 5}}
    assert _metric("dp_row_use_pct", older) is None
    assert _metric("dp_row_use_pct", {"service": dict(service, dp_rows=0)}) is None
