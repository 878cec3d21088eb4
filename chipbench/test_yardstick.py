"""The benchmark's yardstick on the CPU: metric arithmetic, traffic
generation, the frozen references against the program, the control, and the
trace reduction on a small recorded trace."""

import json
from pathlib import Path

import numpy as np
import pytest

from chipbench import check, control, reference, stats, trace
from chipbench.traffic import Traffic, load, make_plan

HERE = Path(__file__).resolve().parent
SILO = json.loads((HERE / "configs" / "xsilo-arbitrary.json").read_text())
DEVICE = json.loads((HERE / "configs" / "xdevice-femnist.json").read_text())


def _small_silo(**sizes):
    return dict(SILO, sizes=dict(SILO["sizes"], **sizes))


SMALL_SILO = _small_silo(n_min=4, n_max=6, u_min=40, u_max=63, t_min=65, t_max=128)
MIX = {"arrivals": "poisson", "rate_per_s": 20.0, "shape_seed": 3}


# ---- metric arithmetic ----------------------------------------------------


def test_latency_is_timed_from_the_due_time_over_every_request():
    requests = [{"due": float(i), "done": i + 0.010 * (i + 1), "ok": True} for i in range(20)]
    lat = stats.latencies_s(requests, give_up_s=100.0)
    np.testing.assert_allclose(lat, 0.010 * np.arange(1, 21))
    assert stats.percentile(lat, 50) == pytest.approx(0.105)
    assert stats.percentile(lat, 95) == pytest.approx(0.1905)


def test_a_failed_request_counts_as_missing():
    requests = [{"due": 0.0, "done": 0.01, "ok": True}, {"due": 1.0, "error": "boom"},
                {"due": 2.0, "done": 2.02, "ok": False}]
    lat = stats.latencies_s(requests, give_up_s=70.0)
    np.testing.assert_allclose(lat, [0.01, 69.0, 68.0])
    assert stats.percentile(lat, 50) == 68.0


def test_rate_is_all_work_over_all_window_time_and_mean_of_nothing_is_none():
    assert stats.rate(300, 20.0) == 15.0
    assert stats.mean([]) is None
    assert stats.mean([1.0, 2.0]) == 1.5


# ---- traffic ---------------------------------------------------------------


@pytest.mark.parametrize("config", [SILO, DEVICE], ids=["silo", "device"])
def test_plans_are_deterministic_and_seeds_share_the_work(config):
    mix = dict(MIX, rate_per_s=3.0)
    a, b = make_plan(config, mix, 2**33 + 5, 4.0), make_plan(config, mix, 2**33 + 5, 4.0)
    c = make_plan(config, mix, 17, 4.0)
    assert len(a.instances) == len(c.instances) == 12
    np.testing.assert_array_equal(a.due_s, b.due_s)
    assert all(x.T == y.T and np.array_equal(x.upper, y.upper) and
               all(np.array_equal(s, t) for s, t in zip(x.tables, y.tables))
               for x, y in zip(a.instances, b.instances))
    assert np.all(np.diff(a.due_s) > 0) and a.due_s[-1] < 4.0
    # another seed: the same arrivals, and the same sizes in another order
    np.testing.assert_array_equal(a.due_s, c.due_s)
    assert [i.T for i in a.instances] != [i.T for i in c.instances]
    if config["family"] == "arbitrary":
        key = sorted((i.T, i.n, tuple(i.upper), tuple(i.lower)) for i in a.instances)
        assert key == sorted((i.T, i.n, tuple(i.upper), tuple(i.lower)) for i in c.instances)
    else:
        assert sorted(i.n for i in a.instances) == sorted(i.n for i in c.instances)


def test_cross_silo_requests_have_the_configured_shapes():
    plan = make_plan(SILO, MIX, 99, 2.0)
    s = SILO["sizes"]
    for inst in plan.instances:
        Tp = inst.T - int(inst.lower.sum())
        assert s["n_min"] <= inst.n <= s["n_max"] and inst.upper.max() == s["u_max"]
        assert s["t_min"] <= Tp <= s["t_max"] and Tp <= int((inst.upper - inst.lower).sum())
        assert all(float(t.max()) < 2**24 for t in inst.tables)
        assert inst.band_cells() == (Tp + 1) * int((inst.upper - inst.lower + 1).sum())


def test_the_population_is_fixed_and_monotone():
    traffic = Traffic(DEVICE, MIX)
    upper, tables = traffic.context
    assert len(upper) == 3550 and upper.min() >= 1 and 32 < upper.max() <= 63  # W bucket 64
    assert abs(upper.mean() - 23.2) < 1.0
    np.testing.assert_array_equal(upper, load("families", "population").prepare(DEVICE)[0])
    plan = traffic.plan(5, 0.5)
    for inst in plan.instances:
        assert 2130 <= inst.n <= 3550
        assert reference.regime(inst.lower, inst.upper, inst.tables) == "increasing"
        assert all(float(t[-1]) < 2**24 for t in inst.tables)


# ---- the frozen references against the program ------------------------


def test_reference_dp_matches_the_programs_dp():
    from repro.core import Problem, solve_schedule_dp, total_cost

    for inst in make_plan(SMALL_SILO, MIX, 11, 0.5).instances:
        p = Problem(T=inst.T, lower=inst.lower, upper=inst.upper, cost_tables=inst.tables)
        x, objective = reference.dp_schedule(inst.T, inst.lower, inst.upper, inst.tables)
        x_prog = solve_schedule_dp(p)
        np.testing.assert_array_equal(x, x_prog)
        assert objective == reference.total_cost(inst.tables, x) == total_cost(p, x_prog)


def test_reference_marin_matches_the_programs_marin():
    from repro.core import Problem, marin, total_cost

    small = dict(DEVICE, sizes=dict(DEVICE["sizes"], population=60, eligible_max=0.8))
    for inst in make_plan(small, MIX, 12, 0.5).instances:
        p = Problem(T=inst.T, lower=inst.lower, upper=inst.upper, cost_tables=inst.tables)
        x, objective = reference.marin_schedule(inst.T, inst.lower, inst.upper, inst.tables)
        np.testing.assert_array_equal(x, marin(p))
        assert objective == reference.total_cost(inst.tables, x) == total_cost(p, x)


# ---- the comparison and its control ------------------------------------


def _answered(plan, solve):
    requests = []
    for inst in plan.instances:
        x, objective = solve(inst.T, inst.lower, inst.upper, inst.tables)
        fixed = sum(float(t[int(lo)]) for t, lo in zip(inst.tables, inst.lower))
        requests.append({"ok": True, "x": x, "objective": objective - fixed})
    return requests


def test_reference_answers_are_correct_and_the_control_is_not():
    for seed in (1, 2, 3):
        plan = make_plan(SMALL_SILO, MIX, seed, 0.5)
        ok, numbers = check.judge(SMALL_SILO, plan.instances, _answered(plan, reference.dp_schedule),
                                  seed)
        assert ok, numbers
        ok, numbers = control.judge(SMALL_SILO, plan, seed)
        assert not ok
        assert dict((k, v) for k, v, _ in numbers)["cost_gap_mj"] > 0


def test_a_missing_answer_is_not_correct():
    plan = make_plan(SMALL_SILO, MIX, 4, 0.5)
    requests = _answered(plan, reference.dp_schedule)
    requests[3] = {"error": "TimeoutError()"}
    ok, numbers = check.judge(SMALL_SILO, plan.instances, requests, 4)
    assert not ok and numbers[0] == ("missing", 1, 0)


def test_sample_holds_the_largest_request():
    plan = make_plan(SMALL_SILO, MIX, 6, 1.0)
    requests = [{"ok": True}] * len(plan.instances)
    picked = check.sample(requests, plan.instances, 6, 3)
    largest = max(range(len(plan.instances)), key=lambda i: plan.instances[i].band_cells())
    assert largest in picked and 3 <= len(picked) <= 4
    assert picked == check.sample(requests, plan.instances, 6, 3)


# ---- trace reduction ---------------------------------------------------------


def test_union_and_gaps_of_device_intervals():
    events = {
        "ops": [(10, 20, "a", "m"), (15, 30, "b", "m"), (50, 60, "a", "m"), (95, 120, "c", "m")],
        "modules": [(10, 30, "jit_x(1)"), (50, 60, "jit_y(2)")],
        "host": [(0, 100, "chipbench.window"), (32, 48, "chipbench.submit"),
                 (61, 70, "chipbench.dispatch")],
    }
    out = trace.reduce(events)
    assert out["busy_s"] == pytest.approx(35e-9)  # 10-30, 50-60, 95-100
    assert out["window_s"] == pytest.approx(100e-9)
    assert out["op_s"] == pytest.approx({"a": 20e-9, "b": 15e-9, "c": 25e-9})
    assert out["module_s"] == pytest.approx({"jit_x(1)": 20e-9, "jit_y(2)": 10e-9})
    assert out["top_ops"][0][0] == "c"
    assert [g[0] for g in out["idle_gaps"]] == ["dispatch", "submit", "no host span"]
    assert [g[1] for g in out["idle_gaps"]] == pytest.approx([35e-9, 20e-9, 10e-9])
