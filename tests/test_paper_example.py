"""Validates the paper's own worked example (Section 3.1, Figs. 1-2)."""

import numpy as np
import pytest

from repro.core import (
    Problem,
    Solver,
    brute_force_schedule,
    schedule,
    solve_schedule_dp,
    total_cost,
)


def paper_problem(T: int) -> Problem:
    # R = {1,2,3}; U = {6,6,5}; L = {1,0,0}
    # C1 = {1:2, 2:3.5, 3:5.5, 4:8, 5:10, 6:12}
    # C2 = {0:0, 1:1.5, 2:2.5, 3:4, 4:7, 5:9, 6:11}
    # C3 = {0:0, 1:3, 2:4, 3:5, 4:6, 5:7}
    c1 = np.array([0.0, 2, 3.5, 5.5, 8, 10, 12])  # C1(0) unused (L1=1)
    c2 = np.array([0.0, 1.5, 2.5, 4, 7, 9, 11])
    c3 = np.array([0.0, 3, 4, 5, 6, 7])
    return Problem(T=T, lower=[1, 0, 0], upper=[6, 6, 5], cost_tables=(c1, c2, c3))


def test_example_T5():
    p = paper_problem(5)
    x = solve_schedule_dp(p)
    assert total_cost(p, x) == pytest.approx(7.5)
    assert list(x) == [2, 3, 0]  # Fig. 1


def test_example_T8():
    p = paper_problem(8)
    x = solve_schedule_dp(p)
    assert total_cost(p, x) == pytest.approx(11.5)
    assert list(x) == [1, 2, 5]  # Fig. 2


def test_example_matches_brute_force():
    for T in range(1, 17):
        p = paper_problem(T)
        bf = brute_force_schedule(p)
        dp = solve_schedule_dp(p)
        assert total_cost(p, dp) == pytest.approx(total_cost(p, bf))


def test_example_jax_dp_matches():
    for T in (5, 8, 12):
        p = paper_problem(T)
        x = Solver().solve(p, algorithm="dp_jax").schedule
        assert total_cost(p, x) == pytest.approx(total_cost(p, solve_schedule_dp(p)))


def test_greedy_insight():
    """Section 3.1: the T=8 optimum does not contain the T=5 optimum, so
    naive greedy extensions of smaller optima are suboptimal in general."""
    p5, p8 = paper_problem(5), paper_problem(8)
    x5, x8 = solve_schedule_dp(p5), solve_schedule_dp(p8)
    assert not np.all(x8 >= x5)


def test_auto_dispatch_on_example():
    p = paper_problem(8)
    x = schedule(p, "auto")
    assert total_cost(p, x) == pytest.approx(11.5)
