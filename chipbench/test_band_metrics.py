"""The reader of ``dp_band_run_pct``: the share of the DP's computed cells
that the min-plus band loops ran, from the service's counters; nothing
where no DP flush ran or the program has no such counter."""

from pathlib import Path

import pytest

from chipbench.traffic import load

HERE = Path(__file__).resolve().parent

SERVICE = {"flushes": 2, "dp_band_cells": 180, "dp_computed_cells": 1000, "dp_run_cells": 560}


def _read(service):
    return load("metrics", "dp_band_run_pct", HERE).read({"service": service})


def test_band_run_share_reads_its_counters():
    assert _read(SERVICE) == pytest.approx(56.0)


@pytest.mark.parametrize(
    "service",
    [
        dict(SERVICE, dp_computed_cells=0, dp_run_cells=0),  # no DP flush in the window
        {k: v for k, v in SERVICE.items() if k != "dp_run_cells"},  # a program before the counter
        {"requests": 10, "flushes": 4, "flushed_rows": 10, "rejected": 0},  # no DP counters at all
    ],
    ids=["no-dp-flush", "no-run-counter", "no-dp-counters"],
)
def test_band_run_share_reads_nothing_without_its_counters(service):
    assert _read(service) is None
