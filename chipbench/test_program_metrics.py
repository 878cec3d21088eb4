"""The readers of the metrics that come from the program's own counters
(``SchedulerService.stats()`` deltas in ``record["service"]``): each gives its
value on a synthetic record, and nothing where its count did not move or the
program has no such counter."""

from pathlib import Path

import pytest

from chipbench.traffic import load

HERE = Path(__file__).resolve().parent

SERVICE = {
    "requests": 10,
    "taken_requests": 10,
    "queue_wait_s": 0.025,
    "flushes": 4,
    "dispatch_s": 0.030,
    "classify_s": 0.020,
    "pack_s": 0.006,
    "launch_s": 0.002,
    "landed_flushes": 4,
    "land_s": 0.320,
    "dp_band_cells": 390,
    "dp_computed_cells": 1000,
}

# metric, expected value on SERVICE, the count whose zero delta reads nothing
CASES = [
    ("queue_wait_ms", 2.5, "taken_requests"),
    ("classify_ms", 5.0, "flushes"),
    ("pack_ms", 1.5, "flushes"),
    ("launch_ms", 0.5, "flushes"),
    ("land_ms", 80.0, "landed_flushes"),
    ("dp_cell_use_pct", 39.0, "dp_computed_cells"),
]


def _read(name, service):
    return load("metrics", name, HERE).read({"service": service})


@pytest.mark.parametrize("name,value,count", CASES, ids=[c[0] for c in CASES])
def test_reader_on_a_synthetic_record(name, value, count):
    assert _read(name, SERVICE) == pytest.approx(value)


@pytest.mark.parametrize("name,value,count", CASES, ids=[c[0] for c in CASES])
def test_reader_reads_nothing_when_its_count_did_not_move(name, value, count):
    assert _read(name, dict(SERVICE, **{count: 0})) is None


@pytest.mark.parametrize("name,value,count", CASES, ids=[c[0] for c in CASES])
def test_reader_reads_nothing_from_a_program_without_the_counters(name, value, count):
    # the service counters a program had before these were added
    older = {"requests": 10, "flushes": 4, "flushed_rows": 10, "rejected": 0}
    assert _read(name, older) is None
