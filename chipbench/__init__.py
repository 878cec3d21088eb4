"""The planner's chip benchmark: one command, cells found by name in
``BENCHMARK.json`` (see ``harness.py``)."""
