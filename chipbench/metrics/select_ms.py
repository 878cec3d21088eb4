"""select_ms: device time of the selection kernel's program per answered
instance, in ms, from the trace. The program is the engine's jitted
``run_sel`` (the MarIn/MarCo selection over the marginal-cost table)."""

# the XLA program that holds the selection kernel
PROGRAM = "jit_run_sel"


def read(record):
    tr = record.get("trace")
    if not tr:
        return None
    seconds = sum(v for name, v in tr["module_s"].items() if name.startswith(PROGRAM))
    answered = sum(1 for r in record["requests"] if r.get("ok"))
    if seconds <= 0 or not answered:
        return None
    return 1e3 * seconds / answered
