"""Reduction of a profiler trace to the benchmark's device numbers.

The JAX profiler writes an ``.xplane.pb``; :func:`read_xplane` takes from it
three kinds of event, with start and end in nanoseconds on the trace's one
clock:

* device operations: the ``XLA Ops`` line of the first TPU's plane, each with
  the XLA program (module) it belongs to;
* device programs: the ``XLA Modules`` line of that plane;
* the benchmark's own host spans: every event named ``chipbench.*``.

:func:`reduce` then measures, inside the span ``chipbench.window``:

* ``busy_s``: the union of the device operations' intervals, and
  ``window_s``, the span's length;
* ``op_s`` and ``module_s``: device seconds by operation and by program, over
  the whole trace, so that work launched in the window and finished after it
  counts in full;
* ``top_ops``: the ten operations that took most device time;
* ``idle_gaps``: the ten longest stretches of the window with nothing on the
  device, each named by the host span that overlaps it most (``no host
  span`` where the benchmark was waiting for the next request).
"""

from __future__ import annotations

import glob
import os
from collections import defaultdict

WINDOW = "chipbench.window"


def _module_of(event) -> str:
    for key, value in event.stats:
        if key == "hlo_module":
            return str(value)
    return ""


def read_xplane(path: str) -> dict:
    """The events of one ``.xplane.pb`` (see the module docstring)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    ops, modules, host = [], [], []
    device_seen = False
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:") and not device_seen:
            device_seen = True
            for line in plane.lines:
                if line.name == "XLA Ops":
                    for e in line.events:
                        s = int(e.start_ns)
                        ops.append((s, s + int(e.duration_ns), e.name, _module_of(e)))
                elif line.name == "XLA Modules":
                    for e in line.events:
                        s = int(e.start_ns)
                        modules.append((s, s + int(e.duration_ns), e.name))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("chipbench."):
                        s = int(e.start_ns)
                        host.append((s, s + int(e.duration_ns), e.name))
    return {"ops": ops, "modules": modules, "host": host}


def union(intervals):
    """Merged, sorted ``(start, end)`` intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def _label(gap, host) -> str:
    best, best_overlap = "no host span", 0
    for s, e, name in host:
        if name == WINDOW:
            continue
        overlap = min(e, gap[1]) - max(s, gap[0])
        if overlap > best_overlap:
            best, best_overlap = name[len("chipbench.") :], overlap
    return best


def reduce(events: dict, top: int = 10) -> dict:
    """The trace's device numbers (see the module docstring)."""
    windows = [(s, e) for s, e, name in events["host"] if name == WINDOW]
    if not windows:
        raise ValueError(f"the trace holds no {WINDOW!r} span")
    lo, hi = windows[0]
    busy = _clip(union((s, e) for s, e, _, _ in events["ops"]), lo, hi)
    op_s, module_s = defaultdict(float), defaultdict(float)
    for s, e, name, module in events["ops"]:
        op_s[name] += (e - s) * 1e-9
    for s, e, name in events["modules"]:
        module_s[name] += (e - s) * 1e-9
    gaps, cursor = [], lo
    for s, e in busy + [(hi, hi)]:
        if s > cursor:
            gaps.append((cursor, s))
        cursor = max(cursor, e)
    gaps.sort(key=lambda g: g[0] - g[1])
    return {
        "busy_s": sum(e - s for s, e in busy) * 1e-9,
        "window_s": (hi - lo) * 1e-9,
        "op_s": dict(op_s),
        "module_s": dict(module_s),
        "top_ops": [[k, v] for k, v in sorted(op_s.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[_label(g, events["host"]), (g[1] - g[0]) * 1e-9] for g in gaps[:top]],
    }


def reduce_dir(trace_dir: str) -> dict:
    """Reads the one ``.xplane.pb`` the profiler wrote under ``trace_dir``."""
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if len(paths) != 1:
        raise ValueError(f"expected one trace under {trace_dir}, found {len(paths)}")
    return reduce(read_xplane(paths[0]))
