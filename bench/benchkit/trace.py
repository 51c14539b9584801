"""From a JAX profiler trace to the numbers the per-layer metrics read.

``load`` turns an ``.xplane.pb`` into a small plain record (lists of
``[name, start_ns, duration_ns, ...]``), which is all the reduction
reads, so the reduction can be checked on the CPU against a recorded
excerpt (``bench/tests/data/trace_excerpt.json``).

The record:

  device_ops   per device plane, the events of its "XLA Ops" line:
               ``[op name, start_ns, dur_ns, program name]``
  modules      per device plane, the events of its "XLA Modules" line:
               ``[name, start_ns, dur_ns]``
  host_spans   the benchmark's own host spans (names ``bench.*``):
               ``[name, start_ns, dur_ns]``
  window       ``[start_ns, end_ns]`` of the ``bench.trace_window`` span

Host and device events share the profiler's clock.
"""
from __future__ import annotations

import re
from typing import Optional

from benchkit import stats

WINDOW_SPAN = "bench.trace_window"


def _stat(ev, key: str):
    for k, v in ev.stats:
        if k == key:
            return v
    return None


def load(path: str) -> dict:
    """Read ``path`` (an ``.xplane.pb``) into the plain record."""
    import jax

    pd = jax.profiler.ProfileData.from_file(path)
    rec = {"device_ops": [], "modules": [], "host_spans": [],
           "window": None}
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "TPU" in plane.name:
            ops, mods = [], []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops = [[e.name, e.start_ns, e.duration_ns,
                            _stat(e, "hlo_module") or ""]
                           for e in line.events]
                elif line.name == "XLA Modules":
                    mods = [[e.name, e.start_ns, e.duration_ns]
                            for e in line.events]
            if ops or mods:
                rec["device_ops"].append(_tidy_ops(ops, mods))
                rec["modules"].append(mods)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == WINDOW_SPAN:
                        rec["window"] = [e.start_ns,
                                         e.start_ns + e.duration_ns]
                    elif e.name.startswith("bench."):
                        rec["host_spans"].append(
                            [e.name, e.start_ns, e.duration_ns])
    return rec


def _short(name: str) -> str:
    """``%fusion.3 = bf16[...] fusion(...)`` -> ``fusion.3``;
    ``jit_f(123)`` -> ``jit_f``."""
    return name.split(" = ")[0].lstrip("%").split("(")[0]


def _tidy_ops(ops: list, mods: list) -> list:
    """Ops named by their HLO instruction, each tagged with the program
    whose execution holds it (the trace's op events carry the full HLO
    text and, on the TPU, no program name)."""
    import bisect
    spans = sorted((m[1], m[1] + m[2], _short(m[0])) for m in mods)
    starts = [m[0] for m in spans]
    out = []
    for name, start, dur, module in ops:
        if not module:
            i = bisect.bisect_right(starts, start) - 1
            if i >= 0 and start < spans[i][1]:
                module = spans[i][2]
        out.append([_short(name), start, dur, module])
    return out


def _in_window(events, lo, hi):
    return [e for e in events if e[1] < hi and e[1] + e[2] > lo]


def busy_s(rec: dict) -> Optional[float]:
    """Seconds in the traced window in which some operation ran on the
    device, averaged over the device planes."""
    if rec["window"] is None or not rec["device_ops"]:
        return None
    lo, hi = rec["window"]
    per = [stats.union_length([(e[1], e[1] + e[2]) for e in ops], lo, hi)
           for ops in rec["device_ops"]]
    return sum(per) / len(per) / 1e9


def window_s(rec: dict) -> Optional[float]:
    if rec["window"] is None:
        return None
    lo, hi = rec["window"]
    return (hi - lo) / 1e9


def op_seconds(rec: dict, pattern: str) -> Optional[float]:
    """Summed device seconds (first device plane) of the operations whose
    name matches ``pattern``, clipped to the window; None when none."""
    if rec["window"] is None or not rec["device_ops"]:
        return None
    lo, hi = rec["window"]
    rx = re.compile(pattern)
    hits = [e for e in _in_window(rec["device_ops"][0], lo, hi)
            if rx.search(e[0])]
    if not hits:
        return None
    return sum(min(e[1] + e[2], hi) - max(e[1], lo) for e in hits) / 1e9


def module_durations(rec: dict, pattern: str) -> list:
    """Device seconds of each execution of the programs whose name
    matches ``pattern``, wholly inside the window (first device)."""
    if rec["window"] is None or not rec["modules"]:
        return []
    lo, hi = rec["window"]
    rx = re.compile(pattern)
    return [e[2] / 1e9 for e in rec["modules"][0]
            if rx.search(e[0]) and e[1] >= lo and e[1] + e[2] <= hi]


def _leaves(ops: list) -> list:
    """The ops that hold no other op (a loop's op spans the ops of its
    body; counting both would count the time twice)."""
    ops = sorted(ops, key=lambda e: (e[1], -e[2]))
    return [e for i, e in enumerate(ops)
            if i + 1 == len(ops) or ops[i + 1][1] >= e[1] + e[2]]


def breakdown(rec: dict, top: int = 10) -> Optional[dict]:
    """The device operations (innermost ones) that took most time in the
    window, by program and op name, and the device's idle time grouped by
    the innermost ``bench.*`` host span around each idle stretch (``host
    idle`` where none is)."""
    if rec["window"] is None or not rec["device_ops"]:
        return None
    lo, hi = rec["window"]
    ops = _in_window(rec["device_ops"][0], lo, hi)
    by_op: dict = {}
    for name, s, d, module in _leaves(ops):
        key = f"{module}/{name}" if module else name
        by_op[key] = by_op.get(key, 0.0) + \
            (min(s + d, hi) - max(s, lo)) / 1e9
    spans = sorted(rec["host_spans"], key=lambda e: e[1])
    by_host: dict = {}
    for g0, g1 in stats.gaps([(e[1], e[1] + e[2]) for e in ops], lo, hi):
        mid = (g0 + g1) / 2
        inner = None
        for name, s, d in spans:
            if s > mid:
                break
            if s + d >= mid and (inner is None or d < inner[1]):
                inner = (name, d)
        key = inner[0] if inner else "host idle"
        by_host[key] = by_host.get(key, 0.0) + (g1 - g0) / 1e9
    rank = lambda d: sorted(([k, v] for k, v in d.items()),
                            key=lambda kv: -kv[1])[:top]
    return {"device_ops": rank(by_op), "idle_gaps": rank(by_host)}
