"""The serving program's own spans in a profiler trace, and the
per-layer numbers they give.

The program writes host spans named ``engine.*`` and ``backend.*``
(``repro.obs.trace.span``; the catalogue is in ``docs/OBSERVABILITY.md``)
on the profiler's clock, each with its identifiers as event stats, and
``engine.step`` carries the backend's byte counters at entry.  ``load``
reads them from an ``.xplane.pb`` as the record's ``program_spans``:
``[name, start_ns, dur_ns, stats]``.  ``benchkit.trace.load`` does not
fill that key yet (``bench/tools/program_spans.py`` adds it), so every
reader here returns None on a record without it, as on a trace of a
program that writes no such spans.

"Per step" is per ``backend.dispatch`` span that starts in the traced
window.  The spans nest, all on the engine's thread.
"""
from __future__ import annotations

from typing import Optional

from benchkit import stats

PREFIXES = ("engine.", "backend.")


def load(path: str) -> list:
    """The program spans of the host planes of ``path``, by start."""
    import jax

    pd = jax.profiler.ProfileData.from_file(path)
    out = [[e.name, e.start_ns, e.duration_ns, dict(e.stats)]
           for plane in pd.planes if plane.name.startswith("/host:")
           for line in plane.lines for e in line.events
           if e.name.startswith(PREFIXES)]
    return sorted(out, key=lambda e: (e[1], -e[2]))


def _record(run) -> Optional[dict]:
    rec = run.trace
    if rec is None or rec.get("window") is None \
            or "program_spans" not in rec:
        return None
    return rec


def _started(rec: dict, *names: str) -> list:
    lo, hi = rec["window"]
    return [e for e in rec["program_spans"]
            if e[0] in names and lo <= e[1] < hi]


def _ms_per_step(run, *names: str) -> Optional[float]:
    rec = _record(run)
    if rec is None:
        return None
    steps = len(_started(rec, "backend.dispatch"))
    if not steps:
        return None
    return sum(e[2] for e in _started(rec, *names)) / 1e6 / steps


def kv_stage_ms_per_step(run):
    """Host milliseconds staging dirty pool blocks into the device
    mirror (``backend.stage``) per decode step."""
    return _ms_per_step(run, "backend.stage")


def kv_commit_ms_per_step(run):
    """Host milliseconds of the deferred KV write-back
    (``backend.commit``: new K/V to the host, block-table extends) per
    decode step."""
    return _ms_per_step(run, "backend.commit")


def lane_order_ms_per_step(run):
    """Host milliseconds of the MARS lane ordering
    (``engine.lane_order``) per decode step."""
    return _ms_per_step(run, "engine.lane_order")


def sample_ms_per_step(run):
    """Host milliseconds bringing the logits to the host and sampling
    them (``backend.decode.fetch`` + ``engine.sample``) per decode
    step."""
    return _ms_per_step(run, "backend.decode.fetch", "engine.sample")


def prefill_kv_host_ms_per_ktok(run):
    """Host milliseconds bringing the prompts' K/V to the host and
    storing them in the pool (``backend.prefill.fetch`` +
    ``backend.prefill.store``) per thousand prompt tokens, over the
    prefills that start in the traced window."""
    rec = _record(run)
    if rec is None:
        return None
    fetch = _started(rec, "backend.prefill.fetch")
    toks = sum(e[3]["rows"] * e[3]["tokens"] for e in fetch)
    if not toks:
        return None
    ns = sum(e[2] for e in fetch) + sum(
        e[2] for e in _started(rec, "backend.prefill.store"))
    return ns / 1e6 / (toks / 1e3)


def hostdev_mb_per_step_counted(run):
    """Megabytes the backend moved between host and device (its
    ``h2d_bytes`` + ``d2h_bytes`` counters, read from the first and the
    last ``engine.step`` that start in the traced window) over the
    decode steps dispatched between them."""
    rec = _record(run)
    if rec is None:
        return None
    steps = _started(rec, "engine.step")
    if len(steps) < 2 or "d2h_bytes" not in steps[0][3]:
        return None
    a, b = steps[0][3], steps[-1][3]
    n = b["decode_steps"] - a["decode_steps"]
    if n <= 0:
        return None
    moved = b["h2d_bytes"] + b["d2h_bytes"] - a["h2d_bytes"] \
        - a["d2h_bytes"]
    return moved / n / 1e6


def _innermost(spans: list, lo: float, hi: float) -> list:
    """``[lo, hi]`` cut into ``(start, end, name)`` stretches, ``name``
    the innermost program span open over the stretch (None where none
    is).  Spans must nest."""
    out = []
    t = lo

    def emit(t1, name):
        nonlocal t
        a, b = max(t, lo), min(t1, hi)
        if b > a:
            out.append((a, b, name))
        t = max(t, t1)

    stack: list = []                 # (end, name), innermost last
    for name, s, d, _ in sorted(spans, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][0] <= s:
            emit(*stack.pop())
        emit(s, stack[-1][1] if stack else None)
        stack.append((s + d, name))
    while stack:
        emit(*stack.pop())
    emit(hi, None)
    return out


def idle_by_span(run) -> Optional[dict]:
    """Seconds of the traced window in which the device (the first one)
    is idle, by the innermost program span open then (the span's self
    time; None where no program span is open)."""
    rec = _record(run)
    if rec is None or not rec["device_ops"]:
        return None
    lo, hi = rec["window"]
    idle = stats.gaps([(e[1], e[1] + e[2]) for e in rec["device_ops"][0]],
                      lo, hi)
    out: dict = {}
    segs = _innermost(rec["program_spans"], lo, hi)
    j = 0
    for g0, g1 in idle:
        while j < len(segs) and segs[j][1] <= g0:
            j += 1
        k = j
        while k < len(segs) and segs[k][0] < g1:
            a, b, name = segs[k]
            out[name] = out.get(name, 0.0) + \
                (min(b, g1) - max(a, g0)) / 1e9
            k += 1
    return out


def device_idle_unattributed(run):
    """Share of the traced window in which the device is idle and no
    program span is open."""
    by = idle_by_span(run)
    if by is None:
        return None
    lo, hi = run.trace["window"]
    return 100.0 * by.get(None, 0.0) / ((hi - lo) / 1e9)
