"""The arithmetic behind ``bench/metrics/*.py``.  Each reader takes the
``harness.Run`` of one run and returns a number, or None where the run
holds nothing it can read (then the metric is left out of the line).
"""
from __future__ import annotations

from benchkit import flops, stats, trace

# the jitted decode step (``jit(_paged_decode_kernel)``) and the Pallas
# paged-attention kernel inside it, as the device trace names them
DECODE_PROGRAM = r"_paged_decode"
ATTENTION_KERNEL = r"paged_attention"


def tpot_p95_ms(run):
    """95th percentile of every gap between consecutive output tokens
    that ends in the window, over every request."""
    gaps = [(b - a) * 1e3 for r in run.requests
            for a, b in zip(r.times, r.times[1:]) if run.in_window(b)]
    return stats.percentile(gaps, 95)


def setup_s(run):
    return run.setup_s


def prefill_ms_per_ktok(run):
    """Host milliseconds inside the blocking prefill call per thousand
    prompt tokens, over the prefills that started in the window."""
    spans = [(t1 - t0, n) for t0, t1, n, rid in run.prefills
             if rid is not None and run.in_window(t0)]
    toks = sum(n for _, n in spans)
    if not toks:
        return None
    return sum(d for d, _ in spans) * 1e3 / toks * 1e3


def hostdev_mb_per_step(run):
    """Bytes between host and device per decode step, from counts and
    shapes: staged pool blocks, operands, new K/V, logits and hybrid
    state of every decode step in the window, plus the prompt K/V of the
    window's prefills, over the window's decode steps."""
    dec = [d for d in run.decodes if run.in_window(d[0]) and d[1]]
    if not dec or any(d[2] is None for d in dec):
        return None
    bs = run.serve["block_size"]
    total = sum(flops.decode_hostdev_bytes(run.model, ctx, staged, bs)
                for _, ctx, staged in dec)
    total += sum(flops.prefill_hostdev_bytes(run.model, n)
                 for t0, _, n, _ in run.prefills if run.in_window(t0))
    return total / len(dec) / 1e6


def _traced_decodes(run):
    if run.traced is None or run.traced[1] is None:
        return []
    t0, t1 = run.traced
    return [d for d in run.decodes if t0 <= d[0] < t1 and d[1]]


def decode_step_ms(run):
    """Mean device time of one execution of the decode program."""
    if run.trace is None:
        return None
    durs = trace.module_durations(run.trace, DECODE_PROGRAM)
    return 1e3 * sum(durs) / len(durs) if durs else None


def decode_mfu(run):
    """Model FLOPs of the decode tokens dispatched in the traced window,
    over the window's length and the chip's bf16 peak."""
    dec = _traced_decodes(run)
    if not dec or run.peaks is None:
        return None
    work = sum(flops.decode_token_flops(run.model, c)
               for _, ctx, _ in dec for c in ctx)
    secs = run.traced[1] - run.traced[0]
    return 100.0 * work / secs / run.peaks["bf16_flops_per_s"]


def paged_attention_roofline(run):
    """The least time the chip could take for the paged-attention
    kernel's work in the traced window (the larger of FLOPs over peak and
    bytes over HBM bandwidth), over the kernel's summed device time."""
    dec = _traced_decodes(run)
    if not dec or run.peaks is None or run.trace is None:
        return None
    kernel_s = trace.op_seconds(run.trace, ATTENTION_KERNEL)
    if not kernel_s:
        return None
    fl = by = 0.0
    for _, ctx, _ in dec:
        f, b = flops.attention_kernel_cost(run.model, ctx,
                                           run.serve["block_size"])
        fl, by = fl + f, by + b
    bound = max(fl / run.peaks["bf16_flops_per_s"],
                by / run.peaks["hbm_bytes_per_s"])
    return 100.0 * bound / kernel_s


def device_idle(run):
    """Share of the traced window in which no operation ran on the
    device."""
    if run.trace is None:
        return None
    busy, win = trace.busy_s(run.trace), trace.window_s(run.trace)
    if busy is None or not win:
        return None
    return 100.0 * (1.0 - busy / win)
