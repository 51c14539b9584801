"""The reduction from a profiler trace to the per-layer metrics: on a
hand-made record with known answers, and on an excerpt of a trace
recorded on the chip (``bench/tests/data/trace_excerpt.json``: the
reduced record of a traced ``qwen05b.chat`` run, cut to a short
stretch of its window)."""
import json
import pathlib

import numpy as np
import pytest

from benchkit import readers, refmodel, trace
from conftest import BENCH

EXCERPT = pathlib.Path(__file__).resolve().parent / "data" / \
    "trace_excerpt.json"


def _toy():
    # one device: two decode programs, each a layer loop (``while.1``)
    # holding an attention kernel op and a fusion; a host span covers
    # the gap between them
    ms = 1_000_000
    dec = "jit__paged_decode_kernel"
    return {
        "window": [0, 10 * ms],
        "device_ops": [[
            ["while.1", 1 * ms, 2 * ms, dec],
            ["_paged_attention.4", 1 * ms, 500_000, dec],
            ["fusion.1", 1500_000, 1500_000, dec],
            ["while.1", 6 * ms, 2 * ms, dec],
            ["_paged_attention.4", 6 * ms, 1 * ms, dec],
            ["fusion.1", 7 * ms, 1 * ms, dec],
            ["copy.3", 9500_000, 1 * ms, "jit_other"],
        ]],
        "modules": [[
            [dec, 1 * ms, 2 * ms],
            [dec, 6 * ms, 2 * ms],
            ["jit_other", 9500_000, 1 * ms],
        ]],
        "host_spans": [["bench.step", 0, 9 * ms],
                       ["bench.sync", 3 * ms, 2 * ms]],
    }


def test_toy_record_reduces_to_known_numbers():
    rec = _toy()
    assert trace.window_s(rec) == pytest.approx(0.010)
    # busy: [1,3] + [6,8] + [9.5,10] (clipped) = 4.5 ms
    assert trace.busy_s(rec) == pytest.approx(0.0045)
    assert trace.op_seconds(rec, readers.ATTENTION_KERNEL) == \
        pytest.approx(0.0015)
    assert trace.module_durations(rec, readers.DECODE_PROGRAM) == \
        pytest.approx([0.002, 0.002])
    bd = trace.breakdown(rec)
    ops = dict(bd["device_ops"])
    # the loops hold the other ops and are not counted again
    assert "jit__paged_decode_kernel/while.1" not in ops
    assert ops["jit__paged_decode_kernel/fusion.1"] == pytest.approx(0.0025)
    assert ops["jit__paged_decode_kernel/_paged_attention.4"] == \
        pytest.approx(0.0015)
    assert ops["jit_other/copy.3"] == pytest.approx(0.0005)
    gaps = dict(bd["idle_gaps"])
    # [0,1] and [8,9.5] under bench.step only; [3,6] inside bench.sync
    assert gaps["bench.sync"] == pytest.approx(0.003)
    assert gaps["bench.step"] == pytest.approx(0.0025)
    assert sum(gaps.values()) + trace.busy_s(rec) == \
        pytest.approx(trace.window_s(rec))


def test_ops_are_tagged_with_their_program():
    ops = [["%fusion.2 = bf16[8]{0} fusion(x)", 5, 1, ""],
           ["%copy = bf16[8]{0} copy(y)", 20, 1, ""]]
    mods = [["jit_step(123)", 4, 3]]
    assert trace._tidy_ops(ops, mods) == [["fusion.2", 5, 1, "jit_step"],
                                          ["copy", 20, 1, ""]]


def test_no_trace_reads_nothing():
    rec = {"window": None, "device_ops": [], "modules": [],
           "host_spans": []}
    assert trace.busy_s(rec) is None
    assert trace.op_seconds(rec, "x") is None
    assert trace.breakdown(rec) is None


@pytest.fixture(scope="module")
def excerpt():
    return json.loads(EXCERPT.read_text())


def _brute_busy(ops, lo, hi, step_ns=1000):
    """Busy time at 1 us resolution: the slow way."""
    grid = np.zeros(int(hi - lo) // step_ns + 1, bool)
    for _, s, d, *_ in ops:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            grid[int(a - lo) // step_ns:int(b - lo) // step_ns] = True
    return grid.sum() * step_ns / 1e9


def test_recorded_excerpt_busy_and_idle(excerpt):
    lo, hi = excerpt["window"]
    busy = trace.busy_s(excerpt)
    assert 0 < busy <= trace.window_s(excerpt)
    assert busy == pytest.approx(
        _brute_busy(excerpt["device_ops"][0], lo, hi), abs=2e-4)
    bd = trace.breakdown(excerpt)
    assert sum(v for _, v in bd["idle_gaps"]) <= \
        trace.window_s(excerpt) - busy + 1e-9
    assert len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10


def test_recorded_excerpt_finds_the_kernel_and_the_step(excerpt):
    kernel = trace.op_seconds(excerpt, readers.ATTENTION_KERNEL)
    steps = trace.module_durations(excerpt, readers.DECODE_PROGRAM)
    assert kernel and kernel > 0
    assert steps and all(s > 0 for s in steps)
    # the kernel runs inside the decode program
    assert kernel <= sum(trace.module_durations(
        dict(excerpt, window=[excerpt["window"][0] - 10**9,
                              excerpt["window"][1] + 10**9]),
        readers.DECODE_PROGRAM)) + 1e-9


class _Run:
    """What the roofline and idle readers read of a run."""

    def __init__(self, rec, decodes, m):
        self.trace, self.decodes, self.model = rec, decodes, m
        self.peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
        self.serve = {"block_size": 16}
        self.traced = (0.0, 1.0)


def test_roofline_share_is_bound_time_over_kernel_time():
    m = refmodel.Model.from_config(json.loads(
        (BENCH / "configs" / "qwen1_5_0_5b.json").read_text())["model"])
    ctx = [500] * 32
    run = _Run(_toy(), [(0.5, ctx, 0)], m)
    from benchkit import flops
    f, b = flops.attention_kernel_cost(m, ctx, 16)
    want = 100 * max(f / 197e12, b / 819e9) / 0.0015
    assert readers.paged_attention_roofline(run) == pytest.approx(want)
    assert readers.device_idle(run) == pytest.approx(55.0)
    run.trace = None
    assert readers.paged_attention_roofline(run) is None
