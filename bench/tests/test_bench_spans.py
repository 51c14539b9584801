"""The reduction of the program's own spans (``benchkit.spans``): on a
hand-made record with known answers, on the recorded chip excerpt that
holds none, and wired through ``bench/tools/program_spans.py`` on the
CPU at smoke size (a check of the wiring, not a device number)."""
import importlib.util
import io
import json
import pathlib
import time
import types

import pytest

from benchkit import readers, spans, trace
from conftest import BENCH

EXCERPT = pathlib.Path(__file__).resolve().parent / "data" / \
    "trace_excerpt.json"
MS = 1_000_000


def _span(name, t0, t1, **st):
    return [name, t0 * MS, (t1 - t0) * MS, st]


def _counters(h2d, d2h, steps):
    return dict(step_num=steps, h2d_bytes=h2d, d2h_bytes=d2h,
                staged_blocks=0, decode_steps=steps)


def _toy():
    # 100 ms window; the device runs [10, 30] and [50, 70] ms.  Two
    # decode steps dispatch in it, one 500-token prompt is prefilled
    return {
        "window": [0, 100 * MS],
        "device_ops": [[["fusion.1", 10 * MS, 20 * MS, "jit_a"],
                        ["fusion.2", 50 * MS, 20 * MS, "jit_a"]]],
        "modules": [[]],
        "host_spans": [],
        "program_spans": [
            _span("engine.step", 0, 45, **_counters(0, 0, 0)),
            _span("engine.lane_order", 2, 5, lanes=2),
            _span("backend.dispatch", 5, 12, step=0, lanes=2),
            _span("backend.stage", 6, 9, step=0, lanes=2),
            _span("backend.launch", 9, 11, step=0, lanes=2),
            _span("backend.decode", 12, 35, step=0, lanes=2),
            _span("backend.decode.wait", 12, 30, step=0, lanes=2),
            _span("backend.decode.fetch", 30, 33, step=0, lanes=2),
            _span("engine.sample", 35, 38, lanes=2),
            _span("engine.step", 46, 90, **_counters(3 * MS, MS, 1)),
            _span("engine.prefill", 46, 60, rid=7, tokens=500),
            _span("backend.prefill", 47, 59, rows=1),
            _span("backend.prefill.wait", 47, 52, rows=1, tokens=500),
            _span("backend.prefill.fetch", 52, 55, rows=1, tokens=500),
            _span("backend.prefill.store", 55, 58, rows=1, tokens=500),
            _span("backend.flush", 60, 66),
            _span("backend.commit", 60, 66, step=0, lanes=2),
            _span("backend.commit.fetch", 60, 62, step=0, lanes=2),
            _span("backend.commit.store", 62, 66, step=0, lanes=2),
            _span("backend.dispatch", 66, 72, step=1, lanes=3),
            _span("backend.stage", 66, 70, step=1, lanes=3),
            _span("engine.step", 92, 99, **_counters(7 * MS, 3 * MS, 2)),
        ],
    }


def _run(rec):
    return types.SimpleNamespace(trace=rec)


def test_toy_record_reduces_to_known_numbers():
    run = _run(_toy())
    assert spans.kv_stage_ms_per_step(run) == pytest.approx(3.5)
    assert spans.kv_commit_ms_per_step(run) == pytest.approx(3.0)
    assert spans.lane_order_ms_per_step(run) == pytest.approx(1.5)
    assert spans.sample_ms_per_step(run) == pytest.approx(3.0)
    assert spans.prefill_kv_host_ms_per_ktok(run) == pytest.approx(12.0)
    # 10 MB moved from the first step's entry to the last's, two steps
    assert spans.hostdev_mb_per_step_counted(run) == pytest.approx(5.0)
    # idle [45, 46], [90, 92] and [99, 100] ms lie under no span
    assert spans.device_idle_unattributed(run) == pytest.approx(4.0)


def test_idle_goes_to_the_innermost_span():
    by = spans.idle_by_span(_run(_toy()))
    want = {"engine.step": 34, "engine.lane_order": 3,
            "backend.dispatch": 3, "backend.stage": 3,
            "backend.launch": 1, "backend.decode.fetch": 3,
            "backend.decode": 2, "engine.sample": 3, None: 4,
            "engine.prefill": 1, "backend.prefill.wait": 3}
    assert by == pytest.approx({k: v / 1e3 for k, v in want.items()})
    # every idle stretch is counted once: the parts sum to the idle time
    assert sum(by.values()) == pytest.approx(0.060)


SPAN_READERS = [spans.kv_stage_ms_per_step, spans.kv_commit_ms_per_step,
                spans.lane_order_ms_per_step, spans.sample_ms_per_step,
                spans.prefill_kv_host_ms_per_ktok,
                spans.hostdev_mb_per_step_counted,
                spans.device_idle_unattributed, spans.idle_by_span]


@pytest.fixture(scope="module")
def excerpt():
    return json.loads(EXCERPT.read_text())


def _existing(rec):
    run = _run(rec)
    return (trace.busy_s(rec), trace.window_s(rec), trace.breakdown(rec),
            trace.op_seconds(rec, readers.ATTENTION_KERNEL),
            trace.module_durations(rec, readers.DECODE_PROGRAM),
            readers.device_idle(run), readers.decode_step_ms(run))


@pytest.mark.parametrize("reader", SPAN_READERS,
                         ids=lambda f: f.__name__)
def test_a_record_without_program_spans_reads_nothing(excerpt, reader):
    assert "program_spans" not in excerpt
    assert reader(_run(excerpt)) is None
    assert reader(_run(None)) is None


def test_program_spans_leave_the_existing_readers_alone(excerpt):
    before = _existing(excerpt)
    assert _existing(dict(excerpt, program_spans=_toy()["program_spans"])) \
        == before


def _tool():
    path = BENCH / "tools" / "program_spans.py"
    spec = importlib.util.spec_from_file_location("program_spans_tool",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_tool_reads_every_span_number_at_smoke_size(checkout, tmp_path):
    """On the CPU: every span number is read; the idle share is not,
    since a CPU trace holds no device plane."""
    out, err = io.StringIO(), io.StringIO()
    line = _tool().measure(checkout, "qwen05b.chat", 20240611, 2.0,
                           time.perf_counter(), tmp_path / "rec.json",
                           require_tpu=False, out=out, err=err)
    assert json.loads(out.getvalue().splitlines()[-1]) == line
    got = line["metrics"]
    assert got.pop("device_idle_unattributed.chat") is None
    assert all(v is not None and v >= 0 for v in got.values()), got
    assert line["engine_step_ms_mean"] > 0
    assert line["span_cost_us"]["off"] > 0
