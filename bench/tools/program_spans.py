"""Run a cell once with ``--trace 1`` and read the program's own spans.

    python3 bench/tools/program_spans.py --workload qwen05b.chat \\
        --seed 11 --seconds 51 --record spans.json

Runs the cell as ``bench/run.py --trace 1`` does and prints its result
line, with the harness's trace record extended by the program's
``engine.*`` and ``backend.*`` spans (``benchkit.spans.load``).  Then
prints one JSON line of what those spans give: the numbers of
``benchkit.spans`` over the traced window, the device's idle seconds by
innermost program span, the mean host duration of ``engine.step`` and
of the harness's ``bench.step`` there, and what one span costs on this
host with no profiler trace running and with one.  Writes the record,
as JSON, to ``--record``.  Runs on the chip; on a program that writes
no such spans the span numbers read null.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import types  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parents[1]
NO_SPAN = "(no program span)"


def readers() -> dict:
    from benchkit import spans
    return {
        "kv_stage_ms_per_step.chat": spans.kv_stage_ms_per_step,
        "kv_commit_ms_per_step.chat": spans.kv_commit_ms_per_step,
        "prefill_kv_host_ms_per_ktok.chat":
            spans.prefill_kv_host_ms_per_ktok,
        "lane_order_ms_per_step.chat": spans.lane_order_ms_per_step,
        "sample_ms_per_step.chat": spans.sample_ms_per_step,
        "hostdev_mb_per_step_counted.chat":
            spans.hostdev_mb_per_step_counted,
        "device_idle_unattributed.chat": spans.device_idle_unattributed,
    }


def span_cost_us(n: int = 100_000):
    """Mean microseconds of one ``repro.obs.trace.span`` with two
    fields, entered and left; None for a program without it."""
    try:
        from repro.obs.trace import span
    except ImportError:
        return None
    t0 = time.perf_counter()
    for i in range(n):
        with span("engine.cost", step=i, lanes=1):
            pass
    return (time.perf_counter() - t0) / n * 1e6


def span_cost_traced_us(n: int = 20_000):
    """``span_cost_us`` while a profiler trace is being taken."""
    import jax
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        try:
            return span_cost_us(n)
        finally:
            jax.profiler.stop_trace()


def _mean_ms(events: list, name: str, lo: float, hi: float):
    durs = [e[2] for e in events if e[0] == name and lo <= e[1] < hi]
    return sum(durs) / len(durs) / 1e6 if durs else None


def measure(root, workload: str, seed: int, seconds: float,
            t_process: float, keep: pathlib.Path, *,
            require_tpu: bool = True, out=sys.stdout,
            err=sys.stderr) -> dict:
    """One traced run of ``workload`` with the program's spans kept;
    prints and returns the span line."""
    from benchkit import harness, spans, trace
    load = trace.load

    def load_with_program_spans(path):
        rec = load(path)
        rec["program_spans"] = spans.load(path)
        return rec

    trace.load = load_with_program_spans
    try:
        result = harness.run_cell(root, workload, seed, seconds, True,
                                  t_process, require_tpu=require_tpu,
                                  keep_trace=keep, out=out, err=err)
    finally:
        trace.load = load
    with open(keep, encoding="utf-8") as fh:
        rec = json.load(fh)
    run = types.SimpleNamespace(trace=rec)
    lo, hi = rec["window"]
    idle = spans.idle_by_span(run) or {}
    line = {
        "workload": workload, "seed": seed,
        "metrics": {k: fn(run) for k, fn in readers().items()},
        "hostdev_mb_per_step.chat": result["metrics"].get(
            "hostdev_mb_per_step.chat", {}).get("value"),
        "window_s": (hi - lo) / 1e9,
        "idle_s": sum(idle.values()),
        "idle_by_span_s": {NO_SPAN if k is None else k: v
                           for k, v in sorted(idle.items(),
                                              key=lambda kv: -kv[1])},
        "engine_step_ms_mean": _mean_ms(rec["program_spans"],
                                        "engine.step", lo, hi),
        "bench_step_ms_mean": _mean_ms(rec["host_spans"], "bench.step",
                                       lo, hi),
        "span_cost_us": {"off": span_cost_us(),
                         "on": span_cost_traced_us()},
        "device": result["device"],
    }
    print(json.dumps(line), file=out, flush=True)
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--record", type=pathlib.Path, required=True,
                    help="where to write the trace record, as JSON")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(BENCH))
    measure(BENCH.parent, args.workload, args.seed, args.seconds,
            T_PROCESS, args.record)
    return 0


if __name__ == "__main__":
    sys.exit(main())
