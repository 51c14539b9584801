"""Run a cell once with ``--trace 1`` and keep the reduced trace record.

    python3 bench/tools/record_trace.py --workload qwen05b.chat \\
        --seed 11 --seconds 10

Prints the run's result line as ``bench/run.py`` does and writes the
record that the per-layer readers read (``benchkit.trace.load``) to
``chiprun_out/trace_<workload>.json``: the source of the recorded
excerpt the CPU tests check the trace reduction on.  Runs on the chip.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(BENCH))
    from benchkit import harness
    out = BENCH.parent / "chiprun_out"
    out.mkdir(exist_ok=True)
    harness.run_cell(BENCH.parent, args.workload, args.seed, args.seconds,
                     True, T_PROCESS,
                     keep_trace=out / f"trace_{args.workload}.json")
    return 0


if __name__ == "__main__":
    sys.exit(main())
