"""Run one benchmark cell on the chips of this machine.

    python3 bench/run.py --workload qwen05b.chat --seed 7 --seconds 30 \\
        --trace 0

Reads ``BENCHMARK.json`` at the checkout's root, serves the cell's
traffic through the program in ``src/`` for ``--seconds`` after its
set-up, checks what it served against the configuration's plain
reference, and prints one JSON line last on standard output: the cell's
end-to-end metrics (``--trace 0``) or its per-layer metrics from a
profiled run (``--trace 1``).  Exits nonzero, printing no result, when
JAX finds no TPU or fewer chips than the cell asks for.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True,
                    help="a workload name from BENCHMARK.json")
    ap.add_argument("--seed", type=int, required=True,
                    help="draws the weights, the traffic and the sample "
                         "that is checked")
    ap.add_argument("--seconds", type=float, required=True,
                    help="length of the measured window")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0,
                    help="1: profile the window's end and report the "
                         "per-layer metrics")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(BENCH))
    from benchkit import harness
    try:
        harness.run_cell(BENCH.parent, args.workload, args.seed,
                         args.seconds, bool(args.trace), T_PROCESS)
    except (harness.NoAccelerator, FileNotFoundError) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    return 0


if __name__ == "__main__":
    sys.exit(main())
