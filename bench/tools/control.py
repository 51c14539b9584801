"""Read the correctness check's two readings for a cell: the program's
widest logit gap over many seeds, and the control's.

    python3 bench/tools/control.py --workload qwen05b.chat \\
        --seeds 101,102,103 --seconds 10

For each seed, in one process: weights from the seed, the served system
(warmed up for the first seed only; later seeds reuse its compiled
programs), a window of the cell's own traffic at its own load, then the
system freed and the check run over the finished requests twice: once
as the benchmark runs it (the gap of the served tokens under the float32
reference) and once with the control in the program's place (the gap of
the token that the reference computed with float8 operands puts first,
at the same positions).  Prints one JSON line per seed and appends it
to ``chiprun_out/control.jsonl``.  The lower reading of the limit is the
largest program gap over the seeds, the upper the smallest control gap.
Runs on the chip only; the benchmark's own runs never run it.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

BENCH = pathlib.Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--mix", default="{}",
                    help="JSON object of traffic keys to override, for "
                         "witness runs at other sizes")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(BENCH))
    from benchkit import harness

    setup = harness.start(BENCH.parent, args.workload)
    setup.mix.update(json.loads(args.mix))
    out = BENCH.parent / "chiprun_out"
    out.mkdir(exist_ok=True)
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        system = harness.build(setup, seed, warm_up=i == 0)
        reqs, w0, w1 = harness.serve(system, setup.mix, seed, args.seconds)
        attempted, failed = harness.counts(setup.mix, reqs, w0, w1)
        harness.free(system)
        t1 = time.perf_counter()
        gap, n_req, n_tok = harness.checked_gap(setup, system, reqs, seed)
        t2 = time.perf_counter()
        ctl, _, _ = harness.checked_gap(setup, system, reqs, seed, "fp8")
        line = {"seed": seed, "mix": args.mix,
                "gap": gap, "control_gap": ctl,
                "requests": n_req, "tokens": n_tok,
                "attempted": attempted, "failed": failed,
                "serve_s": t1 - t0, "check_s": t2 - t1,
                "control_s": time.perf_counter() - t2}
        print(json.dumps(line), flush=True)
        with open(out / "control.jsonl", "a", encoding="utf-8") as fh:
            fh.write(json.dumps(line) + "\n")
        system.params = None
    return 0


if __name__ == "__main__":
    sys.exit(main())
