"""Sweep an open-loop cell's arrival rate from one set-up, to find its
knee: the highest rate at which the backlog does not grow and a stated
share of the requests meets both latency limits.

    python3 bench/tools/knee.py --workload qwen05b.chat --seed 11 \\
        --seconds 30 --rates 0.5,1,1.5,2

Builds and warms the cell's system once, then serves the cell's mix at
each rate in turn (ramp, window, drain), and prints one JSON line per
rate: requests due in the window, those not finished by the drain
limit, TTFT and gap percentiles, the share meeting both limits (a
request meets the gap limit when its mean gap between tokens is under
it; an unfinished one meets neither) and the backlog left at the
window's close.  The same lines go to ``chiprun_out/knee.jsonl``.
Stops after the first rate at which under half the requests meet the
limits: past the knee the backlog only grows.
Runs on the chip only; it is a tool, and the benchmark never runs it.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

BENCH = pathlib.Path(__file__).resolve().parents[1]


def summarize(reqs, w0, w1, ttft_ms, gap_ms):
    from benchkit import stats
    due = [r for r in reqs if w0 <= r.due < w1]
    done = [r for r in due if r.done]
    ttft = [(r.times[0] - r.due) * 1e3 for r in done]
    gaps = [(b - a) * 1e3 for r in done for a, b in zip(r.times,
                                                        r.times[1:])]

    def meets(r, gap=gap_ms):
        if not r.done or (r.times[0] - r.due) * 1e3 > ttft_ms:
            return False
        n = len(r.times) - 1
        return n == 0 or (r.times[-1] - r.times[0]) * 1e3 / n <= gap

    backlog = sum(1 for r in reqs if r.due < w1
                  and (not r.times or r.times[0] >= w1))
    return {"due": len(due), "unfinished": len(due) - len(done),
            "ttft_p50_ms": stats.percentile(ttft, 50),
            "ttft_p95_ms": stats.percentile(ttft, 95),
            "gap_p50_ms": stats.percentile(gaps, 50),
            "gap_p95_ms": stats.percentile(gaps, 95),
            "met_share": sum(meets(r) for r in due) / max(len(due), 1),
            "met_share_by_gap_ms": {
                g: sum(meets(r, g) for r in due) / max(len(due), 1)
                for g in (100, 200, 300, 500)},
            "backlog_at_close": backlog}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True,
                    help="comma-separated arrival rates, requests/s")
    ap.add_argument("--ttft-ms", type=float, default=2000.0)
    ap.add_argument("--gap-ms", type=float, default=250.0)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(BENCH))
    from benchkit import harness

    setup = harness.start(BENCH.parent, args.workload)
    system = harness.build(setup, args.seed)
    out = BENCH.parent / "chiprun_out"
    out.mkdir(exist_ok=True)
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        mix = dict(setup.mix, rate_per_s=rate)
        n0 = len(system.driver.steps)
        t0 = time.perf_counter()
        reqs, w0, w1 = harness.serve(system, mix, args.seed + i,
                                     args.seconds)
        steps = [s for s in system.driver.steps[n0:] if w0 <= s[0] < w1]
        line = {"rate_per_s": rate, "seconds": args.seconds,
                **summarize(reqs, w0, w1, args.ttft_ms, args.gap_ms),
                "steps": len(steps),
                "mean_step_ms": 1e3 * sum(b - a for a, b, _ in steps)
                / max(len(steps), 1),
                "mean_lanes": sum(m for _, _, m in steps)
                / max(len(steps), 1),
                "compiles": sum(1 for t in setup.compiles if t >= t0)}
        print(json.dumps(line), flush=True)
        with open(out / "knee.jsonl", "a", encoding="utf-8") as fh:
            fh.write(json.dumps(line) + "\n")
        if line["met_share"] < 0.5:
            break
        harness.drain(system.driver)
    return 0


if __name__ == "__main__":
    sys.exit(main())
