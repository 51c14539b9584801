"""Serve one cell for several seeds in one process, and print for each
seed what a run of the benchmark reports and what the check reads.

    python3 bench/tools/seeds.py --workload dsv2lite.long_answer \\
        --seeds 11,12,13 --seconds 51 --control

For each seed, as ``control.py`` does: weights from the seed, the served
system (warmed up for the first seed only; later seeds reuse its
compiled programs), the cell's own traffic for its ramp and a window of
``--seconds``, then the system freed and the check run over the
finished requests.  The line per seed holds ``tpot_p95_ms`` and
``prefill_ms_per_ktok`` read as the benchmark reads them, the widest
logit gap and its limit, with ``--control`` the float8 control's gap at
the same positions, the requests attempted and finished, when each
finished request ended (seconds from the start of the traffic, so the
ramp a cell needs for its answers to end inside the window can be read
off), and the chip's peak memory before the check (for the first seed,
set-up included).  ``--ramps 40,50`` adds, for each shorter ramp, what a
run with it would have read: a run serves the same as this one up to
its window's end, so the ``tpot_p95_ms`` of a window of ``--seconds``
after that ramp and the answers ended by then are read off this run
(written to standard error as soon as the serving ends, too).
One JSON line per seed on stdout, appended to
``chiprun_out/seeds.jsonl``.  Runs on the chip only; the benchmark's own
runs never run it.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import pathlib
import sys
import time

BENCH = pathlib.Path(__file__).resolve().parents[1]


def main(argv=None, root=None, require_tpu: bool = True) -> int:
    t_process = time.perf_counter()
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", action="store_true",
                    help="also read the float8 control's gap")
    ap.add_argument("--ramps", default="",
                    help="comma-separated shorter ramps (s) to read the "
                         "window after, from the same run")
    ap.add_argument("--mix", default="{}",
                    help="JSON object of traffic keys to override, for "
                         "readings of the check at other sizes")
    args = ap.parse_args(argv)
    root = pathlib.Path(root or BENCH.parent)
    sys.path.insert(0, str(BENCH))
    from benchkit import harness, readers, refmodel

    setup = harness.start(root, args.workload, require_tpu=require_tpu)
    setup.mix.update(json.loads(args.mix))
    out = root / "chiprun_out"
    out.mkdir(exist_ok=True)
    limit = setup.conf["check"]["max_logit_gap"]
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        system = harness.build(setup, seed, warm_up=i == 0)
        reqs, w0, w1 = harness.serve(system, setup.mix, seed, args.seconds)
        attempted, failed = harness.counts(setup.mix, reqs, w0, w1)
        peak = (setup.devs[0].memory_stats() or {}).get("peak_bytes_in_use")
        run = harness.Run(
            w0=w0, w1=w1, setup_s=w0 - (t_process if i == 0 else t0),
            requests=reqs, steps=harness.free(system),
            prefills=system.recorder.prefills,
            decodes=system.recorder.decodes,
            model=refmodel.Model.from_config(setup.conf["model"]),
            serve=setup.conf["serve"])
        t_base = w0 - float(setup.mix.get("ramp_s", 0))
        by_ramp = {}
        for ramp in (float(r) for r in args.ramps.split(",") if r):
            if t_base + ramp > w0:
                continue
            sub = dataclasses.replace(run, w0=t_base + ramp,
                                      w1=t_base + ramp + args.seconds)
            by_ramp[f"{ramp:g}"] = {
                "tpot_p95_ms": readers.tpot_p95_ms(sub),
                "done": sum(r.done and r.times[-1] < sub.w1 for r in reqs)}
        if by_ramp:
            print(json.dumps({"seed": seed, "by_ramp": by_ramp}),
                  file=sys.stderr, flush=True)
        t1 = time.perf_counter()
        gap, n_req, n_tok = harness.checked_gap(setup, system, reqs, seed)
        check_s = time.perf_counter() - t1
        line = {"workload": args.workload, "seed": seed, "mix": args.mix,
                "tpot_p95_ms": readers.tpot_p95_ms(run),
                "prefill_ms_per_ktok": readers.prefill_ms_per_ktok(run),
                "setup_s": run.setup_s, "gap": gap, "limit": limit,
                "correct": n_req > 0 and gap <= limit,
                "requests": n_req, "tokens": n_tok,
                "attempted": attempted, "failed": failed,
                "done": sum(r.done for r in reqs),
                "done_s": sorted(round(r.times[-1] - t_base, 1)
                                 for r in reqs if r.done),
                "memory_peak_bytes": peak, "check_s": check_s}
        if by_ramp:
            line["by_ramp"] = by_ramp
        if args.control:
            line["control_gap"] = harness.checked_gap(setup, system, reqs,
                                                       seed, "fp8")[0]
        print(json.dumps(line), flush=True)
        with open(out / "seeds.jsonl", "a", encoding="utf-8") as fh:
            fh.write(json.dumps(line) + "\n")
        system.params = None
    return 0


if __name__ == "__main__":
    sys.exit(main())
