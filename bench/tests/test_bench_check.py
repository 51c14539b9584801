"""The comparison that decides ``correct``, on the CPU at smoke size.

The served path (prefill, KV write-back and staging, kernel decode, the
hybrid's carried SSM and conv state, greedy sampling) agrees with the
plain float32 reference, and the control (the reference with float8
operands in the program's place) does not.  ``test_bench_faults`` breaks
the timed path underneath.
"""
import pytest

from conftest import run_smoke

CELLS = ["qwen05b.chat", "hymba15b.batch"]


@pytest.mark.parametrize("cell", CELLS)
def test_served_path_agrees_with_the_reference(checkout, cell):
    res = run_smoke(checkout, cell)
    gap = res["check"]["max_logit_gap"]
    assert res["correct"], gap
    assert res["attempted"] > 0 and res["failed"] == 0
    assert gap["value"] <= gap["limit"]


@pytest.mark.parametrize("cell", CELLS)
def test_control_fails_the_check(checkout, cell):
    """As ``bench/tools/control.py`` reads it on the chip: the program's
    run, then the check once as the benchmark makes it and once with the
    control's picks."""
    from benchkit import harness
    seed = 2**32 + 13
    setup = harness.start(checkout, cell, require_tpu=False)
    # warmed up, so that no compile eats the window on a loaded host and
    # the window always finishes requests to compare
    system = harness.build(setup, seed)
    reqs, _, _ = harness.serve(system, setup.mix, seed, 10.0)
    harness.free(system)
    limit = setup.conf["check"]["max_logit_gap"]
    gap, n_req, _ = harness.checked_gap(setup, system, reqs, seed)
    ctl, _, n_tok = harness.checked_gap(setup, system, reqs, seed, "fp8")
    assert n_req > 0 and gap <= limit
    assert ctl > limit, (ctl, limit, n_tok)
