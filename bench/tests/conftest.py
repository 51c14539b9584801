"""Shared set-up of the benchmark's CPU tests: ``benchkit`` on the path,
and a throwaway checkout whose cells run at a size the CPU can hold."""
from __future__ import annotations

import json
import pathlib
import shutil
import sys

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

# the cells' models at a size the CPU serves in seconds: every kind of
# layer kept (hymba: windowed and global attention beside the SSM)
SMOKE_MODELS = {
    "qwen1_5_0_5b": dict(n_layers=2, d_model=64, n_heads=4, n_kv_heads=4,
                         d_ff=128, vocab=256, name="qwen-smoke"),
    "hymba_1_5b": dict(n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
                       d_ff=128, vocab=256, sliding_window=16,
                       global_every=2, ssm_state=8, d_ssm_head=16,
                       ssm_chunk=8, name="hymba-smoke"),
}
SMOKE_SERVE = {"qwen1_5_0_5b": dict(max_lanes=4, num_blocks=64),
               "hymba_1_5b": dict(max_lanes=4, num_blocks=96)}
# the widest logit gap allowed at smoke size, set as the chip's limits
# are, from two readings over six seeds on the CPU: the served path's
# largest gap and the float8 control's smallest (qwen 0.0066 / 0.028,
# hymba 0.062 / 0.47).  The smoke qwen's tied 0.02-scale embedding gives
# small logits, so its gaps are small too.
SMOKE_LIMIT = {"qwen1_5_0_5b": 0.015, "hymba_1_5b": 0.2}
SMOKE_TRAFFIC = {
    "chat": {"loop": "open", "rate_per_s": 4.0,
             "prompt": {"median": 16, "sigma": 0.5, "ladder": [8, 16, 32]},
             "output": {"median": 8, "sigma": 0.5, "min": 4, "max": 16},
             "ramp_s": 0.5, "drain_gap_s": 2.0},
    "batch": {"loop": "closed", "clients": 6,
              "prompt": {"median": 16, "sigma": 0.5, "ladder": [16, 32]},
              "output": {"uniform": [4, 16]}, "ramp_s": 0.5},
}


def make_checkout(dest: pathlib.Path) -> pathlib.Path:
    """A checkout at ``dest`` holding ``BENCHMARK.json``, a copy of
    ``bench/`` with its configurations and mixes cut to smoke size, and
    the program (``src``, linked)."""
    shutil.copytree(BENCH, dest / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    # the hybrid's files stay under bench/ for the cell to come back
    # (PERF.md); the CPU tests serve it through the same harness
    spec["configs"].append({"name": "hymba_1_5b",
                            "source": "https://arxiv.org/abs/2411.13676",
                            "file": "bench/configs/hymba_1_5b.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "hymba15b.batch",
                              "config": "hymba_1_5b", "traffic": "batch",
                              "chips": 1, "why": "test"})
    (dest / "BENCHMARK.json").write_text(json.dumps(spec))
    (dest / "src").symlink_to(ROOT / "src")
    for name, sizes in SMOKE_MODELS.items():
        path = dest / "bench" / "configs" / f"{name}.json"
        conf = json.loads(path.read_text())
        conf["model"].update(sizes)
        conf["serve"].update(SMOKE_SERVE[name])
        conf["check"]["max_logit_gap"] = SMOKE_LIMIT[name]
        path.write_text(json.dumps(conf))
    for name, mix in SMOKE_TRAFFIC.items():
        (dest / "bench" / "traffic" / f"{name}.json").write_text(
            json.dumps(mix))
    return dest


@pytest.fixture
def checkout(tmp_path) -> pathlib.Path:
    return make_checkout(tmp_path / "checkout")


def run_smoke(root: pathlib.Path, cell: str, seed: int = 20240611,
              seconds: float = 2.0, trace: bool = False) -> dict:
    """One run of ``cell`` in the smoke checkout on the CPU, past the
    harness's look for a chip; returns the result line's object."""
    import io
    import time

    from benchkit import harness
    out, err = io.StringIO(), io.StringIO()
    res = harness.run_cell(root, cell, seed, seconds, trace,
                           time.perf_counter(), require_tpu=False,
                           out=out, err=err)
    assert json.loads(out.getvalue().splitlines()[-1]) == res
    return res
