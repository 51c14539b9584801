"""``bench/run.py`` refuses to measure without a chip: a nonzero exit and
no result line, on the CPU and in a checkout that holds only the
benchmark."""
import os
import pathlib
import shutil
import subprocess
import sys

from conftest import BENCH, ROOT


def _run(cwd: pathlib.Path, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "qwen05b.chat",
         "--seed", str(2**33 + 5), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _no_result(proc):
    assert proc.returncode != 0
    assert not any(line.lstrip().startswith("{")
                   for line in proc.stdout.splitlines())


def test_exits_nonzero_without_a_tpu():
    proc = _run(ROOT)
    _no_result(proc)
    assert "TPU" in proc.stderr


def test_exits_nonzero_with_only_the_benchmark(tmp_path):
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    proc = _run(tmp_path, {"PYTHONPATH": ""})
    _no_result(proc)
    assert not (tmp_path / ".jax_cache").exists()
