"""A configuration, a traffic mix, a per-layer metric and a cell are
added with new files and new entries in ``BENCHMARK.json``, and no edit
to a file that is there."""
import hashlib
import json
import shutil

from conftest import run_smoke


def _digests(root):
    return {p.relative_to(root): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted((root / "bench").rglob("*")) if p.is_file()
            and "__pycache__" not in p.parts}


def test_new_cell_from_new_files_only(checkout):
    before = _digests(checkout)
    b = checkout / "bench"
    # a configuration: its file and its reference beside it
    conf = json.loads((b / "configs" / "qwen1_5_0_5b.json").read_text())
    conf["name"] = "throwaway_lm"
    conf["model"].update(n_layers=1, name="throwaway")
    (b / "configs" / "throwaway_lm.json").write_text(json.dumps(conf))
    shutil.copy(b / "configs" / "qwen1_5_0_5b.py",
                b / "configs" / "throwaway_lm.py")
    # a traffic mix: parameters only
    (b / "traffic" / "throwaway_mix.json").write_text(json.dumps(
        {"loop": "open", "rate_per_s": 3.0,
         "prompt": {"median": 8, "sigma": 0.3, "ladder": [8, 16]},
         "output": {"median": 4, "sigma": 0.3, "min": 2, "max": 6},
         "ramp_s": 0.2, "drain_gap_s": 2.0}))
    # a per-layer metric: a reader of its own
    (b / "metrics" / "steps_in_window.py").write_text(
        '"""steps_in_window: engine steps that started in the window."""\n'
        "def read(run):\n"
        "    return sum(1 for s in run.steps if run.in_window(s[0]))\n")
    spec = json.loads((checkout / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "throwaway_lm", "source": "test",
                            "file": "bench/configs/throwaway_lm.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "throwaway.cell",
                              "config": "throwaway_lm",
                              "traffic": "throwaway_mix", "chips": 1,
                              "why": "test"})
    spec["end_to_end"] = [dict(m, workloads=m["workloads"]
                               + ["throwaway.cell"])
                          if m["name"] == "tpot_p95_ms" else m
                          for m in spec["end_to_end"]]
    spec["per_layer"].append({"name": "steps_in_window", "unit": "steps",
                              "better": "higher",
                              "source": "program_counter",
                              "layer": "engine step",
                              "moves": "tpot_p95_ms",
                              "workloads": ["throwaway.cell"]})
    (checkout / "BENCHMARK.json").write_text(json.dumps(spec))

    after = _digests(checkout)
    assert {k: after[k] for k in before} == before

    plain = run_smoke(checkout, "throwaway.cell", seconds=1.5)
    assert plain["correct"]
    assert set(plain["metrics"]) == {"tpot_p95_ms", "setup_s"}
    traced = run_smoke(checkout, "throwaway.cell", seconds=1.5, trace=True)
    assert traced["metrics"]["steps_in_window"]["value"] > 0
    assert traced["metrics"]["steps_in_window"]["unit"] == "steps"
