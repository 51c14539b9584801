"""The ``dsv2lite.long_answer`` cell: its files are found by name, the
reference reads its sizes from its configuration file and agrees with
the served path, the kernel's counts, and the cell served through the
harness on the CPU at a small size."""
import dataclasses
import json
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchkit import mla, refmodel, spec as spec_mod
from conftest import ROOT, run_smoke

CELL = "dsv2lite.long_answer"
# DeepSeek-V2-Lite at a size the CPU serves in seconds: every kind of
# layer kept (the dense first layer, expert layers with shared experts),
# the router 16 wide of which 4 experts are held, YaRN as published
SMOKE_MODEL = dict(name="deepseek-v2-lite-smoke", n_layers=3, d_model=64,
                   n_heads=4, n_kv_heads=4, d_ff=96, vocab=256,
                   kv_lora_rank=32, qk_nope_head_dim=16,
                   qk_rope_head_dim=8, v_head_dim=16, n_experts=4,
                   n_routed_experts=16, first_expert=4, top_k=4,
                   d_expert=32, max_position=4096)
SMOKE_SERVE = dict(max_lanes=4, num_blocks=96)
# set as the chip's limit is, from two readings over eight seeds on the
# CPU at this size: the served path's widest gap, 0.066, and the float8
# control's smallest, 0.65 (PERF.md)
SMOKE_LIMIT = 0.2
SMOKE_MIX = {"loop": "closed", "clients": 6,
             "prompt": {"median": 16, "sigma": 0.5, "ladder": [16, 32]},
             "output": {"uniform": [8, 16]}, "ramp_s": 0.5}


def smoke_checkout(checkout):
    """The test checkout with this cell's model, pool and mix cut to the
    smoke size."""
    b = checkout / "bench"
    path = b / "configs" / "deepseek_v2_lite.json"
    conf = json.loads(path.read_text())
    conf["model"].update(SMOKE_MODEL)
    conf["serve"].update(SMOKE_SERVE)
    conf["check"]["max_logit_gap"] = SMOKE_LIMIT
    path.write_text(json.dumps(conf))
    (b / "traffic" / "long_answer.json").write_text(json.dumps(SMOKE_MIX))
    return checkout


def test_spec_finds_the_cells_files():
    sp = spec_mod.Spec(ROOT)
    cell = sp.cell(CELL)
    conf = sp.config(cell)
    assert conf["name"] == "deepseek_v2_lite"
    assert sp.traffic(cell)["clients"] == conf["serve"]["max_lanes"] == 32
    assert {m["name"] for m in sp.metrics(cell, False)} == \
        {"tpot_p95_ms", "setup_s"}
    assert {m["name"] for m in sp.metrics(cell, True)} == {
        "prefill_ms_per_ktok", "decode_step_ms.long_answer",
        "mla_attention_roofline.long_answer", "device_idle.long_answer",
        "hostdev_mb_per_step.long_answer", "decode_mfu.long_answer"}
    for m in sp.metrics(cell, True) + sp.metrics(cell, False):
        assert callable(sp.reader(m))
    # the cut: 8 of 64 experts held, everything else as published
    entry = sp.config_entry("deepseek_v2_lite")
    assert entry["reduced"] == ["n_routed_experts", "n_experts"]
    assert conf["n_routed_experts"] == conf["model"]["n_experts"] == 8
    assert conf["published"]["n_routed_experts"] == \
        conf["model"]["n_routed_experts"] == 64
    assert conf["num_hidden_layers"] == conf["model"]["n_layers"] == 27
    assert conf["kv_lora_rank"] == conf["model"]["kv_lora_rank"] == 512


def test_reference_reads_its_sizes_from_its_file():
    from repro.configs.deepseek_v2_lite import CONFIG
    from repro.models import layers
    ref = spec_mod.Spec(ROOT).reference(spec_mod.Spec(ROOT).cell(CELL))
    assert ref.MODEL["kv_lora_rank"] == 512 and ref.MODEL["top_k"] == 6
    m = 0.1 * 0.707 * math.log(40) + 1
    assert ref.softmax_scale() == pytest.approx(192 ** -0.5 * m * m)
    assert ref.softmax_scale() == pytest.approx(CONFIG.softmax_scale)
    np.testing.assert_allclose(ref.yarn_inv_freq(),
                               layers.yarn_inv_freq(CONFIG), rtol=1e-12)


def test_kernel_counts_by_hand():
    """Two lanes, 16-token pages: 20 cached tokens on 2 pages and 0 (no
    page, nothing attended), 2 heads, r 4 + rope 2, 3 layers."""
    model = dict(n_heads=2, kv_lora_rank=4, qk_rope_head_dim=2, n_layers=3)
    fl, by = mla.kernel_cost(model, [20, 0], 16)
    assert fl == 3 * (2 * 2 * 6 * 20 + 2 * 2 * 4 * 20)
    per_lane_io = 2 * 6 * 2 + 2 * 4 * 4 + 2 * 2 * 4
    assert by == 3 * (2 * 16 * 6 * 2 + 2 * per_lane_io)


def test_decode_flops_and_host_bytes_by_hand():
    """d 4, 2 heads, r 4 + rope 2, nope 3, v 3; 2 layers, the first
    dense (width 6), the second 2 of 8 experts (width 5, top 2) and one
    shared; vocabulary 10."""
    model = dict(d_model=4, n_heads=2, kv_lora_rank=4, qk_rope_head_dim=2,
                 qk_nope_head_dim=3, v_head_dim=3, n_layers=2,
                 n_dense_layers=1, d_ff=6, d_expert=5, n_experts=2,
                 n_routed_experts=8, top_k=2, n_shared_experts=1, vocab=10)
    # ctx 3, so 4 positions seen.  Attention: q 80, latent row 48,
    # absorbed query 48, scores 96, values 64, W_uv 48, W_o 48 = 432;
    # dense MLP 144; router 64 + shared 120 + half a token's two held
    # experts' 120 = 244; head 80
    assert mla.decode_token_flops(model, 3) == 2 * 432 + 144 + 244 + 80
    # 3 lanes padded to 4, 2 pages: 3 staged blocks of 16 rows at the
    # mirror's 128 lanes, operands 32 + 32, new rows 96, logits 80, the
    # routed count 4
    assert mla.decode_hostdev_bytes(model, [20, 0, 5], 3, 16) == \
        3 * 2 * 16 * 128 * 2 + 32 + 32 + 96 + 80 + 4
    # 7 tokens up, 2 layers of 7 rows of 6 down, the last logits
    assert mla.prefill_hostdev_bytes(model, 7) == 28 + 168 + 20


def _smoke_cfg():
    from repro.models.config import ModelConfig
    conf = json.loads((ROOT / "bench" / "configs"
                       / "deepseek_v2_lite.json").read_text())
    return ModelConfig(**dict(conf["model"], **SMOKE_MODEL))


@pytest.mark.parametrize("mode", ["kernel", "gather"])
def test_paged_decode_matches_the_reference(checkout, mode):
    """Prefill 13 tokens, then 11 decode steps through the latent pool
    (blocks of 4 tokens, so pages straddle the prompt's end), against the
    reference's full forward at every position.  Float32 weights and
    compute: what is left is the order of the sums (the absorbed decode
    against the expanded form, 2e-6 seen), so 1e-4.  The program's own
    draw (``lm.init``: each matrix at its contraction width's scale):
    attention, the routed and the shared experts each move the logits
    by far more than that."""
    from repro.kvcache.backend import make_backend
    cfg = dataclasses.replace(_smoke_cfg(), param_dtype="float32",
                              compute_dtype="float32")
    sp = spec_mod.Spec(smoke_checkout(checkout))
    ref = sp.reference(sp.cell(CELL))
    m = refmodel.Model.from_config(ref.MODEL)
    from repro.models import lm
    params = lm.init(cfg, jax.random.key(7)).params
    toks = np.asarray(jax.random.randint(jax.random.key(1), (24,), 0,
                                         cfg.vocab), np.int32)
    want = np.asarray(ref.logits(params, m, jnp.asarray(toks),
                                 jnp.arange(12, 24)))
    b = make_backend(cfg, "paged", num_blocks=32, block_size=4,
                     decode_mode=mode)
    sid, got, _ = b.new_seq(params, list(toks[:13]))
    gaps = [np.abs(got - want[0]).max()]
    for i, t in enumerate(range(13, 24)):
        out = b.decode(params, [sid], [int(toks[t])])
        gaps.append(np.abs(out[0] - want[i + 1]).max())
    assert max(gaps) < 1e-4, np.round(gaps, 7)
    # each part shows: without it the logits move by far more than 1e-4
    for drop in ("attn", "experts", "shared"):
        p = jax.tree.map(lambda a: a, params)
        if drop == "attn":
            p["blocks"]["attn"]["wo"] = 0 * p["blocks"]["attn"]["wo"]
        elif drop == "experts":
            p["blocks"]["moe"]["w_out"] = 0 * p["blocks"]["moe"]["w_out"]
        else:
            s = p["blocks"]["moe"]["shared"]
            s["wo"] = 0 * s["wo"]
        moved = np.asarray(ref.logits(p, m, jnp.asarray(toks),
                                      jnp.arange(12, 24)))
        assert np.abs(moved - want).max() > 0.05, drop


def test_cell_serves_and_checks_on_cpu(checkout):
    smoke_checkout(checkout)
    res = run_smoke(checkout, CELL, seconds=2.0)
    assert res["correct"], res["check"]
    assert res["attempted"] > 0 and res["failed"] == 0
    traced = run_smoke(checkout, CELL, seconds=2.0, trace=True)
    assert traced["metrics"]["prefill_ms_per_ktok"]["value"] > 0
    # from counts and shapes, so read on the CPU too
    assert traced["metrics"]["hostdev_mb_per_step.long_answer"]["value"] \
        > 0


def test_seeds_tool_reads_each_seed(checkout, capsys):
    """``tools/seeds.py`` on the CPU at the smoke size: one line per seed
    with the run's end-to-end metric, the check and the control."""
    import importlib.util
    smoke_checkout(checkout)
    path = ROOT / "bench" / "tools" / "seeds.py"
    spec = importlib.util.spec_from_file_location("bench_tool_seeds", path)
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    assert tool.main(["--workload", CELL, "--seeds", "3,4", "--seconds",
                      "1", "--control", "--ramps", "0.1,0.5,9"],
                     root=checkout, require_tpu=False) == 0
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("{")]
    assert [ln["seed"] for ln in lines] == [3, 4]
    for ln in lines:
        assert ln["correct"] and ln["tpot_p95_ms"] > 0
        assert ln["control_gap"] > ln["gap"]
        assert len(ln["done_s"]) == ln["done"] > 0
        # a shorter ramp's window read off the same run; none longer
        assert set(ln["by_ramp"]) == {"0.1", "0.5"}
        assert ln["by_ramp"]["0.5"]["tpot_p95_ms"] == ln["tpot_p95_ms"]
        assert ln["by_ramp"]["0.1"]["done"] <= ln["done"]
    assert (checkout / "chiprun_out" / "seeds.jsonl").exists()
