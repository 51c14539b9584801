"""DeepSeek-V2-Lite's mechanisms at a small size on the CPU: YaRN RoPE
and the MLA softmax scale at the published values, the parameter count,
the router's gate options, an expert share that adds up to the uncut
layer, the latent KV kind through the paged backend (prefill write-back,
staging, commit) and the paged decode against the model's own forward.
"""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.configs.deepseek_v2_lite import CONFIG, expert_share, smoke
from repro.kvcache.backend import ShardedPagedBackend, make_backend, \
    mirror_rows
from repro.models import layers, lm, moe
from repro.models.config import ModelConfig


def _f32(cfg: ModelConfig) -> ModelConfig:
    return dataclasses.replace(cfg, param_dtype="float32",
                               compute_dtype="float32")


def test_yarn_frequencies_and_softmax_scale_at_the_published_values():
    """64 rotated lanes, base 10000, factor 40 over 4096 positions,
    beta 32 / 1: the correction range is [10, 23]; unscaled below it,
    divided by 40 above it, a linear ramp between.  The softmax scale is
    192^-0.5 times the square of 0.1 * 0.707 * ln 40 + 1."""
    inv = layers.yarn_inv_freq(CONFIG)
    j = np.arange(32)
    base = 10000.0 ** (-2.0 * j / 64)
    np.testing.assert_allclose(inv[:11], base[:11], rtol=1e-12)
    np.testing.assert_allclose(inv[23:], base[23:] / 40, rtol=1e-12)
    ramp = (j[11:23] - 10) / 13
    np.testing.assert_allclose(inv[11:23],
                               base[11:23] * ((1 - ramp) + ramp / 40),
                               rtol=1e-12)
    m = 0.1 * 0.707 * math.log(40) + 1
    assert CONFIG.yarn_magnitude(0.707) == pytest.approx(m) \
        == pytest.approx(1.2608, abs=1e-4)
    # cos and sin carry mscale / mscale_all_dim: 1 here
    cos, sin = layers.rope_freqs(CONFIG, jnp.arange(1, 4))
    np.testing.assert_allclose(np.asarray(cos ** 2 + sin ** 2), 1.0,
                               rtol=1e-6)
    louder = dataclasses.replace(CONFIG, rope_mscale=1.0)
    cos2, sin2 = layers.rope_freqs(louder, jnp.arange(1, 4))
    np.testing.assert_allclose(
        np.asarray(cos2), np.asarray(cos) * louder.yarn_magnitude(1.0)
        / louder.yarn_magnitude(0.707), rtol=1e-6)
    assert CONFIG.softmax_scale == pytest.approx(192 ** -0.5 * m * m)
    assert CONFIG.softmax_scale == pytest.approx(0.11472, abs=1e-5)
    # unscaled configs keep plain RoPE over the whole head
    plain = configs.get("qwen1_5_0_5b")
    assert plain.rope_dim == 64 and plain.softmax_scale == 64 ** -0.5
    cos, _ = layers.rope_freqs(CONFIG, jnp.arange(3))
    assert cos.shape == (3, 32)


def test_parameter_count_of_the_model_and_of_one_chips_share():
    """15.7B as published; one chip of eight under expert parallelism
    holds 8 of each MoE layer's 64 experts: 3.11B, and the router keeps
    its 64 outputs."""
    assert CONFIG.n_params() / 1e9 == pytest.approx(15.71, abs=0.01)
    share = expert_share(CONFIG, 8, 0)
    assert (share.n_experts, share.router_width, share.first_expert) == \
        (8, 64, 0)
    assert share.n_params() / 1e9 == pytest.approx(3.11, abs=0.01)
    assert expert_share(CONFIG, 8, 3).first_expert == 24
    # the parameter tree holds what n_params counts, and the layer norms
    # (two per layer and the final one), which it leaves out
    shapes = jax.eval_shape(lambda: lm.init(share, jax.random.key(0)).params)
    held = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))
    assert held == share.n_params() + (2 * 27 + 1) * 2048
    assert shapes["blocks"]["moe"]["router"].shape == (26, 2048, 64)
    # expert matrices flat, expert-major
    assert shapes["blocks"]["moe"]["w_in"].shape == (26, 8 * 2048, 1408)
    assert shapes["blocks"]["moe"]["w_out"].shape == (26, 8 * 1408, 2048)


def test_router_gate_options():
    """Renormalised top-k gates by default; DeepSeek's are the softmax
    values over every expert, unnormalised, times the routed scaling
    factor."""
    cfg = _f32(smoke())
    x = jax.random.normal(jax.random.key(1), (5, cfg.d_model))
    p = moe.moe_init(jax.random.key(2), cfg).params
    probs = jax.nn.softmax(x @ p["router"], -1)
    want_idx = np.argsort(-np.asarray(probs), -1)[:, :cfg.top_k]
    idx, gates, _ = moe.router_topk(p, x, cfg)
    np.testing.assert_array_equal(np.asarray(idx), want_idx)
    np.testing.assert_allclose(np.asarray(gates),
                               np.take_along_axis(np.asarray(probs),
                                                  want_idx, -1), rtol=1e-5)
    scaled = dataclasses.replace(cfg, router_scale=2.5)
    np.testing.assert_allclose(
        np.asarray(moe.router_topk(p, x, scaled)[1]),
        2.5 * np.asarray(gates), rtol=1e-6)
    renorm = dataclasses.replace(cfg, router_renorm=True)
    assert configs.get("qwen1_5_0_5b").router_renorm     # the default
    np.testing.assert_allclose(
        np.asarray(moe.router_topk(p, x, renorm)[1]).sum(-1), 1.0,
        rtol=1e-5)


def test_expert_shares_add_up_to_the_uncut_layer():
    """Four chips holding experts 0-3, 4-7, 8-11 and 12-15 of 16: their
    routed parts, plus the shared experts counted once, are the uncut
    layer's output.  Each share routes over all 16 and counts only the
    assignments to its own experts."""
    cfg = _f32(smoke())
    assert cfg.n_experts == 16 and cfg.router_width == 16
    p = moe.moe_init(jax.random.key(3), cfg).params
    x = jax.random.normal(jax.random.key(4), (2, 7, cfg.d_model))
    whole, aux = moe.moe_apply(p, x, cfg)
    shared = layers.mlp_apply(p["shared"], x, cfg)
    total, routed = shared, 0.0
    for chip in range(4):
        share = expert_share(cfg, 4, chip)
        sl = slice(share.first_expert, share.first_expert + 4)
        ps = dict(p, **{k: w[sl].reshape(-1, w.shape[-1]) for k, w in
                        zip(("w_in", "w_gate", "w_out"),
                            moe.expert_stacks(p))})
        y, aux_s = moe.moe_apply(ps, x, share)
        total = total + (y - shared)
        routed += float(aux_s["moe_routed_here"])
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               rtol=1e-5, atol=1e-5)
    assert routed == float(aux["moe_routed_here"]) == 2 * 7 * cfg.top_k
    # the routed part does move the output
    assert float(jnp.abs(whole - shared).max()) > 0.1


def test_decode_form_of_an_expert_share_matches_the_grouped_form():
    """A decode step's input (one token a lane, S = 1) and the same
    tokens as one sequence take the same sorted grouped path: the same
    output, the same count of assignments routed here, and each
    token's routed part is its held experts' gated outputs."""
    cfg = expert_share(_f32(smoke()), 4, 2)
    p = moe.moe_init(jax.random.key(5), cfg).params
    x = jax.random.normal(jax.random.key(6), (9, 1, cfg.d_model))
    lanes, aux_d = moe.moe_apply(p, x, cfg)
    seq, aux_g = moe.moe_apply(p, x.reshape(1, 9, -1), cfg)
    np.testing.assert_allclose(np.asarray(lanes).reshape(9, -1),
                               np.asarray(seq).reshape(9, -1),
                               rtol=1e-5, atol=1e-5)
    assert float(aux_d["moe_routed_here"]) == \
        float(aux_g["moe_routed_here"]) > 0
    xf = x[:, 0]
    idx, gates, _ = moe.router_topk(p, xf, cfg)
    w_in, w_gate, w_out = moe.expert_stacks(p)
    want = np.zeros((9, cfg.d_model), np.float32)
    for t in range(9):
        for e, g in zip(np.asarray(idx[t]), np.asarray(gates[t])):
            j = e - cfg.first_expert
            if 0 <= j < cfg.n_experts:
                a = jax.nn.silu(xf[t] @ w_gate[j]) * (xf[t] @ w_in[j])
                want[t] += g * np.asarray(a @ w_out[j])
    routed = np.asarray(lanes[:, 0] - layers.mlp_apply(p["shared"], x,
                                                        cfg)[:, 0])
    np.testing.assert_allclose(routed, want, rtol=1e-5, atol=1e-5)
    assert np.abs(want).max() > 0.1


@pytest.mark.parametrize("mode", ["kernel", "gather"])
def test_paged_decode_matches_the_forward(mode):
    """Prefill 13 tokens, then 11 decode steps through the latent pool
    (blocks of 4 tokens, so pages straddle the prompt's end), against
    the model's full forward at every position, at float32: the absorbed
    decode reorders the expanded form's sums (4e-6 seen)."""
    cfg = _f32(expert_share(smoke(), 4, 1))
    params = lm.init(cfg, jax.random.key(0)).params
    toks = np.asarray(jax.random.randint(jax.random.key(1), (1, 24), 0,
                                         cfg.vocab))
    full, _ = lm.forward(params, cfg, jnp.asarray(toks))
    full = np.asarray(full[0])
    b = make_backend(cfg, "paged", num_blocks=32, block_size=4,
                     decode_mode=mode)
    sid, got, _ = b.new_seq(params, list(toks[0, :13]))
    gaps = [np.abs(got - full[12]).max()]
    for t in range(13, 24):
        out = b.decode(params, [sid], [int(toks[0, t])])
        gaps.append(np.abs(out[0] - full[t]).max())
    assert max(gaps) < 1e-4, np.round(gaps, 7)
    if mode == "kernel":   # counted beside the logits
        assert 0 < b.stats.moe_routed_here <= 11 * 2 * cfg.top_k
    else:                  # the gather path counts none
        assert b.stats.moe_routed_here == 0


def test_routed_count_with_every_expert_held():
    """With every expert held, each live lane's token is routed to top_k
    held experts in every MoE layer: the count is exact."""
    cfg = smoke()
    params = lm.init(cfg, jax.random.key(0)).params
    b = make_backend(cfg, "paged", num_blocks=64, block_size=4)
    sids = [b.new_seq(params, [1, 2, 3, 4, 5][:n])[0] for n in (3, 5, 4)]
    for step in range(5):
        b.decode(params, sids, [7, 8, 9])
    n_moe = cfg.n_layers - cfg.n_dense_layers
    assert b.stats.moe_routed_here == 5 * 3 * cfg.top_k * n_moe


def test_latent_pool_round_trip():
    """The latent kind of KV: one buffer of rows ``[c, k_pe]`` per token
    and layer, no V.  The prefill's write-back stores the prompt's rows,
    the staged mirror holds them folded and padded to whole 128-lane
    tiles, and each committed decode step adds its token's row — the
    rows the model's own prefill computes for the whole sequence."""
    cfg = _f32(smoke())
    W = cfg.latent_width
    params = lm.init(cfg, jax.random.key(0)).params
    toks = [int(t) for t in np.asarray(jax.random.randint(
        jax.random.key(2), (15,), 0, cfg.vocab))]
    b = make_backend(cfg, "paged", num_blocks=16, block_size=4)
    pool = b.pool
    assert pool.cfg.kv_kind == "latent" and pool.v_pages is None
    assert pool.k_pages.shape == (cfg.n_layers, 16, 4, 1, W)
    sid, _, _ = b.new_seq(params, toks[:9])
    for t in toks[9:]:
        b.decode(params, [sid], [t])
    b.flush()
    _, parts = lm.prefill_parts(params, cfg, jnp.asarray([toks]))
    assert parts["v"] is None
    want = np.asarray(parts["k"][:, 0, :15, 0])            # (L, 15, W)
    table = b.table(sid)
    assert table.num_tokens == 15
    got = np.concatenate([pool.k_pages[:, bid, :, 0] for bid in
                          table.blocks], axis=1)[:, :15]
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    # the device mirror: folded, padded with zeros to 128 lanes
    kp, vp = b._staged_pages()
    assert vp is None and kp.shape == (cfg.n_layers, 16, 4, 128)
    mirror = np.asarray(kp)
    np.testing.assert_array_equal(mirror, mirror_rows(pool.k_pages, True))
    assert (mirror[..., W:] == 0).all()
    assert mirror_rows(np.zeros((1, 2, 4, 16, 64)), False).shape == \
        (1, 2, 4, 1024)
    # host<->device bytes count the latent rows as they are
    assert b.stats.h2d_bytes > 0 and b.stats.d2h_bytes > 0


def test_sharded_backend_pages_no_latent_kv():
    with pytest.raises(NotImplementedError, match="latent"):
        ShardedPagedBackend(smoke(), num_blocks=16, block_size=4,
                            n_shards=2)
