"""Plain float32 reference of DeepSeek-V2-Lite, one chip's share of its
experts (multi-head latent attention, sparse experts with shared ones).

The sizes the reference needs beyond ``refmodel.Model`` (MLA, YaRN, the
experts) are read from the ``model`` entry of ``deepseek_v2_lite.json``
beside this file.  Per layer: ``x += mla(rmsnorm(x))``, then ``x +=
ffn(rmsnorm(x))``, where ``ffn`` is a gated-SiLU MLP of width
``d_ff`` in the first ``n_dense_layers`` layers and the expert layer in
the rest; then the final RMSNorm and the untied LM head.

MLA (no q compression; H heads, r = kv_lora_rank, nope / rope / v head
sizes), for the hidden state h of every position:

    q = h W_q, per head split into q_nope and q_pe
    [c, k_pe] = h W_kva;  c = rmsnorm(c)  (its own scale);  k_pe is one
        key shared by every head
    k_nope_h = c W_uk,h;  v_h = c W_uv,h
    RoPE on q_pe and k_pe only
    score_h(i, j) = (q_nope_h(i) . k_nope_h(j) + q_pe_h(i) . k_pe(j)) s,
        causal, s = (nope + rope)^-0.5 m^2,  m = 0.1 mscale_all_dim
        ln(factor) + 1
    o = concat_h(softmax(score_h) v_h) W_o

YaRN RoPE over the rope lanes (dimension D, base theta): with
``corr(b) = D ln(original / (2 pi b)) / (2 ln theta)``, ``low =
floor(corr(beta_fast))``, ``high = ceil(corr(beta_slow))`` and ``ramp_j
= clip((j - low) / (high - low), 0, 1)``: ``inv_freq_j = theta^(-2j/D)
((1 - ramp_j) + ramp_j / factor)``; cos and sin are multiplied by
``m(mscale) / m(mscale_all_dim)``, 1 here.

The expert layer: router logits ``h W_r`` over all ``n_routed_experts``
experts, softmax over all of them, the top ``top_k`` (greedy, one
group); gates are those softmax values (renormalised to sum 1 only when
``router_renorm``) times ``router_scale``; then

    y = sum over e in top_k that this chip holds of
            g_e W2_e(silu(W1_e h) * W3_e h)   +   shared(h)

``shared`` is the ``n_shared_experts`` shared experts as one gated MLP
of their summed width (the same function).  This chip holds experts
``first_expert .. first_expert + n_experts - 1``; what the experts held
elsewhere would add is left out, as it is in the program.

Departures from the published model: RoPE rotates halves (lanes j and
j + D/2) where DeepSeek rotates interleaved pairs, a fixed permutation
of the rope lanes of W_q and W_kva, so with random weights the same
family of functions; the weights are random from the seed
(``benchkit.weights``), not the trained checkpoint.
"""
from __future__ import annotations

import functools
import json
import math
import pathlib

import jax
import jax.numpy as jnp
import numpy as np

from benchkit.refmodel import HIGHEST, f32, mm, q8, rmsnorm

CONF = json.loads(pathlib.Path(__file__).with_suffix(".json").read_text())
MODEL = CONF["model"]
QUERY_BLOCK = 512          # query rows of one block of the attention


def yarn_magnitude(mscale: float) -> float:
    f = MODEL.get("rope_factor", 1.0)
    return 0.1 * mscale * math.log(f) + 1.0 if f > 1.0 and mscale else 1.0


def yarn_inv_freq() -> np.ndarray:
    d, theta = MODEL["qk_rope_head_dim"], MODEL["rope_theta"]
    inv = theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)

    def corr(b):
        return d * math.log(MODEL["rope_original_max_position"]
                            / (2 * math.pi * b)) / (2 * math.log(theta))

    low = max(math.floor(corr(MODEL["rope_beta_fast"])), 0)
    high = min(math.ceil(corr(MODEL["rope_beta_slow"])), d - 1)
    ramp = np.clip((np.arange(d // 2) - low) / (high - low), 0.0, 1.0)
    return inv * ((1 - ramp) + ramp / MODEL["rope_factor"])


def softmax_scale() -> float:
    dq = MODEL["qk_nope_head_dim"] + MODEL["qk_rope_head_dim"]
    return dq ** -0.5 * yarn_magnitude(MODEL["rope_mscale_all_dim"]) ** 2


def rope(x, pos):
    """Rotate-half YaRN RoPE.  x: (S, heads, D); pos: (S,)."""
    d = x.shape[-1]
    ang = pos.astype(jnp.float32)[:, None] * jnp.asarray(yarn_inv_freq(),
                                                         jnp.float32)
    mag = yarn_magnitude(MODEL["rope_mscale"]) \
        / yarn_magnitude(MODEL["rope_mscale_all_dim"])
    c = mag * jnp.cos(ang)[:, None, :]
    s = mag * jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def mla(h, p, m, prec: str):
    """Causal multi-head latent attention over the whole sequence, its
    queries a block of ``QUERY_BLOCK`` rows at a time."""
    S, d = h.shape
    H, r = m.n_heads, MODEL["kv_lora_rank"]
    nope, rd, dv = (MODEL["qk_nope_head_dim"], MODEL["qk_rope_head_dim"],
                    MODEL["v_head_dim"])
    pos = jnp.arange(S)
    q = mm(h, p["wq"].reshape(d, H * (nope + rd)), prec) \
        .reshape(S, H, nope + rd)
    q_nope, q_pe = q[..., :nope], rope(q[..., nope:], pos)
    kva = mm(h, p["wkv_a"], prec)
    c = rmsnorm(kva[:, :r], p["kv_norm"]["scale"], m.norm_eps)
    k_pe = rope(kva[:, None, r:], pos)                       # (S, 1, rd)
    k_nope = mm(c, p["w_uk"].reshape(r, H * nope), prec).reshape(S, H, nope)
    v = mm(c, p["w_uv"].reshape(r, H * dv), prec).reshape(S, H, dv)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_pe, (S, H, rd))], -1)
    qf = jnp.concatenate([q_nope, q_pe], -1)
    if prec == "fp8":
        qf, k, v = q8(qf, -1), q8(k, -1), q8(v, 0)
    n = -(-S // QUERY_BLOCK)
    qb = jnp.pad(qf, ((0, n * QUERY_BLOCK - S), (0, 0), (0, 0))) \
        .reshape(n, QUERY_BLOCK, H, nope + rd)

    def block(args):
        i, qi = args
        s = jnp.einsum("qhd,khd->hqk", qi, k, precision=HIGHEST) \
            * softmax_scale()
        rows = i * QUERY_BLOCK + jnp.arange(QUERY_BLOCK)
        s = jnp.where(pos[None, None, :] <= rows[None, :, None], s, -jnp.inf)
        w = jax.nn.softmax(s, axis=-1)
        if prec == "fp8":
            w = q8(w, -1)
        return jnp.einsum("hqk,khd->qhd", w, v, precision=HIGHEST)

    o = jax.lax.map(block, (jnp.arange(n), qb)).reshape(n * QUERY_BLOCK,
                                                          H, dv)[:S]
    return mm(o.reshape(S, H * dv), p["wo"].reshape(H * dv, d), prec)


def gated_mlp(h, p, prec: str):
    return mm(jax.nn.silu(mm(h, p["wg"], prec)) * mm(h, p["wi"], prec),
              p["wo"], prec)


def experts(h, p, prec: str):
    """This chip's routed experts' part of the expert layer, plus the
    shared experts.  ``w_in``/``w_gate`` (E·d, e) and ``w_out`` (E·e,
    d), expert-major: the experts held here, E of them."""
    E, k = MODEL["n_experts"], MODEL["top_k"]
    d, e_w = p["w_out"].shape[1], p["w_in"].shape[1]
    w_in = p["w_in"].reshape(E, d, e_w)
    w_gate = p["w_gate"].reshape(E, d, e_w)
    w_out = p["w_out"].reshape(E, e_w, d)
    probs = jax.nn.softmax(mm(h, p["router"], prec), axis=-1)
    top, idx = jax.lax.top_k(probs, k)
    if MODEL.get("router_renorm", True):
        top = top / top.sum(-1, keepdims=True)
    top = top * MODEL.get("router_scale", 1.0)
    y = gated_mlp(h, p["shared"], prec)
    for e in range(E):
        g = jnp.sum(jnp.where(idx == MODEL.get("first_expert", 0) + e, top,
                              0.0), -1)                       # (S,)
        a = jax.nn.silu(mm(h, w_gate[e], prec)) * mm(h, w_in[e], prec)
        y = y + g[:, None] * mm(a, w_out[e], prec)
    return y


def _layers(x, blocks, m, prec: str, ffn):
    def layer(x, bp):
        bp = f32(bp)
        x = x + mla(rmsnorm(x, bp["ln1"]["scale"], m.norm_eps), bp["attn"],
                    m, prec)
        x = x + ffn(rmsnorm(x, bp["ln2"]["scale"], m.norm_eps), bp, prec)
        return x, None

    return jax.lax.scan(layer, x, blocks)[0]


@functools.partial(jax.jit, static_argnames=("m", "prec"))
def logits(params, m, tokens, at, prec="f32"):
    """float32 logits (len(at), vocab) of the teacher-forced ``tokens``
    at positions ``at``."""
    with jax.default_matmul_precision("highest"):
        emb = params["embed"]["tok"].astype(jnp.float32)
        if prec == "fp8":
            emb = q8(emb, 1)
        x = emb[tokens]
        if "blocks_dense" in params:
            x = _layers(x, params["blocks_dense"], m, prec,
                        lambda h, bp, pr: gated_mlp(h, bp["mlp"], pr))
        x = _layers(x, params["blocks"], m, prec,
                    lambda h, bp, pr: experts(h, bp["moe"], pr))
        h = rmsnorm(x[at], params["final_norm"]["scale"].astype(jnp.float32),
                    m.norm_eps)
        return mm(h, params["embed"]["head"].astype(jnp.float32), prec)
