"""Plain float32 pieces of a decoder, for the per-configuration
references in ``bench/configs/<name>.py``.

Straightforward ``jax.numpy``: one whole sequence at a time, no cache,
no kernel, no batching, every matrix product at ``Precision.HIGHEST``.
The weights arrive in the program's parameter layout (the benchmark made
them, see ``weights``) and are widened to float32 layer by layer.

``prec="fp8"`` is the control: the same computation with every matrix
product's operands rounded to float8 e4m3 first (a scale per row of the
activations and per output column of the weights), the step below the
bfloat16 the configurations state.  Nothing else changes.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
_E4M3_MAX = 448.0


@dataclasses.dataclass(frozen=True)
class Model:
    """The sizes a reference needs, from a configuration file's
    ``model`` entry (hashable, so it can be a static jit argument)."""
    family: str
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    rope_theta: float = 10_000.0
    qkv_bias: bool = False
    sliding_window: int = 0
    global_every: int = 0
    norm_eps: float = 1e-5
    tie_embeddings: bool = False
    ssm_state: int = 0
    d_ssm_head: int = 64
    ssm_expand: int = 2
    ssm_conv: int = 4

    @classmethod
    def from_config(cls, model: dict) -> "Model":
        names = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in model.items() if k in names})

    @property
    def d_head(self) -> int:
        return self.d_model // self.n_heads


def q8(x, axis):
    """Round ``x`` to float8 e4m3 with one scale per slice along
    ``axis`` (the slice's largest magnitude maps to e4m3's largest)."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / _E4M3_MAX, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def mm(x, w, prec: str):
    """``x (..., k) @ w (k, n)`` in float32 at HIGHEST."""
    if prec == "fp8":
        x, w = q8(x, -1), q8(w, 0)
    return jnp.einsum("...k,kn->...n", x, w, precision=HIGHEST)


def f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def rmsnorm(x, scale, eps: float):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale


def rope(x, pos, theta: float):
    """Rotate-half RoPE.  x: (S, heads, dh); pos: (S,)."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = pos.astype(jnp.float32)[:, None] * inv
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def attention(h, p, m: Model, layer, prec: str):
    """Causal GQA self-attention over the whole sequence.  ``layer`` is
    the traced layer index: with a sliding window, layers with
    ``layer % global_every == 0`` attend globally, the rest see the last
    ``sliding_window`` positions (themselves included)."""
    S, d = h.shape
    H, K, dh = m.n_heads, m.n_kv_heads, m.d_head
    q = mm(h, p["wq"].reshape(d, H * dh), prec).reshape(S, H, dh)
    k = mm(h, p["wk"].reshape(d, K * dh), prec).reshape(S, K, dh)
    v = mm(h, p["wv"].reshape(d, K * dh), prec).reshape(S, K, dh)
    if m.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    pos = jnp.arange(S)
    q, k = rope(q, pos, m.rope_theta), rope(k, pos, m.rope_theta)
    k = jnp.repeat(k, H // K, axis=1)        # query head i reads kv i // rep
    v = jnp.repeat(v, H // K, axis=1)
    if prec == "fp8":
        q, k, v = q8(q, -1), q8(k, -1), q8(v, 0)
    s = jnp.einsum("qhd,khd->hqk", q, k, precision=HIGHEST) / jnp.sqrt(
        jnp.float32(dh))
    qi, ki = pos[:, None], pos[None, :]
    allowed = ki <= qi
    if m.sliding_window:
        is_global = (layer % m.global_every == 0) if m.global_every \
            else False
        allowed = allowed & (is_global | (ki > qi - m.sliding_window))
    s = jnp.where(allowed[None], s, -jnp.inf)
    w = jax.nn.softmax(s, axis=-1)
    if prec == "fp8":
        w = q8(w, -1)
    o = jnp.einsum("hqk,khd->qhd", w, v, precision=HIGHEST)
    return mm(o.reshape(S, H * dh), p["wo"].reshape(H * dh, d), prec)


def mlp(h, p, prec: str):
    """Gated SiLU MLP: ``wo(silu(wg h) * wi h)``."""
    return mm(jax.nn.silu(mm(h, p["wg"], prec)) * mm(h, p["wi"], prec),
              p["wo"], prec)


def causal_conv(x, w, b):
    """Depthwise causal convolution.  x: (S, ch); w: (k, ch), its last
    row multiplying the current position; zero history before 0."""
    k = w.shape[0]
    xp = jnp.pad(x, ((k - 1, 0), (0, 0)))
    return sum(w[i] * xp[i:i + x.shape[0]] for i in range(k)) + b


def ssm(h, p, m: Model, prec: str):
    """Mamba2 mixer, one B/C group shared by every head, by its
    recurrence: ``s_t = exp(dt_t a) s_{t-1} + dt_t x_t b_t^T`` and
    ``y_t = s_t c_t + D x_t``, evaluated with an associative scan."""
    S, d = h.shape
    d_in = m.ssm_expand * d
    P, N = m.d_ssm_head, m.ssm_state
    H = d_in // P
    zx = mm(h, p["w_zx"], prec)
    z, xs = zx[:, :d_in], zx[:, d_in:]
    bcdt = mm(h, p["w_bcdt"], prec)
    bc, dt = bcdt[:, :2 * N], bcdt[:, 2 * N:]
    xs = jax.nn.silu(causal_conv(xs, p["conv_w"], p["conv_b"]))
    bc = jax.nn.silu(causal_conv(bc, p["conv_w_bc"], p["conv_b_bc"]))
    b, c = bc[:, :N], bc[:, N:]
    dt = jax.nn.softplus(dt + p["dt_bias"])                 # (S, H)
    decay = jnp.exp(dt * -jnp.exp(p["a_log"]))              # (S, H)
    x = xs.reshape(S, H, P)
    u = (dt[:, :, None] * x)[..., None] * b[:, None, None, :]  # (S,H,P,N)

    def combine(e1, e2):
        a1, u1 = e1
        a2, u2 = e2
        return a1 * a2, a2[..., None, None] * u1 + u2

    _, states = jax.lax.associative_scan(combine, (decay, u))
    y = jnp.einsum("shpn,sn->shp", states, c, precision=HIGHEST) \
        + p["d_skip"][None, :, None] * x
    y = y.reshape(S, d_in) * jax.nn.silu(z)
    y = rmsnorm(y, p["norm"], m.norm_eps)
    return mm(y, p["w_out"], prec)


def decoder_logits(params, m: Model, tokens, at, prec: str = "f32"):
    """Logits (len(at), vocab) at positions ``at`` of the teacher-forced
    sequence ``tokens`` (S,).  Attention-only families add the attention
    output to the residual; hybrid families add the mean of the parallel
    attention and SSM outputs, as Hymba does."""
    emb = params["embed"]["tok"].astype(jnp.float32)
    if prec == "fp8":
        emb = q8(emb, 1)
    x = emb[tokens]

    def layer(x, inp):
        li, bp = inp
        bp = f32(bp)
        a = attention(rmsnorm(x, bp["ln1"]["scale"], m.norm_eps),
                      bp["attn"], m, li, prec)
        if m.family == "hybrid":
            s = ssm(rmsnorm(x, bp["ln_ssm"]["scale"], m.norm_eps),
                    bp["ssm"], m, prec)
            x = x + 0.5 * (a + s)
        else:
            x = x + a
        x = x + mlp(rmsnorm(x, bp["ln2"]["scale"], m.norm_eps),
                    bp["mlp"], prec)
        return x, None

    x, _ = jax.lax.scan(layer, x, (jnp.arange(m.n_layers),
                                   params["blocks"]))
    h = rmsnorm(x[at], params["final_norm"]["scale"].astype(jnp.float32),
                m.norm_eps)
    if m.tie_embeddings:                 # emb is already rounded for fp8
        hq = q8(h, -1) if prec == "fp8" else h
        return jnp.einsum("nk,vk->nv", hq, emb, precision=HIGHEST)
    return mm(h, params["embed"]["head"].astype(jnp.float32), prec)
