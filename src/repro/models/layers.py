"""Core transformer building blocks (pure functions over param pytrees).

Parameters are nested dicts whose leaves are ``jnp`` arrays.  Every init
function also produces a parallel tree of *logical sharding axes* (tuples of
axis names) — ``sharding/rules.py`` maps those onto the device mesh.  Init
functions are pure and work under ``jax.eval_shape`` for allocation-free
abstract initialization (used by the multi-pod dry-run).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.config import ModelConfig

Pytree = Any


@dataclasses.dataclass
class ParamBundle:
    """Parameters plus their logical-axis annotations (same tree shape)."""
    params: Pytree
    specs: Pytree


def _merge(*bundles_kv) -> ParamBundle:
    params = {k: b.params for k, b in bundles_kv}
    specs = {k: b.specs for k, b in bundles_kv}
    return ParamBundle(params, specs)


def _dense_init(key, shape, axes, dtype, scale: float | None = None):
    fan_in = shape[0] if len(shape) > 1 else 1
    scale = scale if scale is not None else 1.0 / np.sqrt(fan_in)
    w = (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)
    return ParamBundle(w, axes)


def _zeros_init(shape, axes, dtype):
    return ParamBundle(jnp.zeros(shape, dtype), axes)


def _ones_init(shape, axes, dtype):
    return ParamBundle(jnp.ones(shape, dtype), axes)


# ---------------------------------------------------------------------------
# Normalization
# ---------------------------------------------------------------------------

def norm_init(cfg: ModelConfig) -> ParamBundle:
    if cfg.norm == "ln":
        return ParamBundle(
            {"scale": jnp.ones(cfg.d_model, cfg.pdtype),
             "bias": jnp.zeros(cfg.d_model, cfg.pdtype)},
            {"scale": ("embed",), "bias": ("embed",)})
    return ParamBundle({"scale": jnp.ones(cfg.d_model, cfg.pdtype)},
                       {"scale": ("embed",)})


def apply_norm(p, x, cfg: ModelConfig):
    x32 = x.astype(jnp.float32)
    if cfg.norm == "ln":
        mu = x32.mean(-1, keepdims=True)
        var = ((x32 - mu) ** 2).mean(-1, keepdims=True)
        y = (x32 - mu) * jax.lax.rsqrt(var + cfg.norm_eps)
        return (y * p["scale"].astype(jnp.float32)
                + p["bias"].astype(jnp.float32)).astype(x.dtype)
    var = (x32 ** 2).mean(-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(var + cfg.norm_eps)
    return (y * p["scale"].astype(jnp.float32)).astype(x.dtype)


# ---------------------------------------------------------------------------
# Rotary position embedding
# ---------------------------------------------------------------------------

def yarn_inv_freq(cfg: ModelConfig) -> np.ndarray:
    """YaRN's inverse frequencies of the ``cfg.rope_dim`` rotated lanes
    (float64): frequencies above the ``beta_fast`` correction dimension
    are kept, those below ``beta_slow``'s divided by ``rope_factor``, a
    linear ramp between."""
    d, base = cfg.rope_dim, cfg.rope_theta
    inv = 1.0 / base ** (np.arange(0, d, 2, dtype=np.float64) / d)

    def corr_dim(rotations):
        return d * math.log(cfg.rope_original_max_position
                            / (rotations * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(corr_dim(cfg.rope_beta_fast)), 0)
    high = min(math.ceil(corr_dim(cfg.rope_beta_slow)), d - 1)
    ramp = np.clip((np.arange(d // 2) - low) / max(high - low, 1e-3), 0, 1)
    return inv * (1 - ramp) + inv / cfg.rope_factor * ramp


def rope_freqs(cfg: ModelConfig, positions: jnp.ndarray) -> tuple:
    """positions: int32[...]; returns (cos, sin) with trailing dim
    ``rope_dim``/2, at YaRN's frequencies and magnitude when
    ``rope_factor`` > 1."""
    d = cfg.rope_dim
    if cfg.rope_factor > 1.0:
        inv = jnp.asarray(yarn_inv_freq(cfg), jnp.float32)
    else:
        inv = 1.0 / (cfg.rope_theta
                     ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions.astype(jnp.float32)[..., None] * inv
    mag = cfg.yarn_magnitude(cfg.rope_mscale) \
        / cfg.yarn_magnitude(cfg.rope_mscale_all_dim)
    if mag != 1.0:        # DeepSeek-V2's two mscales are equal: none
        return mag * jnp.cos(ang), mag * jnp.sin(ang)
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray):
    """x: (..., S, H, d_head); cos/sin: (..., S, d_head/2)."""
    x1, x2 = jnp.split(x, 2, axis=-1)
    c = cos[..., None, :]
    s = sin[..., None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s],
                           axis=-1).astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention (GQA, optional sliding window / prefix / cross)
# ---------------------------------------------------------------------------

def attention_init(key, cfg: ModelConfig, cross: bool = False) -> ParamBundle:
    if cfg.is_mla:
        return mla_init(key, cfg)
    d, H, K, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    ks = jax.random.split(key, 4)
    items = [
        ("wq", _dense_init(ks[0], (d, H, dh), ("embed", "heads", "head"),
                           cfg.pdtype)),
        ("wk", _dense_init(ks[1], (d, K, dh), ("embed", "kv_heads", "head"),
                           cfg.pdtype)),
        ("wv", _dense_init(ks[2], (d, K, dh), ("embed", "kv_heads", "head"),
                           cfg.pdtype)),
        ("wo", _dense_init(ks[3], (H, dh, d), ("heads", "head", "embed"),
                           cfg.pdtype, scale=1.0 / np.sqrt(H * dh))),
    ]
    if cfg.qkv_bias:
        items += [
            ("bq", _zeros_init((H, dh), ("heads", "head"), cfg.pdtype)),
            ("bk", _zeros_init((K, dh), ("kv_heads", "head"), cfg.pdtype)),
            ("bv", _zeros_init((K, dh), ("kv_heads", "head"), cfg.pdtype)),
        ]
    return _merge(*items)


def _qkv(p, x, cfg: ModelConfig, positions=None):
    cd = cfg.cdtype
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(cd))
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"].astype(cd))
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"].astype(cd))
    if cfg.qkv_bias:
        q = q + p["bq"].astype(cd)
        k = k + p["bk"].astype(cd)
        v = v + p["bv"].astype(cd)
    if cfg.use_rope and positions is not None:
        cos, sin = rope_freqs(cfg, positions)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
    return q, k, v


def _repeat_kv(k: jnp.ndarray, n_rep: int) -> jnp.ndarray:
    if n_rep == 1:
        return k
    b, s, kh, dh = k.shape
    return jnp.broadcast_to(k[:, :, :, None, :],
                            (b, s, kh, n_rep, dh)).reshape(b, s, kh * n_rep, dh)


def _constrain_heads(x):
    """Pin (B,S,H,dh) activations to head-sharding on the model axis.

    For head counts that don't divide the TP degree (56 heads / 16-way),
    parameter shardings must fall back (inputs need exact divisibility),
    and without a hint GSPMD chooses head-DIM sharding — which makes QK^T
    a partial contraction and all-reduces the S x S logits (§Perf A1:
    7.8e12 B/chip on deepseek prefill_32k).  Intermediates MAY be padded,
    so constraining heads onto ``model`` here keeps attention fully local
    per shard; only the row-parallel output psum remains.
    """
    import os
    from repro.sharding import context as shctx
    mesh = shctx.current_mesh()
    if mesh is None or "model" not in mesh.axis_names \
            or os.environ.get("REPRO_NO_HEAD_CONSTRAINT"):
        return x
    daxes = shctx.data_axes(mesh)
    spec = jax.sharding.PartitionSpec(
        daxes if x.shape[0] % np.prod([mesh.shape[a] for a in daxes]) == 0
        else None, None, "model", None)
    return jax.lax.with_sharding_constraint(
        x, jax.sharding.NamedSharding(mesh, spec))


def _constrain_kv_seq(x):
    """Pin cached (B,S,*,dh) K/V to sequence-sharding on the model axis.

    Decode over a sequence-sharded cache (the kv_heads<TP fallback) must
    NOT gather the cache: with K/V kept S-sharded the QK^T contraction is
    local, softmax needs only (B,H,1) max/sum all-reduces, and the PV
    product psums a (B,1,H,dh) partial — flash-decoding semantics.  Without
    this hint GSPMD all-gathers the entire cache every token (§Perf D1:
    3.8e11 B/chip/step on deepseek decode_32k).
    """
    import os
    from repro.sharding import context as shctx
    mesh = shctx.current_mesh()
    if mesh is None or "model" not in mesh.axis_names \
            or os.environ.get("REPRO_NO_KV_SEQ_CONSTRAINT") \
            or x.shape[1] % mesh.shape["model"] != 0:
        return x
    daxes = shctx.data_axes(mesh)
    spec = jax.sharding.PartitionSpec(
        daxes if x.shape[0] % np.prod([mesh.shape[a] for a in daxes]) == 0
        else None, "model", None, None)
    return jax.lax.with_sharding_constraint(
        x, jax.sharding.NamedSharding(mesh, spec))


def sdpa(q, k, v, mask=None, scale=None, kv_seq_sharded: bool = False):
    """q:(B,Sq,H,dh) k,v:(B,Sk,H,dh); mask broadcastable to (B,H,Sq,Sk)."""
    if kv_seq_sharded:
        k = _constrain_kv_seq(k)
        v = _constrain_kv_seq(v)
    else:
        q = _constrain_heads(q)
        k = _constrain_heads(k)
        v = _constrain_heads(v)
    scale = scale or (1.0 / np.sqrt(q.shape[-1]))
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    if mask is not None:
        logits = jnp.where(mask, logits, jnp.float32(-1e30))
    w = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", w, v)


def causal_mask(sq: int, sk: int, window: int = 0, prefix_len=None):
    """bool[Sq, Sk] (True = attend).  ``sk - sq`` offsets queries to the
    cache tail; ``window`` > 0 restricts to a sliding window; ``prefix_len``
    makes the first ``prefix_len`` keys bidirectional (VLM prefix-LM)."""
    qpos = jnp.arange(sq)[:, None] + (sk - sq)
    kpos = jnp.arange(sk)[None, :]
    m = kpos <= qpos
    if window:
        m &= kpos > qpos - window
    if prefix_len is not None:
        m |= kpos < prefix_len
    return m


def _cache_write(cache, new, cache_positions):
    """The dense cache (B, Smax, ...) with ``new`` (B, S, ...) written at
    ``cache_positions``: None appends, a (B,) vector writes one position
    per sequence (ragged decode: paged / continuous-batching lanes
    advance independently), a scalar one position for all."""
    if cache_positions is None:
        return jnp.concatenate([cache, new], axis=1)
    new = new.astype(cache.dtype)
    if getattr(cache_positions, "ndim", 0):
        return jax.vmap(lambda c, u, pos: jax.lax.dynamic_update_slice_in_dim(
            c, u, pos, axis=0))(cache, new, cache_positions)
    return jax.lax.dynamic_update_slice_in_dim(cache, new, cache_positions,
                                               axis=1)


def attention_apply(p, x, cfg: ModelConfig, *, positions, mask,
                    kv_cache=None, cache_positions=None,
                    xattn_kv=None):
    """Full attention layer.  Modes:
      - training/prefill: kv_cache is None -> self-attention over x
      - decode: kv_cache=(k,v) of shape (B,S,K,dh) -> append x's kv
      - cross: xattn_kv=(k,v) precomputed from the encoder
    Returns (out, new_kv) where new_kv is (k, v) for cache maintenance.
    """
    if cfg.is_mla:
        return mla_attention_apply(p, x, cfg, positions=positions, mask=mask,
                                   kv_cache=kv_cache,
                                   cache_positions=cache_positions)
    cd = cfg.cdtype
    H, K = cfg.n_heads, cfg.n_kv_heads
    if xattn_kv is not None:
        q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(cd))
        if cfg.qkv_bias:
            q = q + p["bq"].astype(cd)
        k, v = xattn_kv
        new_kv = None
    else:
        q, k, v = _qkv(p, x, cfg, positions)
        new_kv = (k, v)
        if kv_cache is not None:
            ck, cv = kv_cache
            k = _cache_write(ck, k, cache_positions)
            v = _cache_write(cv, v, cache_positions)
            new_kv = (k, v)
            k = k.astype(cfg.cdtype)   # fp8 cache reads upcast for compute
            v = v.astype(cfg.cdtype)
    from repro.sharding import context as shctx
    mesh = shctx.current_mesh()
    kv_seq_sharded = (
        kv_cache is not None and cache_positions is not None
        and mesh is not None and "model" in mesh.axis_names
        and K % mesh.shape["model"] != 0)
    k = _repeat_kv(k, H // K)
    v = _repeat_kv(v, H // K)
    out = sdpa(q, k, v, mask, kv_seq_sharded=kv_seq_sharded)
    out = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(cd))
    return out, new_kv


def paged_attention_apply(p, x, cfg: ModelConfig, *, lengths, k_pages,
                          v_pages, page_tables, layer, window=0,
                          interpret: bool | None = None):
    """Decode attention reading cached KV straight from the block pool via
    the Pallas ``paged_attention`` kernel (kernel over the cached pages +
    online-softmax merge of the in-flight token).

    x: (B, 1, d); k_pages/v_pages: the pool's folded device mirror
    (L, P, page, K·dh); ``layer`` selects the plane — one page table
    serves every layer.  ``window`` > 0 applies the kernel's sliding-
    window mask (a traced int32, so a scan over a ``global_every``
    hybrid's layers flips it per layer).  ``interpret=None`` lets the platform decide
    (``repro.kernels.pallas_interpret``).  Returns (out (B, 1, d),
    (k_new, v_new) each (B, 1, K, dh), post-RoPE, for pool write-back
    after the step).
    """
    from repro.kernels.paged_attention.paged_attention import decode_attend
    cd = cfg.cdtype
    positions = lengths[:, None]
    q, k, v = _qkv(p, x, cfg, positions)
    # round-trip through the cache dtype so the in-flight token sees the
    # same quantization the dense backend applies on cache write/read
    kc = k.astype(cfg.kvdtype).astype(cd)
    vc = v.astype(cfg.kvdtype).astype(cd)
    o = decode_attend(q[:, 0], kc[:, 0], vc[:, 0], k_pages, v_pages,
                      page_tables, lengths, layer=layer, window=window,
                      folded=True, interpret=interpret)
    out = jnp.einsum("bshk,hkd->bsd", o[:, None].astype(cd),
                     p["wo"].astype(cd))
    return out, (k, v)


# ---------------------------------------------------------------------------
# Multi-head latent attention (DeepSeek-V2 MLA, no q compression)
# ---------------------------------------------------------------------------

# queries per step of a long prompt's MLA (see mla_attention_apply)
MLA_QUERY_CHUNK = 512


def mla_init(key, cfg: ModelConfig) -> ParamBundle:
    """``wq`` (d, H, nope+rope); ``wkv_a`` (d, r+rope) makes the latent
    ``c`` (normed by ``kv_norm``) and the shared RoPE key; ``w_uk`` (r,
    H, nope) and ``w_uv`` (r, H, v) expand ``c`` into each head's key and
    value (DeepSeek's ``kv_b_proj``, split); ``wo`` (H, v, d)."""
    d, H, r = cfg.d_model, cfg.n_heads, cfg.kv_lora_rank
    nope, rope, dv = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                      cfg.v_head_dim)
    ks = jax.random.split(key, 5)
    return _merge(
        ("wq", _dense_init(ks[0], (d, H, nope + rope),
                           ("embed", "heads", "head"), cfg.pdtype)),
        ("wkv_a", _dense_init(ks[1], (d, r + rope), ("embed", "latent"),
                              cfg.pdtype)),
        ("kv_norm", ParamBundle({"scale": jnp.ones(r, cfg.pdtype)},
                                {"scale": ("latent",)})),
        ("w_uk", _dense_init(ks[2], (r, H, nope),
                             ("latent", "heads", "head"), cfg.pdtype)),
        ("w_uv", _dense_init(ks[3], (r, H, dv),
                             ("latent", "heads", "head"), cfg.pdtype)),
        ("wo", _dense_init(ks[4], (H, dv, d), ("heads", "head", "embed"),
                           cfg.pdtype, scale=1.0 / np.sqrt(H * dv))),
    )


def _mla_query(p, x, cfg: ModelConfig, positions):
    """(q_nope (B,S,H,nope), q_pe (B,S,H,rope) rotated)."""
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(cfg.cdtype))
    nope = cfg.qk_nope_head_dim
    cos, sin = rope_freqs(cfg, positions)
    return q[..., :nope], apply_rope(q[..., nope:], cos, sin)


def mla_rows(p, x, cfg: ModelConfig, positions):
    """What the latent pool caches for each token of x (B, S, d): the row
    ``[c, k_pe]`` (B, S, r+rope), ``c`` after its RMSNorm and ``k_pe``
    after RoPE."""
    r = cfg.kv_lora_rank
    kva = jnp.einsum("bsd,dr->bsr", x, p["wkv_a"].astype(cfg.cdtype))
    c = apply_norm(p["kv_norm"], kva[..., :r], cfg)
    cos, sin = rope_freqs(cfg, positions)
    k_pe = apply_rope(kva[..., None, r:], cos, sin)[..., 0, :]
    return jnp.concatenate([c, k_pe], axis=-1)


def mla_attention_apply(p, x, cfg: ModelConfig, *, positions, mask,
                        kv_cache=None, cache_positions=None):
    """MLA in its expanded form (training, prefill, dense decode): every
    head's key ``[c W_uk, k_pe]`` and value ``c W_uv`` are made from the
    cached rows.  ``kv_cache`` is ``(rows (B, Smax, 1, W), None)``, the
    latent kind of the dense cache.  Returns (out, (rows (B, S|Smax, 1,
    W), None))."""
    cd = cfg.cdtype
    r, H = cfg.kv_lora_rank, cfg.n_heads
    q_nope, q_pe = _mla_query(p, x, cfg, positions)
    rows = mla_rows(p, x, cfg, positions)[:, :, None, :]
    if kv_cache is not None:
        rows = _cache_write(kv_cache[0], rows, cache_positions)
    ctx = rows[:, :, 0].astype(cd)
    c, k_pe = ctx[..., :r], ctx[..., r:]
    k_nope = jnp.einsum("bsr,rhn->bshn", c, p["w_uk"].astype(cd))
    v = jnp.einsum("bsr,rhv->bshv", c, p["w_uv"].astype(cd))
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_pe[:, :, None, :],
                                  k_nope.shape[:3] + k_pe.shape[-1:])], -1)
    q = jnp.concatenate([q_nope, q_pe], -1)
    B, S = q.shape[:2]
    if kv_cache is None and getattr(mask, "ndim", 0) == 2 \
            and S > MLA_QUERY_CHUNK and S % MLA_QUERY_CHUNK == 0:
        # a long prompt's queries a chunk at a time: the float32 scores
        # of all heads over every query would hold 16 x S^2 x 4 bytes
        # (1 GiB at 4096 tokens) beside the weights and the pool mirrors
        n = S // MLA_QUERY_CHUNK
        qc = q.reshape((B, n, MLA_QUERY_CHUNK) + q.shape[2:]).swapaxes(0, 1)
        out = jax.lax.map(
            lambda a: sdpa(a[0], k, v, a[1], scale=cfg.softmax_scale),
            (qc, mask.reshape(n, MLA_QUERY_CHUNK, S)))
        out = out.swapaxes(0, 1).reshape((B, S) + out.shape[3:])
    else:
        out = sdpa(q, k, v, mask, scale=cfg.softmax_scale)
    return jnp.einsum("bshv,hvd->bsd", out, p["wo"].astype(cd)), (rows, None)


def mla_paged_apply(p, x, cfg: ModelConfig, *, lengths, pages, page_tables,
                    layer, interpret: bool | None = None):
    """MLA decode in its absorbed form, reading the latent pool through
    the kernel's latent mode (``mla_attention``): ``q̃_h = [q_nope_h
    W_uk,hᵀ, q_pe_h]`` scores the cached rows ``[c, k_pe]``, ``õ_h = Σ
    p c`` and ``o_h = õ_h W_uv,h``.  x: (B, 1, d); pages: the latent
    mirror (L, P, page, Wp).  Returns (out (B, 1, d), (row (B, 1, 1, W),
    None)) for the pool write-back after the step."""
    from repro.kernels.paged_attention.paged_attention import \
        mla_decode_attend
    cd = cfg.cdtype
    positions = lengths[:, None]
    q_nope, q_pe = _mla_query(p, x, cfg, positions)
    rows = mla_rows(p, x, cfg, positions)
    # the in-flight token sees the rounding the pool applies on write
    row = rows[:, 0].astype(cfg.kvdtype).astype(cd)
    q_abs = jnp.einsum("bhn,rhn->bhr", q_nope[:, 0], p["w_uk"].astype(cd))
    q_lat = jnp.concatenate([q_abs, q_pe[:, 0]], axis=-1)
    o = mla_decode_attend(q_lat, row, pages, page_tables, lengths,
                          layer=layer, scale=cfg.softmax_scale,
                          dv=cfg.kv_lora_rank, interpret=interpret)
    o = jnp.einsum("bhr,rhv->bhv", o.astype(cd), p["w_uv"].astype(cd))
    out = jnp.einsum("bhv,hvd->bd", o, p["wo"].astype(cd))
    return out[:, None], (rows[:, :, None, :], None)


def cross_kv(p, enc_out, cfg: ModelConfig):
    """Precompute cross-attention K/V from encoder output."""
    cd = cfg.cdtype
    k = jnp.einsum("bsd,dhk->bshk", enc_out, p["wk"].astype(cd))
    v = jnp.einsum("bsd,dhk->bshk", enc_out, p["wv"].astype(cd))
    if cfg.qkv_bias:
        k = k + p["bk"].astype(cd)
        v = v + p["bv"].astype(cd)
    return k, v


# ---------------------------------------------------------------------------
# MLP (gated or plain)
# ---------------------------------------------------------------------------

def mlp_init(key, cfg: ModelConfig, d_ff: int | None = None) -> ParamBundle:
    d = cfg.d_model
    f = d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    items = [("wi", _dense_init(ks[0], (d, f), ("embed", "mlp"), cfg.pdtype)),
             ("wo", _dense_init(ks[1], (f, d), ("mlp", "embed"), cfg.pdtype))]
    if cfg.mlp_gated:
        items.append(("wg", _dense_init(ks[2], (d, f), ("embed", "mlp"),
                                        cfg.pdtype)))
    return _merge(*items)


def _act(x, kind: str):
    return jax.nn.silu(x) if kind == "silu" else jax.nn.gelu(x)


def mlp_apply(p, x, cfg: ModelConfig):
    cd = cfg.cdtype
    h = jnp.einsum("bsd,df->bsf", x, p["wi"].astype(cd))
    if cfg.mlp_gated:
        g = jnp.einsum("bsd,df->bsf", x, p["wg"].astype(cd))
        h = _act(g, cfg.act) * h
    else:
        h = _act(h, cfg.act)
    return jnp.einsum("bsf,fd->bsd", h, p["wo"].astype(cd))


# ---------------------------------------------------------------------------
# Embedding / LM head
# ---------------------------------------------------------------------------

def embedding_init(key, cfg: ModelConfig) -> ParamBundle:
    ks = jax.random.split(key, 3)
    items = [("tok", _dense_init(ks[0], (cfg.vocab, cfg.d_model),
                                 ("vocab", "embed"), cfg.pdtype, scale=0.02))]
    if not cfg.tie_embeddings:
        items.append(("head", _dense_init(ks[1], (cfg.d_model, cfg.vocab),
                                          ("embed", "vocab"), cfg.pdtype)))
    if not cfg.use_rope and cfg.family in ("encdec",):
        items.append(("pos", _dense_init(
            ks[2], (cfg.max_position, cfg.d_model), ("seq", "embed"),
            cfg.pdtype, scale=0.02)))
    return _merge(*items)


def embed_tokens(p, tokens, cfg: ModelConfig, positions=None):
    from repro.kernels.mars_gather import ops as gather_ops
    x = gather_ops.embedding_gather(p["tok"], tokens).astype(cfg.cdtype)
    if "pos" in p and positions is not None:
        x = x + p["pos"].astype(cfg.cdtype)[positions]
    return x


def lm_head(p, x, cfg: ModelConfig):
    w = p["tok"].T if cfg.tie_embeddings else p["head"]
    return jnp.einsum("bsd,dv->bsv", x, w.astype(cfg.cdtype))
