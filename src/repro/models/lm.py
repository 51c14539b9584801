"""Unified causal LM covering every assigned architecture family.

One parameter tree, one scan-over-layers forward, three entry points:

  ``forward``            — teacher-forced training/prefill logits
  ``prefill``            — build the serving cache from a prompt
  ``decode_step``        — one-token serve step against the cache
  ``paged_decode_step``  — one-token serve step reading KV straight from
                           the block pool via the Pallas paged-attention
                           kernel (the PagedBackend's kernel decode path)

Families: dense / moe (leading-dense + shared experts + dense residual) /
ssm (mamba2) / hybrid (parallel attention+SSM heads, hymba-style) /
encdec (whisper: audio-frame encoder + cross-attention decoder) /
vlm (paligemma: image-patch prefix LM).  Modality frontends are stubs per
the assignment: ``frontend_emb`` carries precomputed frame/patch embeddings.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.models import layers, moe as moe_mod, ssm as ssm_mod
from repro.models.config import ModelConfig
from repro.models.layers import ParamBundle, _merge


# ---------------------------------------------------------------------------
# Block init
# ---------------------------------------------------------------------------

def _block_init(key, cfg: ModelConfig, li: int, *, decoder: bool = False,
                encoder: bool = False) -> ParamBundle:
    ks = jax.random.split(key, 8)
    items = []
    if cfg.has_attention:
        items += [("ln1", layers.norm_init(cfg)),
                  ("attn", layers.attention_init(ks[0], cfg))]
    if cfg.has_ssm and not encoder:
        items += [("ln_ssm", layers.norm_init(cfg)),
                  ("ssm", ssm_mod.ssm_init(ks[1], cfg))]
    if decoder:
        items += [("lnx", layers.norm_init(cfg)),
                  ("xattn", layers.attention_init(ks[2], cfg, cross=True))]
    is_moe_layer = cfg.is_moe and li >= cfg.n_dense_layers and not encoder
    if is_moe_layer:
        items += [("ln2", layers.norm_init(cfg)),
                  ("moe", moe_mod.moe_init(ks[3], cfg))]
        if cfg.moe_dense_residual:
            items += [("mlp", layers.mlp_init(ks[4], cfg))]
    elif cfg.d_ff:
        items += [("ln2", layers.norm_init(cfg)),
                  ("mlp", layers.mlp_init(ks[4], cfg))]
    return _merge(*items)


def _stack_bundles(bundles):
    params = jax.tree.map(lambda *xs: jnp.stack(xs), *[b.params for b in bundles])
    specs = jax.tree.map(lambda s: ("layers",) + tuple(s),
                         bundles[0].specs,
                         is_leaf=lambda x: isinstance(x, tuple))
    return ParamBundle(params, specs)


def init(cfg: ModelConfig, key) -> ParamBundle:
    ks = jax.random.split(key, cfg.n_layers + cfg.enc_layers + 4)
    items = [("embed", layers.embedding_init(ks[0], cfg)),
             ("final_norm", layers.norm_init(cfg))]
    decoder = cfg.family == "encdec"
    nd = cfg.n_dense_layers if cfg.is_moe else 0
    if nd:
        items.append(("blocks_dense", _stack_bundles(
            [_block_init(ks[1 + i], cfg, 0, decoder=decoder)
             for i in range(nd)])))
    items.append(("blocks", _stack_bundles(
        [_block_init(ks[1 + nd + i], cfg, nd + i, decoder=decoder)
         for i in range(cfg.n_layers - nd)])))
    if cfg.enc_layers:
        enc = _stack_bundles(
            [_block_init(ks[1 + cfg.n_layers + i], cfg, i, encoder=True)
             for i in range(cfg.enc_layers)])
        items.append(("encoder", enc))
        items.append(("enc_norm", layers.norm_init(cfg)))
    return _merge(*items)


def abstract_init(cfg: ModelConfig):
    """Shape-only init (no allocation) — used by the dry-run."""
    return jax.eval_shape(lambda: init(cfg, jax.random.key(0)).params)


# ---------------------------------------------------------------------------
# Block apply
# ---------------------------------------------------------------------------

def _is_global_layer(cfg: ModelConfig, li):
    """Hybrid archs: a few global-attention layers among sliding-window."""
    if cfg.sliding_window == 0:
        return jnp.ones((), bool) if isinstance(li, jnp.ndarray) else True
    if cfg.global_every:
        return li % cfg.global_every == 0
    return li < 0  # none


def _block_apply(bp, x, cfg: ModelConfig, *, masks, positions,
                 kv=None, cache_pos=None, ssm_state=None, xkv=None,
                 is_global=None, paged=None):
    """One transformer block.  Returns (x, new_kv, new_ssm_state, aux).

    ``paged`` routes decode attention through the Pallas paged-attention
    kernel (KV read straight from the pool's layered page buffers) instead
    of a dense cache view; everything around attention is unchanged."""
    aux = {}
    new_kv = None
    new_ssm = None
    attn_out = None
    if cfg.is_mla and paged is not None:
        h = layers.apply_norm(bp["ln1"], x, cfg)
        attn_out, new_kv = layers.mla_paged_apply(
            bp["attn"], h, cfg, lengths=paged["lengths"],
            pages=paged["k_pages"], page_tables=paged["page_tables"],
            layer=paged["layer"], interpret=paged["interpret"])
    elif cfg.has_attention and paged is not None:
        h = layers.apply_norm(bp["ln1"], x, cfg)
        attn_out, new_kv = layers.paged_attention_apply(
            bp["attn"], h, cfg, lengths=paged["lengths"],
            k_pages=paged["k_pages"], v_pages=paged["v_pages"],
            page_tables=paged["page_tables"], layer=paged["layer"],
            window=paged.get("window", 0),
            interpret=paged["interpret"])
    elif cfg.has_attention:
        mask = masks[0]
        if cfg.sliding_window and is_global is not None:
            mask = jnp.where(is_global, masks[1], masks[0])
        h = layers.apply_norm(bp["ln1"], x, cfg)
        attn_out, new_kv = layers.attention_apply(
            bp["attn"], h, cfg, positions=positions, mask=mask,
            kv_cache=kv, cache_positions=cache_pos)
    if cfg.has_ssm:
        hs = layers.apply_norm(bp.get("ln_ssm", bp.get("ln1")), x, cfg)
        if ssm_state is not None:
            ssm_out, new_ssm = ssm_mod.ssm_apply(
                bp["ssm"], hs, cfg, state=ssm_state[0],
                conv_state=ssm_state[1], return_state=True)
        else:
            ssm_out, new_ssm = ssm_mod.ssm_apply(bp["ssm"], hs, cfg,
                                                 return_state=True)
        if attn_out is not None:
            # hymba: parallel heads, mean-combined
            x = x + 0.5 * (attn_out + ssm_out)
        else:
            x = x + ssm_out
    elif attn_out is not None:
        x = x + attn_out
    if "xattn" in bp and xkv is not None:
        h = layers.apply_norm(bp["lnx"], x, cfg)
        xo, _ = layers.attention_apply(bp["xattn"], h, cfg,
                                       positions=None, mask=None, xattn_kv=xkv)
        x = x + xo
    if "moe" in bp:
        h = layers.apply_norm(bp["ln2"], x, cfg)
        # a paged decode's padded lanes (length 0) route but are not
        # counted as served tokens
        valid = None if paged is None else paged["lengths"] > 0
        mo, aux = moe_mod.moe_apply(bp["moe"], h, cfg, valid=valid)
        if cfg.moe_dense_residual and "mlp" in bp:
            mo = mo + layers.mlp_apply(bp["mlp"], h, cfg)
        x = x + mo
    elif "mlp" in bp:
        h = layers.apply_norm(bp["ln2"], x, cfg)
        x = x + layers.mlp_apply(bp["mlp"], h, cfg)
    return x, new_kv, new_ssm, aux


def _zero_aux(cfg: ModelConfig):
    """Per-layer sums the block scan carries: the MoE aux losses, and for
    a model with experts ``moe_routed_here``, the (token, expert)
    assignments that went to the experts this model holds."""
    aux = {"moe_lb": jnp.zeros((), jnp.float32),
           "moe_z": jnp.zeros((), jnp.float32)}
    if cfg.is_moe:
        aux["moe_routed_here"] = jnp.zeros((), jnp.float32)
    return aux


def _cat_layers(ys_all: dict, name: str, idx: int):
    """One per-layer output of the leading-dense and main block scans,
    concatenated over layers; None where neither scan made it."""
    parts = [ys[name][idx] for ys in ys_all.values()
             if name in ys and ys[name][idx] is not None]
    return jnp.concatenate(parts, 0) if parts else None


def _scan_blocks(stacked, x, cfg: ModelConfig, *, masks, positions,
                 layer_offset: int, n: int, kv=None, cache_pos=None,
                 ssm_states=None, xkv=None, remat: bool = False,
                 paged=None):
    """lax.scan over stacked block params (+ optional caches).

    ``paged``: kernel-path decode operands (pool page buffers + table +
    lengths); the absolute layer index rides the scan so every iteration
    reads its own plane of the layered pool through one shared table."""
    li = jnp.arange(layer_offset, layer_offset + n)
    glob = None
    if cfg.sliding_window:
        ge = max(cfg.global_every, 1)
        glob = (li % ge == 0) if cfg.global_every else jnp.zeros(n, bool)

    def body(carry, inp):
        xx, aux_acc = carry
        bp = inp["p"]
        paged_l = None
        if paged is not None:
            paged_l = dict(paged, layer=inp["li"])
            if cfg.sliding_window:
                # per-layer global/window flag: global layers attend the
                # whole cache (window 0), the rest apply the sliding
                # window — one traced int32 rides the scan, so one
                # compiled kernel serves a global_every hybrid
                paged_l["window"] = jnp.where(
                    inp["glob"], 0, cfg.sliding_window).astype(jnp.int32)
        out, new_kv, new_ssm, aux = _block_apply(
            bp, xx, cfg, masks=masks, positions=positions,
            kv=inp.get("kv"), cache_pos=cache_pos,
            ssm_state=inp.get("ssm"), xkv=inp.get("xkv"),
            is_global=inp.get("glob"), paged=paged_l)
        for k in aux_acc:
            aux_acc = dict(aux_acc)
            aux_acc[k] = aux_acc[k] + aux.get(k, 0.0)
        ys = {}
        if new_kv is not None:
            ys["kv"] = new_kv
        if new_ssm is not None:
            ys["ssm"] = new_ssm
        return (out, aux_acc), ys

    fn = jax.checkpoint(body) if remat else body
    xs: dict = {"p": stacked}
    if kv is not None:
        xs["kv"] = kv
    if ssm_states is not None:
        xs["ssm"] = ssm_states
    if xkv is not None:
        xs["xkv"] = xkv
    if glob is not None:
        xs["glob"] = glob
    if paged is not None:
        xs["li"] = jnp.asarray(li, jnp.int32)
    (x, aux), ys = jax.lax.scan(fn, (x, _zero_aux(cfg)), xs)
    return x, aux, ys


# ---------------------------------------------------------------------------
# Entry points
# ---------------------------------------------------------------------------

def _encoder_forward(params, cfg: ModelConfig, frontend_emb):
    x = frontend_emb.astype(cfg.cdtype)
    S = x.shape[1]
    masks = (jnp.ones((S, S), bool), None)
    positions = jnp.arange(S)[None, :]
    x, _, _ = _scan_blocks(params["encoder"], x, cfg, masks=masks,
                           positions=positions, layer_offset=0,
                           n=cfg.enc_layers)
    return layers.apply_norm(params["enc_norm"], x, cfg)


def _cross_kvs(params, cfg: ModelConfig, enc_out):
    def body(_, bp):
        return None, layers.cross_kv(bp["xattn"], enc_out, cfg)
    _, kvs = jax.lax.scan(body, None, params["blocks"])
    return kvs


def forward(params, cfg: ModelConfig, tokens, frontend_emb=None,
            remat: bool = False):
    """Teacher-forced logits.  tokens: (B, S) int32.

    encdec: frontend_emb (B, Senc, d) feeds the encoder.
    vlm: frontend_emb (B, P, d) is prepended as a bidirectional prefix;
    logits are returned for the token part only."""
    B, S = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
    x = layers.embed_tokens(params["embed"], tokens, cfg, positions)
    xkv = None
    prefix = 0
    if cfg.family == "encdec":
        enc_out = _encoder_forward(params, cfg, frontend_emb)
        xkv = _cross_kvs(params, cfg, enc_out)
    elif cfg.family == "vlm":
        pimg = frontend_emb.astype(cfg.cdtype)
        prefix = pimg.shape[1]
        x = jnp.concatenate([pimg, x], axis=1)
        positions = jnp.broadcast_to(
            jnp.arange(prefix + S)[None, :], (B, prefix + S))
    Sq = x.shape[1]
    m_causal = layers.causal_mask(Sq, Sq, prefix_len=prefix or None)
    m_window = layers.causal_mask(Sq, Sq, window=cfg.sliding_window,
                                  prefix_len=prefix or None) \
        if cfg.sliding_window else m_causal
    masks = (m_window if cfg.sliding_window else m_causal, m_causal)

    nd = cfg.n_dense_layers if cfg.is_moe else 0
    aux_total = _zero_aux(cfg)
    if nd:
        x, aux, _ = _scan_blocks(params["blocks_dense"], x, cfg, masks=masks,
                                 positions=positions, layer_offset=0, n=nd,
                                 remat=remat)
        aux_total = {k: aux_total[k] + aux[k] for k in aux_total}
    x, aux, _ = _scan_blocks(params["blocks"], x, cfg, masks=masks,
                             positions=positions, layer_offset=nd,
                             n=cfg.n_layers - nd, xkv=xkv, remat=remat)
    aux_total = {k: aux_total[k] + aux[k] for k in aux_total}
    x = layers.apply_norm(params["final_norm"], x, cfg)
    if prefix:
        x = x[:, prefix:]
    logits = layers.lm_head(params["embed"], x, cfg)
    return logits, aux_total


# ---------------------------------------------------------------------------
# Serving cache — dense storage (the DenseBackend's pytree)
# ---------------------------------------------------------------------------

@jax.tree_util.register_dataclass
@dataclasses.dataclass
class Cache:
    k: Any            # (L, B, Smax, K, dh) or None
    v: Any
    ssm: Any          # (L, B, H, P, N) or None
    conv: Any         # (L, B, k-1, ch) or None
    xk: Any           # (L, B, Senc, K, dh) or None (encdec)
    xv: Any
    length: Any       # int32 — tokens already cached; scalar, or (B,) for
                      # ragged (per-sequence) decode


def init_dense_cache(cfg: ModelConfig, batch: int, max_seq: int,
                     enc_len: int = 0) -> Cache:
    """The dense per-layer storage pytree (jit/sharding friendly)."""
    L, K, dh = cfg.n_layers, cfg.n_kv_heads, cfg.d_head
    cd = cfg.kvdtype
    k = v = ssm = conv = xk = xv = None
    if cfg.is_mla:          # the latent kind: one row per token, no V
        k = jnp.zeros((L, batch, max_seq, 1, cfg.latent_width), cd)
    elif cfg.has_attention:
        k = jnp.zeros((L, batch, max_seq, K, dh), cd)
        v = jnp.zeros((L, batch, max_seq, K, dh), cd)
    if cfg.has_ssm:
        (ss, cs) = ssm_mod.ssm_state_shapes(cfg, batch)
        ssm = jnp.zeros((L,) + ss, jnp.float32)
        conv = jnp.zeros((L,) + cs, cd)
    if cfg.family == "encdec":
        xk = jnp.zeros((L, batch, enc_len, K, dh), cd)
        xv = jnp.zeros((L, batch, enc_len, K, dh), cd)
    return Cache(k, v, ssm, conv, xk, xv, jnp.zeros((), jnp.int32))


def init_cache(cfg: ModelConfig, batch: int, max_seq: int,
               enc_len: int = 0, kind: str = "dense", **backend_kw):
    """Build a KV backend (``kind``: "dense" | "paged").

    The serving entry point of the KVBackend API: returns a
    ``kvcache.backend.KVBackend`` whose ``prefill``/``decode_step`` drive
    this model.  ``DenseBackend`` forwards ``.k``/``.v``/``.length`` reads
    to its underlying ``Cache``, so code written against the old concrete
    cache keeps working.
    """
    from repro.kvcache.backend import make_backend
    return make_backend(cfg, kind, batch=batch, max_seq=max_seq,
                        enc_len=enc_len, **backend_kw)


def abstract_cache(cfg: ModelConfig, batch: int, max_seq: int,
                   enc_len: int = 0):
    """Shape-only dense storage pytree (dry-run / sharding planning)."""
    return jax.eval_shape(
        lambda: init_dense_cache(cfg, batch, max_seq, enc_len))


def dense_decode_step(params, cfg: ModelConfig, tokens, cache: Cache):
    """One-token decode against dense storage (pure; jit/shard friendly).

    tokens: (B, 1) int32.  ``cache.length`` may be a scalar (all lanes at
    the same position) or an int32 (B,) vector for ragged decode — the
    paged backend decodes continuous-batching lanes whose sequences have
    different lengths in one call.  Returns (logits, cache).
    """
    B = tokens.shape[0]
    pos = jnp.asarray(cache.length)
    ragged = pos.ndim > 0
    posv = jnp.broadcast_to(jnp.atleast_1d(pos), (B,))
    positions = posv[:, None]
    x = layers.embed_tokens(params["embed"], tokens, cfg, positions)

    masks = None
    kv = None
    if cfg.has_attention:
        Smax = cache.k.shape[2]
        kpos = jnp.arange(Smax)[None, :]
        m_causal = kpos <= posv[:, None]
        m = m_causal
        if cfg.sliding_window:
            m = m_causal & (kpos > posv[:, None] - cfg.sliding_window)
        masks = (m[:, None, None, :] if cfg.sliding_window else
                 m_causal[:, None, None, :],
                 m_causal[:, None, None, :])
        kv = (cache.k, cache.v)
    ssm_states = (cache.ssm, cache.conv) if cfg.has_ssm else None
    xkv = (cache.xk, cache.xv) if cfg.family == "encdec" else None
    cache_pos = posv if ragged else pos

    nd = cfg.n_dense_layers if cfg.is_moe else 0
    ys_all = {}
    if nd:
        kv_d = jax.tree.map(lambda a: a[:nd], kv) if kv is not None else None
        x, _, ys = _scan_blocks(params["blocks_dense"], x, cfg, masks=masks,
                                positions=positions, layer_offset=0, n=nd,
                                kv=kv_d, cache_pos=cache_pos,
                                ssm_states=jax.tree.map(
                                    lambda a: a[:nd], ssm_states)
                                if ssm_states else None)
        ys_all["dense"] = ys
    kv_m = jax.tree.map(lambda a: a[nd:], kv) if kv is not None else None
    x, _, ys = _scan_blocks(
        params["blocks"], x, cfg, masks=masks, positions=positions,
        layer_offset=nd, n=cfg.n_layers - nd, kv=kv_m, cache_pos=cache_pos,
        ssm_states=jax.tree.map(lambda a: a[nd:], ssm_states)
        if ssm_states else None,
        xkv=xkv)
    ys_all["main"] = ys

    x = layers.apply_norm(params["final_norm"], x, cfg)
    logits = layers.lm_head(params["embed"], x, cfg)

    new_cache = Cache(
        k=_cat_layers(ys_all, "kv", 0) if cfg.has_attention else None,
        v=_cat_layers(ys_all, "kv", 1) if cfg.has_attention else None,
        ssm=_cat_layers(ys_all, "ssm", 0) if cfg.has_ssm else None,
        conv=_cat_layers(ys_all, "ssm", 1) if cfg.has_ssm else None,
        xk=cache.xk, xv=cache.xv,
        length=cache.length + 1)
    return logits, new_cache


def paged_decode_step(params, cfg: ModelConfig, tokens, k_pages, v_pages,
                      page_tables, lengths, *, ssm_state=None,
                      conv_state=None, interpret: bool | None = None):
    """One-token decode reading cached KV straight from the block pool via
    the Pallas ``paged_attention`` kernel — no gathered dense view.

    tokens: (B, 1) int32; k_pages/v_pages: the pool's folded device
    mirror (L, P, page, K·dh); page_tables: (B, n_pages) int32;
    lengths: (B,) int32 ragged per-lane cached token counts.  One page
    table serves every layer (the pool's layer axis = one placement
    decision per block id).  Sliding-window configs run natively: the
    scan flips the kernel's window mask per layer (``global_every``
    hybrids keep their global layers unmasked).

    Hybrid (attention + SSM) families thread their side state through the
    scan: ``ssm_state`` (L, B, H, P, N) float32 and ``conv_state``
    (L, B, k-1, ch) ride alongside the page operands — the PagedBackend
    keeps them per-sequence next to the block tables.

    Latent-KV (MLA) configs pass the latent pool's mirror (L, P, page,
    Wp) as ``k_pages`` and None as ``v_pages``; each layer runs the
    kernel's latent mode (``layers.mla_paged_apply``).

    Returns (logits (B, 1, V), k_new, v_new, ssm_new, conv_new, routed)
    with k_new/v_new (L, B, 1, K, dh) — the in-flight token's per-layer
    K/V for the caller's pool write-back (write-after-attend: the kernel
    never reads a partially-written page; for the latent kind k_new is
    the row (L, B, 1, 1, W) and v_new None) — ssm_new/conv_new the
    advanced side state (None for attention-only families), and routed
    the int32 count of the live lanes' (token, expert) assignments to
    the experts this model holds, summed over layers (None without
    experts).  ``interpret=None`` lets the platform decide whether the
    kernel runs compiled or interpreted (``repro.kernels.pallas_interpret``).
    """
    assert cfg.has_attention and cfg.family not in ("encdec", "vlm"), \
        f"kernel-path decode pages attention KV (+ SSM side state) only " \
        f"(family {cfg.family!r})"
    if cfg.has_ssm:
        assert ssm_state is not None and conv_state is not None, \
            "hybrid kernel-path decode needs ssm_state/conv_state"
    ssm_states = (ssm_state, conv_state) if cfg.has_ssm else None
    B = tokens.shape[0]
    lengths = jnp.asarray(lengths, jnp.int32)
    positions = lengths[:, None]
    x = layers.embed_tokens(params["embed"], tokens, cfg, positions)
    paged = dict(k_pages=k_pages, v_pages=v_pages, page_tables=page_tables,
                 lengths=lengths, interpret=interpret)

    nd = cfg.n_dense_layers if cfg.is_moe else 0
    ys_all = {}
    if nd:
        x, _, ys = _scan_blocks(params["blocks_dense"], x, cfg, masks=None,
                                positions=positions, layer_offset=0, n=nd,
                                ssm_states=jax.tree.map(
                                    lambda a: a[:nd], ssm_states)
                                if ssm_states else None,
                                paged=paged)
        ys_all["dense"] = ys
    x, aux, ys = _scan_blocks(params["blocks"], x, cfg, masks=None,
                              positions=positions, layer_offset=nd,
                              n=cfg.n_layers - nd,
                              ssm_states=jax.tree.map(
                                  lambda a: a[nd:], ssm_states)
                              if ssm_states else None,
                              paged=paged)
    ys_all["main"] = ys

    x = layers.apply_norm(params["final_norm"], x, cfg)
    logits = layers.lm_head(params["embed"], x, cfg)
    routed = aux["moe_routed_here"].astype(jnp.int32) if cfg.is_moe \
        else None

    return (logits, _cat_layers(ys_all, "kv", 0),
            _cat_layers(ys_all, "kv", 1), _cat_layers(ys_all, "ssm", 0),
            _cat_layers(ys_all, "ssm", 1), routed)


def decode_step(params, cfg: ModelConfig, tokens, cache):
    """One-token decode.  ``cache`` is either a concrete dense ``Cache``
    pytree (pure path, used under jit by the dry-run and the dense
    backend) or any ``KVBackend``.  Returns (logits, cache)."""
    if isinstance(cache, Cache):
        return dense_decode_step(params, cfg, tokens, cache)
    logits = cache.decode_step(params, tokens)
    return logits, cache


def prefill_parts(params, cfg: ModelConfig, tokens, frontend_emb=None):
    """Run the prompt, returning last-position logits plus every cacheable
    part — the storage-agnostic half of prefill that both backends share.

    Returns (logits (B,1,V), parts) with parts:
      k/v   (L, B, S, K, dh) or None   (post-RoPE, compute dtype); for
            the latent kind (MLA) k is the rows (L, B, S, 1, W), v None
      ssm   (L, B, H, P, N) or None    conv (L, B, k-1, ch) or None
      xk/xv (L, B, Senc, K, dh) or None
    """
    B, S = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
    x = layers.embed_tokens(params["embed"], tokens, cfg, positions)
    xkv = None
    if cfg.family == "encdec":
        enc_out = _encoder_forward(params, cfg, frontend_emb)
        xkv = _cross_kvs(params, cfg, enc_out)
    m_causal = layers.causal_mask(S, S)
    m_window = layers.causal_mask(S, S, window=cfg.sliding_window) \
        if cfg.sliding_window else m_causal
    masks = (m_window if cfg.sliding_window else m_causal, m_causal)
    nd = cfg.n_dense_layers if cfg.is_moe else 0
    ys_all = {}
    if nd:
        x, _, ys = _scan_blocks(params["blocks_dense"], x, cfg, masks=masks,
                                positions=positions, layer_offset=0, n=nd)
        ys_all["dense"] = ys
    x, _, ys = _scan_blocks(params["blocks"], x, cfg, masks=masks,
                            positions=positions, layer_offset=nd,
                            n=cfg.n_layers - nd, xkv=xkv)
    ys_all["main"] = ys
    x = layers.apply_norm(params["final_norm"], x, cfg)
    logits = layers.lm_head(params["embed"], x[:, -1:], cfg)

    parts = {
        "k": _cat_layers(ys_all, "kv", 0) if cfg.has_attention else None,
        "v": _cat_layers(ys_all, "kv", 1) if cfg.has_attention else None,
        "ssm": _cat_layers(ys_all, "ssm", 0) if cfg.has_ssm else None,
        "conv": _cat_layers(ys_all, "ssm", 1) if cfg.has_ssm else None,
        "xk": xkv[0] if xkv is not None else None,
        "xv": xkv[1] if xkv is not None else None,
    }
    return logits, parts


def dense_prefill(params, cfg: ModelConfig, tokens, max_seq: int,
                  frontend_emb=None):
    """Prompt -> (logits, concrete dense Cache)."""
    B, S = tokens.shape
    cache = init_dense_cache(cfg, B, max_seq,
                             enc_len=frontend_emb.shape[1]
                             if cfg.family == "encdec" else 0)
    logits, parts = prefill_parts(params, cfg, tokens, frontend_emb)
    if cfg.has_attention:
        cache.k = jax.lax.dynamic_update_slice_in_dim(
            cache.k, parts["k"].astype(cache.k.dtype), 0, axis=2)
    if cfg.has_attention and not cfg.is_mla:
        cache.v = jax.lax.dynamic_update_slice_in_dim(
            cache.v, parts["v"].astype(cache.v.dtype), 0, axis=2)
    if cfg.has_ssm:
        cache.ssm = parts["ssm"]
        cache.conv = parts["conv"]
    if cfg.family == "encdec":
        cache.xk, cache.xv = parts["xk"], parts["xv"]
    cache.length = jnp.asarray(S, jnp.int32)
    return logits, cache


def prefill(params, cfg: ModelConfig, tokens, max_seq: int = 0,
            frontend_emb=None, backend=None):
    """Run the prompt through the model, building the serving cache.

    Returns (logits, backend).  With ``backend=None`` a ``DenseBackend``
    sized by ``max_seq`` is created; pass a ``PagedBackend`` to prefill
    into pool block tables instead.
    """
    if backend is None:
        assert max_seq, "prefill needs max_seq (or an explicit backend)"
        backend = init_cache(cfg, tokens.shape[0], max_seq,
                             enc_len=frontend_emb.shape[1]
                             if cfg.family == "encdec" else 0)
    logits = backend.prefill(params, tokens, frontend_emb=frontend_emb)
    return logits, backend


def loss_fn(params, cfg: ModelConfig, tokens, labels, frontend_emb=None,
            remat: bool = False, aux_weight: float = 0.01):
    """Causal LM cross-entropy with MoE aux losses."""
    logits, aux = forward(params, cfg, tokens, frontend_emb, remat=remat)
    logits = logits.astype(jnp.float32)
    mask = labels >= 0
    safe = jnp.maximum(labels, 0)
    ll = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(ll, safe[..., None], axis=-1)[..., 0]
    loss = (nll * mask).sum() / jnp.maximum(mask.sum(), 1)
    loss = loss + aux_weight * (aux["moe_lb"] + 1e-3 * aux["moe_z"])
    return loss, {"lm_loss": loss, **aux}
