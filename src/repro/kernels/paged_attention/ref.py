"""Pure-jnp oracle for paged-KV decode attention.

Cache layout: KV lives in fixed-size pages; each sequence owns a list of
page ids (its "page table").  One decode step attends one query token per
sequence over its first ``length`` cached positions.

``paged_attention_ref`` mirrors the kernel (cached positions only);
``paged_decode_ref`` is the full decode-step oracle: cached positions
*plus* the in-flight token's K/V, computed with one plain softmax over the
concatenated keys — what ``paged_attention.decode_attend`` must match.
Both accept 4-D pages or a layered 5-D pool buffer with ``layer``, and a
``window`` > 0 sliding-window restriction (query at position ``length``,
so valid cached positions are ``(length - window, length)``; the
in-flight token is always inside the window).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def _layer_plane(k_pages, v_pages, layer):
    if k_pages.ndim == 5:
        return k_pages[layer], v_pages[layer]
    return k_pages, v_pages


def _window_lo(ln, window):
    """First valid cached position for a query at position ``ln``."""
    w = jnp.asarray(window, jnp.int32)
    return jnp.where(w > 0, ln - w + 1, 0)


def paged_attention_ref(q, k_pages, v_pages, page_tables, lengths,
                        layer=0, window=0):
    """q: (B, H, D); k_pages/v_pages: (P, page, Hkv, D) or layered
    (L, P, page, Hkv, D); page_tables: int32 (B, pages_per_seq);
    lengths: int32 (B,); ``window`` 0 = global.

    Returns (B, H, D).  GQA via H % Hkv == 0 head repetition."""
    B, H, D = q.shape
    k_pages, v_pages = _layer_plane(k_pages, v_pages, layer)
    P, page, Hkv, _ = k_pages.shape
    n_rep = H // Hkv
    scale = 1.0 / np.sqrt(D)

    def one(qb, pt, ln):
        k = k_pages[pt].reshape(-1, Hkv, D)      # (S_max, Hkv, D)
        v = v_pages[pt].reshape(-1, Hkv, D)
        k = jnp.repeat(k, n_rep, axis=1)
        v = jnp.repeat(v, n_rep, axis=1)
        s = jnp.einsum("hd,khd->hk", qb, k).astype(jnp.float32) * scale
        pos = jnp.arange(k.shape[0])
        mask = (pos < ln) & (pos >= _window_lo(ln, window))
        s = jnp.where(mask[None, :], s, -1e30)
        w = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hk,khd->hd", w.astype(qb.dtype), v)

    return jax.vmap(one)(q, page_tables, lengths)


def paged_decode_ref(q, k_new, v_new, k_pages, v_pages, page_tables,
                     lengths, layer=0, window=0):
    """Decode-step oracle: attend the cached pages AND the in-flight
    token (k_new/v_new: (B, Hkv, D)) with one flat softmax; ``window``
    > 0 restricts the cached positions to the sliding window (the
    in-flight token is always attended).

    Returns (B, H, D)."""
    B, H, D = q.shape
    k_pages, v_pages = _layer_plane(k_pages, v_pages, layer)
    P, page, Hkv, _ = k_pages.shape
    n_rep = H // Hkv
    scale = 1.0 / np.sqrt(D)

    def one(qb, kn, vn, pt, ln):
        k = jnp.concatenate(
            [k_pages[pt].reshape(-1, Hkv, D), kn[None]], axis=0)
        v = jnp.concatenate(
            [v_pages[pt].reshape(-1, Hkv, D), vn[None]], axis=0)
        k = jnp.repeat(k, n_rep, axis=1)
        v = jnp.repeat(v, n_rep, axis=1)
        s = jnp.einsum("hd,khd->hk",
                       qb.astype(jnp.float32),
                       k.astype(jnp.float32)) * scale
        S = k.shape[0]
        pos = jnp.arange(S)
        # cached positions inside [window lo, ln) are valid; the final
        # slot is the in-flight token itself (its own causal context,
        # always inside the window) — always attended
        mask = ((pos < ln) & (pos >= _window_lo(ln, window))) \
            | (pos == S - 1)
        s = jnp.where(mask[None, :], s, -1e30)
        w = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hk,khd->hd", w,
                          v.astype(jnp.float32)).astype(qb.dtype)

    return jax.vmap(one)(q, k_new, v_new, page_tables, lengths)


def mla_attention_ref(q, pages, page_tables, lengths, layer=0, *,
                      scale: float, dv: int, row_new=None):
    """Latent-mode oracle (absorbed MLA decode): q (B, H, W) against the
    rows of a latent pool, (L, P, page, Wp) or (P, page, Wp), whose first
    W lanes hold ``[c, k_pe]``; the value is a row's first ``dv`` lanes.
    ``row_new`` (B, W), if given, is the in-flight token, attended after
    the cached positions.  Returns (B, H, dv) float32."""
    if pages.ndim == 4:
        pages = pages[layer]
    W = q.shape[-1]

    def one(qb, pt, ln, new):
        rows = pages[pt].reshape(-1, pages.shape[-1])[:, :W]
        rows = rows.astype(jnp.float32)
        valid = jnp.arange(rows.shape[0]) < ln
        if new is not None:
            rows = jnp.concatenate([rows, new[None].astype(jnp.float32)])
            valid = jnp.concatenate([valid, jnp.ones(1, bool)])
        s = jnp.einsum("hw,kw->hk", qb.astype(jnp.float32), rows,
                       precision=jax.lax.Precision.HIGHEST) * scale
        s = jnp.where(valid[None, :], s, -1e30)
        w = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hk,kv->hv", w, rows[:, :dv],
                          precision=jax.lax.Precision.HIGHEST)

    if row_new is None:
        return jax.vmap(lambda qb, pt, ln: one(qb, pt, ln, None))(
            q, page_tables, lengths)
    return jax.vmap(one)(q, page_tables, lengths, row_new)
