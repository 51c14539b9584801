"""Paged-KV decode attention — Pallas TPU kernel (MARS page-visit order).

The serving analogue of the paper: a decode batch's KV reads are scattered
across cache pages ("DRAM rows"); visiting each sequence's pages
*in page-table order, page-contiguously* turns the gather into sequential
HBM block reads.  The page table is scalar-prefetched and drives the K/V
BlockSpec index maps — exactly the PhyPageList head/tail walk.

Grid: (B, pages_per_seq) with online-softmax state in VMEM scratch across
the page loop; one query token per sequence (decode).

The kernel understands the block pool's leading **layer axis**: pass
``k_pages``/``v_pages`` of shape (L, P, page, Hkv, D) plus ``layer`` and
the index map reads plane ``layer`` of the pool directly — one block-table
lookup serves every layer of a row group, and no per-layer plane is ever
materialized.  4-D pages (single-layer pools, the PR-1 engine) keep
working unchanged.

``window`` adds the sliding-window mask: with ``window > 0`` the query
(the in-flight token at position ``lengths[b]``) attends only cached
positions in ``(lengths[b] - window, lengths[b])`` — the same keys the
dense decode mask ``kpos > pos - window`` admits.  ``window`` is a traced
int32 scalar (scalar-prefetched alongside ``layer``), so a scan over a
``global_every`` hybrid's layers can flip it per layer (0 = global) with
one compiled kernel.  Pages that fall entirely outside the window are
skipped — never fetched, never touching the DRAM address stream.

``decode_attend`` is the full decode-step attention: kernel over the
cached pages + one online-softmax merge step folding in the in-flight
token's K/V (which is not in the pool yet — the backend writes it back
*after* the step, so the kernel never reads a partially-written page).
The in-flight token is its own causal context and always inside any
window, so the merge step needs no mask.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import pallas_interpret

NEG_INF = -1e30


def _window_lo(ln, w):
    """First valid cached position for a query at position ``ln`` under
    sliding window ``w`` (0 = global).  The canonical definition lives in
    the oracle (``ref._window_lo`` — kept independent so parity tests
    stay meaningful); ``ops._lane_lines`` mirrors it for the DRAM-trace
    bench."""
    return jnp.where(w > 0, ln - w + 1, 0)


def _kernel(pt_ref, len_ref, layer_ref, win_ref, q_ref, k_ref, v_ref,
            o_ref, m_out_ref, l_out_ref,
            m_ref, l_ref, acc_ref, *, page: int, n_pages: int,
            n_rep: int, scale: float):
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    ln = len_ref[b]
    w = win_ref[0]
    base = j * page
    # sliding window: the query sits at position ln, so valid cached
    # positions are [lo, ln) (w = 0 means global, lo <= 0).  The page
    # gate must admit a page only if it holds at least one valid
    # position — a fully-masked page would feed
    # exp(NEG_INF - NEG_INF) = 1 into the softmax state.
    lo = _window_lo(ln, w)

    @pl.when((base < ln) & (base + page > lo) & (lo < ln))
    def _body():
        pos = base + jax.lax.broadcasted_iota(jnp.int32, (n_rep, page), 1)
        valid = (pos < ln) & (pos >= lo)
        # GQA: kv head h serves query rows [h*n_rep, (h+1)*n_rep).  Each
        # head is a pair of 2-D dots read straight from the refs — Mosaic
        # has no layout for folding (H, D) into (Hkv, n_rep, D) in-kernel,
        # nor for batched dots over the middle (head) axis of a page.
        # f32 at HIGHEST keeps the products exact on the MXU (default
        # precision would round the f32 operands to bf16).
        for h in range(k_ref.shape[3]):
            rows = pl.ds(h * n_rep, n_rep)
            q = q_ref[0, rows, :].astype(jnp.float32)          # (n_rep, D)
            k = k_ref[0, 0, :, h, :].astype(jnp.float32)       # (page, D)
            v = v_ref[0, 0, :, h, :].astype(jnp.float32)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32) * scale
            s = jnp.where(valid, s, NEG_INF)
            m_prev = m_ref[rows, :]
            m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_ref[rows, :] = l_ref[rows, :] * alpha \
                + p.sum(-1, keepdims=True)
            pv = jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())),
                precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32)
            acc_ref[rows, :] = acc_ref[rows, :] * alpha + pv
            m_ref[rows, :] = m_new

    @pl.when(j == n_pages - 1)
    def _store():
        o_ref[0] = (acc_ref[...]
                    / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)
        m_out_ref[0] = m_ref[...]
        l_out_ref[0] = l_ref[...]


def paged_attention(q, k_pages, v_pages, page_tables, lengths, *,
                    layer=None, window=0, interpret: bool | None = None,
                    return_state: bool = False):
    """q: (B, H, D); k/v_pages: (P, page, Hkv, D) or, for a layered block
    pool, (L, P, page, Hkv, D) with ``layer`` selecting the plane;
    page_tables: (B, n_pages); lengths: (B,).  ``window`` > 0 restricts
    each query to the last ``window`` positions (query at ``lengths[b]``
    included); 0 attends all cached positions.

    Returns (B, H, D), or with ``return_state`` the online-softmax state
    ``(o, m, l)`` (m/l: (B, H, 1) float32) so a caller can merge more
    keys — e.g. the decode step's in-flight token — without renormalizing.
    A lane whose window admits no cached position (length 0, or
    ``window == 1``) comes back as (o=0, m=-inf, l=0) for the merge.
    ``interpret=None`` lets the platform decide (``pallas_interpret``).
    """
    # concrete-value validation must live outside the jit boundary —
    # inside, every operand is a tracer and isinstance checks are dead
    if k_pages.ndim == 4 and isinstance(layer, (int, np.integer)) \
            and layer != 0:
        raise ValueError(
            f"4-D pages have only plane 0, got layer={layer} — a "
            f"calling-convention mix-up (layered pools are 5-D)")
    if interpret is None:
        interpret = pallas_interpret()
    return _paged_attention(q, k_pages, v_pages, page_tables, lengths,
                            layer=layer, window=window,
                            interpret=interpret, return_state=return_state)


@functools.partial(jax.jit,
                   static_argnames=("interpret", "return_state"))
def _paged_attention(q, k_pages, v_pages, page_tables, lengths, *,
                     layer=None, window=0, interpret: bool = False,
                     return_state: bool = False):
    B, H, D = q.shape
    if k_pages.ndim == 4:            # single-layer pool: lift to one plane
        k_pages = k_pages[None]
        v_pages = v_pages[None]
        layer = 0
    assert layer is not None, "layered k_pages needs a layer index"
    L, P, page, Hkv, _ = k_pages.shape
    n_pages = page_tables.shape[1]
    n_rep = H // Hkv
    scale = 1.0 / np.sqrt(D)
    layer_arr = jnp.atleast_1d(jnp.asarray(layer, jnp.int32))
    win_arr = jnp.atleast_1d(jnp.asarray(window, jnp.int32))

    def kv_index(b, j, pt, ln, la, w):
        # MARS page walk: the page table drives the block index; the
        # layer plane comes straight from the layered pool buffer.  The
        # fetch gate lives HERE, not in the kernel body — a pl.when only
        # skips compute, the pipeline still DMAs whatever the index map
        # names.  Clamping j to the lane's valid page range [j0, jmax]
        # makes every out-of-range grid step re-name the same in-range
        # block, and Pallas elides the copy when consecutive steps map to
        # the same block — out-of-window (and beyond-length) pages never
        # reach the DRAM address stream.
        lnb = ln[b]
        lo = _window_lo(lnb, w[0])
        j0 = jnp.maximum(lo, 0) // page
        jmax = jnp.maximum(lnb - 1, 0) // page
        jj = jnp.clip(j, j0, jnp.maximum(jmax, j0))
        return (la[0], pt[b, jj], 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(B, n_pages),
        in_specs=[
            pl.BlockSpec((1, H, D), lambda b, j, pt, ln, la, w: (b, 0, 0)),
            pl.BlockSpec((1, 1, page, Hkv, D), kv_index),
            pl.BlockSpec((1, 1, page, Hkv, D), kv_index),
        ],
        out_specs=[
            pl.BlockSpec((1, H, D), lambda b, j, pt, ln, la, w: (b, 0, 0)),
            pl.BlockSpec((1, H, 1), lambda b, j, pt, ln, la, w: (b, 0, 0)),
            pl.BlockSpec((1, H, 1), lambda b, j, pt, ln, la, w: (b, 0, 0)),
        ],
        scratch_shapes=[pltpu.VMEM((H, 1), jnp.float32),
                        pltpu.VMEM((H, 1), jnp.float32),
                        pltpu.VMEM((H, D), jnp.float32)],
    )
    o, m, l = pl.pallas_call(
        functools.partial(_kernel, page=page, n_pages=n_pages,
                          n_rep=n_rep, scale=scale),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, H, D), q.dtype),
                   jax.ShapeDtypeStruct((B, H, 1), jnp.float32),
                   jax.ShapeDtypeStruct((B, H, 1), jnp.float32)],
        interpret=interpret,
        name="paged_attention",
    )(page_tables, lengths, layer_arr, win_arr, q, k_pages, v_pages)
    return (o, m, l) if return_state else o


def decode_attend(q, k_new, v_new, k_pages, v_pages, page_tables,
                  lengths, *, layer=0, window=0,
                  interpret: bool | None = None):
    """Decode-step attention: the paged kernel over the cached pages plus
    one online-softmax merge step for the in-flight token (position
    ``lengths[b]``, always attended — it is its own causal context and
    always inside any sliding window).

    q: (B, H, D); k_new/v_new: (B, Hkv, D) — the in-flight token's K/V,
    not yet written to the pool.  ``window`` > 0 applies the sliding-
    window mask to the cached positions.  Returns (B, H, D).

    A lane with ``lengths[b] == 0`` degenerates cleanly: the kernel state
    is (m=-inf, l=0) and the merge reduces to attending the token alone.
    """
    B, H, D = q.shape
    Hkv = k_new.shape[1]
    n_rep = H // Hkv
    scale = 1.0 / np.sqrt(D)
    o, m, l = paged_attention(q, k_pages, v_pages, page_tables, lengths,
                              layer=layer, window=window,
                              interpret=interpret, return_state=True)
    # score of the in-flight token, same GQA head layout as the kernel
    qg = q.reshape(B, Hkv, n_rep, D)
    s_new = jnp.einsum("bhrd,bhd->bhr", qg.astype(jnp.float32),
                       k_new.astype(jnp.float32),
                       precision=jax.lax.Precision.HIGHEST) * scale
    s_new = s_new.reshape(B, H, 1)
    m2 = jnp.maximum(m, s_new)
    alpha = jnp.exp(m - m2)
    p = jnp.exp(s_new - m2)
    l2 = l * alpha + p
    v_rep = jnp.repeat(v_new, n_rep, axis=1).astype(jnp.float32)  # (B,H,D)
    o2 = (o.astype(jnp.float32) * (l * alpha) + p * v_rep) \
        / jnp.maximum(l2, 1e-30)
    return o2.astype(q.dtype)
