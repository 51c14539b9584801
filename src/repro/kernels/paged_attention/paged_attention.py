"""Paged-KV decode attention — Pallas TPU kernel (MARS page-visit order).

The serving analogue of the paper: a decode batch's KV reads are scattered
across cache pages ("DRAM rows"); visiting each sequence's pages
*in page-table order, page-contiguously* turns the gather into sequential
HBM block reads.  The page table is scalar-prefetched and names every
page the kernel copies — exactly the PhyPageList head/tail walk.

**Operand layout.**  The kernel reads the pool *folded*: K and V as
``(L, P, page, Hkv·D)``, every head of a token side by side in one row.
That is the layout the TPU gives such an array by default (row-major,
lanes unpadded when ``Hkv·D`` is a multiple of 128), so the served
device mirror (``PagedBackend._staged_pages``) is handed over as it is,
with no relayout.  The unfolded ``(P, page, Hkv, D)`` and
``(L, P, page, Hkv, D)`` pools the tests and the toy engine keep are
folded by the wrapper — a copy, off the served path.

**Grid.**  One grid step per lane, in order; the pool stays in HBM.
Inside a step the kernel loops over the lane's *live* pages only — from
the window's first page to the page holding the last cached position —
in blocks of ``pages_per_step`` pages (``TOKENS_PER_STEP`` tokens),
double-buffered: block ``k + 1``'s page copies are in flight while
block ``k`` is attended, and a lane's last block starts the next lane's
first copies.  Pages beyond the lane's length or outside its sliding
window are never copied, and a lane with nothing to attend (length 0,
padded lanes, ``window == 1``) costs no copy at all.

**Every head at once.**  A block is a ``(T, Hkv·D)`` tile of K and of V.
The query arrives folded too, ``(n_rep, Hkv·D)`` with ``n_rep = H //
Hkv``, and is spread into a block-diagonal ``(Hp, Hkv·D)`` matrix (query
head ``h`` keeps only the lanes of its KV head ``h // n_rep``; ``Hp``
rounds ``H`` up to the sublane tile), so the scores of every head are one
MXU dot ``(Hp, Hkv·D) × (T, Hkv·D)ᵀ``, and ``p × V`` one more, whose row
``h`` holds head ``h``'s output in its KV head's lanes.  MHA and GQA are
the same code.  Precision matches the f32 math: bf16 (or float8) pages
and queries multiply exactly into f32 accumulators; ``p`` stays f32,
split into three bf16 parts whose sum is ``p`` exactly before it meets
V; f32 operands run at ``Precision.HIGHEST``.

``window`` adds the sliding-window mask: with ``window > 0`` the query
(the in-flight token at position ``lengths[b]``) attends only cached
positions in ``(lengths[b] - window, lengths[b])`` — the same keys the
dense decode mask ``kpos > pos - window`` admits.  ``window`` is a traced
int32 scalar (scalar-prefetched alongside ``layer``), so a scan over a
``global_every`` hybrid's layers can flip it per layer (0 = global) with
one compiled kernel.

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
# tokens one block of pages covers (pages_per_step * page), at most
TOKENS_PER_STEP = 256
_HIGHEST = jax.lax.Precision.HIGHEST


def _window_lo(ln, w):
    """First valid cached position for a query at position ``ln`` under
    sliding window ``w`` (0 = global).  The canonical definition lives in
    the oracle (``ref._window_lo`` — kept independent so parity tests
    stay meaningful); ``ops._lane_lines`` mirrors it for the DRAM-trace
    bench."""
    return jnp.where(w > 0, ln - w + 1, 0)


def _bf16_exact(dtype) -> bool:
    """Whether every value of ``dtype`` is a bfloat16 value, so the MXU
    multiplies it exactly in one bf16 pass."""
    dtype = jnp.dtype(dtype)
    return dtype == jnp.bfloat16 or (
        jnp.issubdtype(dtype, jnp.floating) and dtype.itemsize == 1)


def _split3(x):
    """f32 ``x`` as three bf16 parts whose f32 sum is ``x`` exactly."""
    hi = x.astype(jnp.bfloat16)
    r = x - hi.astype(jnp.float32)
    mid = r.astype(jnp.bfloat16)
    lo = (r - mid.astype(jnp.float32)).astype(jnp.bfloat16)
    return hi, mid, lo


def _kernel(*refs, page: int, pps: int, n_pages: int, n_rep: int,
            scale: float, dv: int):
    """One lane's page walk.  ``dv`` 0: K and V pages (the standard
    mode).  ``dv`` > 0: the latent mode, one pool of rows whose first
    ``dv`` lanes are the value — one copy per page serves both."""
    if dv:
        (pt_ref, len_ref, layer_ref, win_ref, gid_ref, q_ref, k_hbm,
         o_ref, m_out_ref, l_out_ref,
         k_buf, sems, nxt, m_ref, l_ref, acc_ref) = refs
        v_hbm = v_buf = None
    else:
        (pt_ref, len_ref, layer_ref, win_ref, gid_ref, q_ref, k_hbm, v_hbm,
         o_ref, m_out_ref, l_out_ref,
         k_buf, v_buf, sems, nxt, m_ref, l_ref, acc_ref) = refs
    b = pl.program_id(0)
    la = layer_ref[0]
    w = win_ref[0]

    def span(lane):
        """(lo, ln, first live page, live pages) of ``lane``: the query
        sits at position ln, so its valid cached positions are [lo, ln)
        (w = 0 means global, lo <= 0)."""
        ln = len_ref[lane]
        lo = _window_lo(ln, w)
        j0 = jnp.maximum(lo, 0) // page
        return lo, ln, j0, jnp.where(lo < ln, (ln - 1) // page - j0 + 1, 0)

    lo, ln, j0, n_live = span(b)
    n_blk = (n_live + pps - 1) // pps

    def copies(lane, j0, n_live, blk, slot):
        """(live, K copy, V copy) for each page of ``lane``'s block
        ``blk`` into buffer ``slot``; only live pages are ever copied."""
        out = []
        for i in range(pps):
            j = blk * pps + i
            pid = pt_ref[lane * n_pages + jnp.minimum(j0 + j, n_pages - 1)]
            rows = pl.ds(i * page, page)
            out.append((j < n_live,
                        pltpu.make_async_copy(k_hbm.at[la, pid],
                                              k_buf.at[slot, rows],
                                              sems.at[0, slot]),
                        None if dv else
                        pltpu.make_async_copy(v_hbm.at[la, pid],
                                              v_buf.at[slot, rows],
                                              sems.at[1, slot])))
        return out

    def start(cps):
        for live, kc, vc in cps:
            @pl.when(live)
            def _():
                kc.start()
                if vc is not None:
                    vc.start()

    # the grid runs lanes in order, and a lane's last block starts the
    # next lane's first copies.  nxt: (this lane's first buffer slot,
    # whether the lane before already started its copies)
    @pl.when(b == 0)
    def _():
        nxt[0] = 0
        nxt[1] = 0

    s0 = nxt[0]

    @pl.when((n_blk > 0) & (nxt[1] == 0))
    def _():
        start(copies(b, j0, n_live, 0, s0))

    nb = jnp.minimum(b + 1, pl.num_programs(0) - 1)
    _, _, j0n, n_live_next = span(nb)
    prefetch_next = (b + 1 < pl.num_programs(0)) & (n_live_next > 0)

    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    Hp = acc_ref.shape[0]
    F = k_buf.shape[-1]
    T = pps * page
    # block-diagonal query: row h keeps query head h in the lanes of its
    # KV head h // n_rep (gid: each lane's KV head) and zeros elsewhere
    heads = jax.lax.broadcasted_iota(jnp.int32, (Hp, F), 0)
    gid = gid_ref[...]
    q = jnp.zeros((Hp, F), jnp.float32)
    for r in range(n_rep):
        q = jnp.where(heads == gid * n_rep + r,
                      q_ref[0, pl.ds(r, 1), :].astype(jnp.float32), q)
    # bf16 operands multiply exactly in one MXU pass; f32 ones need
    # HIGHEST
    if _bf16_exact(q_ref.dtype) and _bf16_exact(k_buf.dtype):
        qk_dtype, qk_precision = jnp.bfloat16, None
    else:
        qk_dtype, qk_precision = jnp.float32, _HIGHEST
    q = q.astype(qk_dtype)

    def body(blk, carry):
        slot = (s0 + blk) % 2

        @pl.when(blk + 1 < n_blk)
        def _():
            start(copies(b, j0, n_live, blk + 1, 1 - slot))

        @pl.when((blk + 1 == n_blk) & prefetch_next)
        def _():
            start(copies(nb, j0n, n_live_next, 0, 1 - slot))

        for i, (live, kc, vc) in enumerate(copies(b, j0, n_live, blk,
                                                  slot)):
            @pl.when(live)
            def _():
                kc.wait()
                if vc is not None:
                    vc.wait()

            # a page the block does not fill holds no copy: zero its V
            # rows (the latent mode's rows) so that p = 0 there meets
            # finite values
            val_buf = k_buf if dv else v_buf

            @pl.when(jnp.logical_not(live))
            def _():
                val_buf[slot, pl.ds(i * page, page), :] = jnp.zeros(
                    (page, F), val_buf.dtype)

        s = jax.lax.dot_general(
            q, k_buf[slot].astype(qk_dtype), (((1,), (1,)), ((), ())),
            precision=qk_precision,
            preferred_element_type=jnp.float32) * scale      # (Hp, T)
        pos = (j0 + blk * pps) * page \
            + jax.lax.broadcasted_iota(jnp.int32, (1, T), 1)
        s = jnp.where((pos >= lo) & (pos < ln), s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + p.sum(-1, keepdims=True)
        v = k_buf[slot, :, :dv] if dv else v_buf[slot]
        if _bf16_exact(v.dtype):
            # p stays f32: its three bf16 parts, stacked, meet V in one
            # dot and are summed back in f32
            pv3 = jax.lax.dot_general(
                jnp.concatenate(_split3(p), axis=0),
                v.astype(jnp.bfloat16), (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            pv = pv3[:Hp] + pv3[Hp:2 * Hp] + pv3[2 * Hp:]
        else:
            pv = jax.lax.dot_general(
                p, v.astype(jnp.float32), (((1,), (0,)), ((), ())),
                precision=_HIGHEST, preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * alpha + pv
        m_ref[...] = m_new
        return carry

    jax.lax.fori_loop(0, n_blk, body, 0)
    nxt[0] = (s0 + n_blk) % 2
    nxt[1] = jnp.where((n_blk > 0) & prefetch_next, 1, 0)

    o = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)      # (Hp, F)
    if dv:
        o_ref[0] = o                # one KV head: row h is query head h
    else:
        # fold back: row r of the output holds query head g * n_rep + r
        # in the lanes of KV head g
        for r in range(n_rep):
            o_ref[0, pl.ds(r, 1), :] = jnp.sum(
                jnp.where(heads == gid * n_rep + r, o, 0.0), axis=0,
                keepdims=True)
    m_out_ref[0] = m_ref[...]
    l_out_ref[0] = l_ref[...]


def fold_pages(pages):
    """``(P, page, Hkv, D)`` or ``(L, P, page, Hkv, D)`` pages as the
    kernel reads them: ``(L, P, page, Hkv·D)`` (a single-layer pool gets
    ``L = 1``)."""
    if pages.ndim == 4:
        pages = pages[None]
    L, P, page, Hkv, D = pages.shape
    return pages.reshape(L, P, page, Hkv * D)


def paged_attention(q, k_pages, v_pages, page_tables, lengths, *,
                    layer=None, window=0, folded: bool = False,
                    interpret: bool | None = None,
                    return_state: bool = False):
    """q: (B, H, D); k/v_pages: (P, page, Hkv, D) or, for a layered block
    pool, (L, P, page, Hkv, D) with ``layer`` selecting the plane; with
    ``folded`` the pool as the kernel reads it, (L, P, page, Hkv·D)
    (``fold_pages``; the served device mirror).  page_tables: (B,
    n_pages); lengths: (B,).  ``window`` > 0 restricts each query to the
    last ``window`` positions (query at ``lengths[b]`` included); 0
    attends all cached positions.

    Returns (B, H, D), or with ``return_state`` the online-softmax state
    ``(o, m, l)`` (o: (B, H, D) float32, m/l: (B, H, 1) float32) so a
    caller can merge more keys — e.g. the decode step's in-flight token —
    without renormalizing.  A lane whose window admits no cached position
    (length 0, or ``window == 1``) comes back as (o=0, m=-inf, l=0) for
    the merge.  ``interpret=None`` lets the platform decide
    (``pallas_interpret``).
    """
    # concrete-value validation must live outside the jit boundary —
    # inside, every operand is a tracer and isinstance checks are dead
    if not folded and k_pages.ndim == 4 \
            and isinstance(layer, (int, np.integer)) and layer != 0:
        raise ValueError(
            f"4-D pages have only plane 0, got layer={layer} — a "
            f"calling-convention mix-up (layered pools are 5-D)")
    if not folded:
        k_pages, v_pages = fold_pages(k_pages), fold_pages(v_pages)
        if k_pages.shape[0] == 1:
            layer = 0
    if interpret is None:
        interpret = pallas_interpret()
    o, m, l = _paged_attention(q, k_pages, v_pages, page_tables, lengths,
                               layer=layer, window=window,
                               interpret=interpret)
    return (o, m, l) if return_state else o.astype(q.dtype)


@functools.partial(jax.jit, static_argnames=("interpret", "scale", "dv"))
def _paged_attention(q, k_pages, v_pages, page_tables, lengths, *,
                     layer=None, window=0, interpret: bool = False,
                     scale=None, dv: int = 0):
    """The kernel call.  ``dv`` 0: the standard mode over K and V pages.
    ``dv`` > 0: the latent mode (``mla_attention``), ``v_pages`` None."""
    assert layer is not None, "layered k_pages needs a layer index"
    B, H, D = q.shape
    L, P, page, F = k_pages.shape
    if dv:
        # one row per token shared by every head; the pool keeps it padded
        # to whole 128-lane tiles, so only the query is padded here
        Hkv, n_rep, Fp = 1, H, F
        assert Fp % 128 == 0 and D <= Fp, (D, Fp)
        F = D
    else:
        Hkv = F // D
        n_rep = H // Hkv
        Fp = -(-F // 128) * 128
    n_pages = page_tables.shape[1]
    pps = max(1, min(n_pages, TOKENS_PER_STEP // page))
    Hp = -(-H // 16) * 16            # whole bf16 sublane tiles
    if scale is None:
        scale = 1.0 / np.sqrt(D)
    layer_arr = jnp.atleast_1d(jnp.asarray(layer, jnp.int32))
    win_arr = jnp.atleast_1d(jnp.asarray(window, jnp.int32))
    # (B, H, D) -> (B, n_rep, Hkv·D): row r holds heads g * n_rep + r
    qf = q.reshape(B, Hkv, n_rep, D).transpose(0, 2, 1, 3) \
        .reshape(B, n_rep, F)
    if Fp != F:
        qf = jnp.pad(qf, ((0, 0), (0, 0), (0, Fp - F)))
    if Fp != k_pages.shape[-1]:
        # Mosaic copies a page only in whole 128-lane tiles: a folded
        # width off the tile (hymba's 5 x 64) is padded here, a pool-sized
        # copy; the served qwen width (16 x 64) is read as it is
        pad = ((0, 0),) * 3 + ((0, Fp - F),)
        k_pages, v_pages = jnp.pad(k_pages, pad), jnp.pad(v_pages, pad)
    lane = jnp.arange(Fp, dtype=jnp.int32)
    # each lane's KV head; -1 on padding lanes, which no head reads
    gid = jnp.where(lane < F, lane // D, -1)[None]             # (1, Fp)
    pools = (k_pages,) if dv else (k_pages, v_pages)
    buffers = [pltpu.VMEM((2, pps * page, Fp), a.dtype) for a in pools]
    o_spec = pl.BlockSpec((1, Hp, dv), lambda b, *_: (b, 0, 0)) if dv \
        else pl.BlockSpec((1, n_rep, Fp), lambda b, *_: (b, 0, 0))
    o_shape = (B, Hp, dv) if dv else (B, n_rep, Fp)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, Fp), lambda b, *_: (0, 0)),
            pl.BlockSpec((1, n_rep, Fp), lambda b, *_: (b, 0, 0)),
        ] + [pl.BlockSpec(memory_space=pltpu.HBM)] * len(pools),
        out_specs=[
            o_spec,
            pl.BlockSpec((1, Hp, 1), lambda b, *_: (b, 0, 0)),
            pl.BlockSpec((1, Hp, 1), lambda b, *_: (b, 0, 0)),
        ],
        scratch_shapes=buffers + [
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SMEM((2,), jnp.int32),
            pltpu.VMEM((Hp, 1), jnp.float32),
            pltpu.VMEM((Hp, 1), jnp.float32),
            pltpu.VMEM((Hp, dv or Fp), jnp.float32),
        ],
    )
    o, m, l = pl.pallas_call(
        functools.partial(_kernel, page=page, pps=pps, n_pages=n_pages,
                          n_rep=n_rep, scale=scale, dv=dv),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(o_shape, jnp.float32),
                   jax.ShapeDtypeStruct((B, Hp, 1), jnp.float32),
                   jax.ShapeDtypeStruct((B, Hp, 1), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="mla_attention" if dv else "paged_attention",
    )(page_tables.reshape(-1), lengths, layer_arr, win_arr,
      gid, qf, *pools)
    if dv:
        o = o[:, :H]
    else:
        o = o[..., :F].reshape(B, n_rep, Hkv, D).transpose(0, 2, 1, 3) \
            .reshape(B, H, D)
    return o, m[:, :H], l[:, :H]


def decode_attend(q, k_new, v_new, k_pages, v_pages, page_tables,
                  lengths, *, layer=0, window=0, folded: bool = False,
                  interpret: bool | None = None):
    """Decode-step attention: the paged kernel over the cached pages plus
    one online-softmax merge step for the in-flight token (position
    ``lengths[b]``, always attended — it is its own causal context and
    always inside any sliding window).

    q: (B, H, D); k_new/v_new: (B, Hkv, D) — the in-flight token's K/V,
    not yet written to the pool; k/v_pages and ``folded`` as
    ``paged_attention`` takes them.  ``window`` > 0 applies the sliding-
    window mask to the cached positions.  Returns (B, H, D).

    A lane with ``lengths[b] == 0`` degenerates cleanly: the kernel state
    is (m=-inf, l=0) and the merge reduces to attending the token alone.
    """
    B, H, D = q.shape
    Hkv = k_new.shape[1]
    n_rep = H // Hkv
    scale = 1.0 / np.sqrt(D)
    o, m, l = paged_attention(q, k_pages, v_pages, page_tables, lengths,
                              layer=layer, window=window, folded=folded,
                              interpret=interpret, return_state=True)
    # score of the in-flight token, same GQA head layout as the kernel
    qg = q.reshape(B, Hkv, n_rep, D)
    s_new = jnp.einsum("bhrd,bhd->bhr", qg.astype(jnp.float32),
                       k_new.astype(jnp.float32),
                       precision=jax.lax.Precision.HIGHEST) * scale
    s_new = s_new.reshape(B, H, 1)
    v_rep = jnp.repeat(v_new, n_rep, axis=1).astype(jnp.float32)  # (B,H,D)
    return _merge_token(o, m, l, s_new, v_rep).astype(q.dtype)


def _merge_token(o, m, l, s_new, v):
    """One online-softmax merge step: the kernel's state ``(o, m, l)``
    over the cached pages, then the in-flight token with score ``s_new``
    (B, H, 1) and value ``v`` (B, H, Dv), float32.  Returns the
    normalised output (B, H, Dv)."""
    m2 = jnp.maximum(m, s_new)
    alpha = jnp.exp(m - m2)
    p = jnp.exp(s_new - m2)
    l2 = l * alpha + p
    return (o * (l * alpha) + p * v) / jnp.maximum(l2, 1e-30)


def mla_attention(q, pages, page_tables, lengths, *, layer, scale: float,
                  dv: int, interpret: bool | None = None,
                  return_state: bool = False):
    """The kernel's latent mode: absorbed multi-head latent attention, a
    multi-query attention over one shared row per cached token.

    q: (B, H, W) absorbed queries ``[q_nope W_uk, q_pe]``; pages: the
    latent pool's device mirror (L, P, page, Wp), each row ``[c, k_pe]``
    in its first W lanes and zeros up to Wp, a whole number of 128-lane
    tiles.  Scores are ``q · row * scale``; the value is the row's first
    ``dv`` lanes (the latent ``c``), so each page is copied once and
    serves both.  Returns (B, H, dv), or with ``return_state`` the
    online-softmax state as ``paged_attention`` gives it."""
    if interpret is None:
        interpret = pallas_interpret()
    o, m, l = _paged_attention(q, pages, None, page_tables, lengths,
                               layer=layer, interpret=interpret,
                               scale=float(scale), dv=int(dv))
    return (o, m, l) if return_state else o.astype(q.dtype)


def mla_decode_attend(q, row_new, pages, page_tables, lengths, *, layer,
                      scale: float, dv: int,
                      interpret: bool | None = None):
    """Decode-step latent attention: ``mla_attention`` over the cached
    pages plus one online-softmax merge step for the in-flight token's
    row ``row_new`` (B, W), not yet in the pool.  Returns (B, H, dv)."""
    o, m, l = mla_attention(q, pages, page_tables, lengths, layer=layer,
                            scale=scale, dv=dv, interpret=interpret,
                            return_state=True)
    row = row_new.astype(jnp.float32)
    s_new = jnp.einsum("bhw,bw->bh", q.astype(jnp.float32), row,
                       precision=_HIGHEST)[..., None] * scale
    return _merge_token(o, m, l, s_new, row[:, None, :dv]).astype(q.dtype)
