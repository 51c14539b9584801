"""Bulk MARS reorder — the policy of the cycle-level engine as a single
vectorized program transform.

The hardware engine (``core.mars``) is online: bounded RequestQ window,
group-by-page, pages drained oldest-first.  Inside a bulk-synchronous TPU
step the same policy becomes: within a bounded window of requests (tokens /
indices / KV-page reads), emit requests grouped by destination page, pages
ordered by first arrival, FIFO within a page.  That is exactly a stable
argsort by ``first_arrival[page_of(i)]`` — computable on-device in O(n log n)
with no data-dependent shapes, hence jit/pjit friendly.

This module is the bridge between the paper-faithful simulator and the
TPU-native kernels: ``kernels/moe_dispatch``, ``kernels/mars_gather``,
``serving/scheduler`` and ``data/pipeline`` all consume these functions.

The emission order has two renderings of one permutation.  ``mars_order``
is the jax one, for streams that live on the device inside a program
(and the DRAM-model replays of ``benchmarks/kvcache_bench.py``).
``mars_order_host`` is the numpy one, for the short streams the host
orders between programs: the engine's decode lanes
(``kernels.paged_attention.ops.batch_lane_order``) and a tier promotion
batch (``kvcache.tiers.promotion_order``).  Run eagerly on tens of ints,
the jax rendering would dispatch each of its primitives as a device
program of its own.
"""
from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np


def mars_order(page_ids: jnp.ndarray, *, num_pages: int | None = None,
               window: int | None = None) -> jnp.ndarray:
    """Return the MARS emission permutation for a stream of page ids.

    ``perm`` such that ``page_ids[perm]`` is grouped by page, pages in
    first-arrival (oldest-first) order, FIFO within a page — the
    PhyPageOrderQ policy with an unbounded RequestQ.  With ``window`` set,
    the stream is processed in independent windows of that size (the
    bounded-RequestQ semantics of the hardware engine, up to drain-boundary
    effects).
    """
    page_ids = jnp.asarray(page_ids)
    n = page_ids.shape[0]
    if n == 0:
        # empty stream (e.g. a zero-sequence decode batch from an idle
        # engine step): the identity permutation, not an associative_scan
        # over zero segments — mirrors the mars_reorder empty-input fix
        return jnp.zeros(0, jnp.int32)
    if window is not None and window < n:
        pad = (-n) % window
        padded = jnp.concatenate(
            [page_ids, jnp.full(pad, jnp.iinfo(jnp.int32).max, page_ids.dtype)])
        wperm = jax.vmap(lambda p: _mars_order_full(p, num_pages))(
            padded.reshape(-1, window))
        base = (jnp.arange(wperm.shape[0]) * window)[:, None]
        return (wperm + base).reshape(-1)[:n]
    return _mars_order_full(page_ids, num_pages)


def mars_order_host(keys: Sequence[int] | np.ndarray) -> np.ndarray:
    """``mars_order``'s permutation (no window), computed on the host.

    A stable sort by each key's first arrival: keys grouped, groups in
    first-arrival order, FIFO within a group.  Returns int32, equal to
    ``np.asarray(mars_order(keys))`` for any int32 keys, ``-1`` included.

    >>> mars_order_host([3, 1, 3, 1, 2]).tolist()
    [0, 2, 1, 3, 4]
    """
    keys = np.asarray(keys)
    if keys.size == 0:
        return np.zeros(0, np.int32)
    _, first, inverse = np.unique(keys, return_index=True,
                                  return_inverse=True)
    return np.argsort(first[inverse], kind="stable").astype(np.int32)


def _mars_order_full(page_ids: jnp.ndarray, num_pages: int | None) -> jnp.ndarray:
    n = page_ids.shape[0]
    arrival = jnp.arange(n, dtype=jnp.int32)
    if num_pages is not None:
        # dense page-id space (e.g. experts): segment-min first arrival
        first = jnp.full(num_pages, n, jnp.int32).at[page_ids].min(arrival)
        key = first[page_ids]
    else:
        # sparse page-id space: first arrival via sort-scan-unsort
        order = jnp.argsort(page_ids, stable=True)
        sp = page_ids[order]
        sa = arrival[order]
        seg_start = jnp.concatenate(
            [jnp.ones(1, bool), sp[1:] != sp[:-1]])
        # broadcast each page-segment's first arrival across the segment
        first_sorted = _segment_broadcast_first(sa, seg_start)
        key = jnp.zeros(n, jnp.int32).at[order].set(first_sorted)
    return jnp.argsort(key, stable=True).astype(jnp.int32)


def _segment_broadcast_first(vals: jnp.ndarray, seg_start: jnp.ndarray) -> jnp.ndarray:
    """For sorted segments, broadcast each segment's first value across it."""
    def combine(a, b):
        (va, sa_), (vb, sb) = a, b
        return jnp.where(sb, vb, va), sa_ | sb
    out, _ = jax.lax.associative_scan(combine, (vals, seg_start))
    return out


def group_offsets(page_ids_sorted: jnp.ndarray, num_pages: int) -> jnp.ndarray:
    """Start offset of each page group in a MARS-sorted stream (dense ids).

    Returns int32[num_pages + 1]; group g spans [offsets[g], offsets[g+1]).
    Computed without data-dependent shapes (cumsum of bincount).
    """
    counts = jnp.bincount(page_ids_sorted, length=num_pages)
    return jnp.concatenate(
        [jnp.zeros(1, jnp.int32), jnp.cumsum(counts).astype(jnp.int32)])


def inverse_permutation(perm: jnp.ndarray) -> jnp.ndarray:
    inv = jnp.zeros_like(perm)
    return inv.at[perm].set(jnp.arange(perm.shape[0], dtype=perm.dtype))


def mars_sort_by_page(page_ids: jnp.ndarray, num_pages: int):
    """One-stop helper for kernels: (perm, inv_perm, sorted_pages, offsets).

    Note: for *throughput* consumers (MoE dispatch) page order is
    irrelevant, so we sort by page id directly (cheaper key); the MARS
    first-arrival order matters for *latency* consumers (serving scheduler),
    which use ``mars_order``.
    """
    perm = jnp.argsort(page_ids, stable=True).astype(jnp.int32)
    sorted_pages = page_ids[perm]
    return perm, inverse_permutation(perm), sorted_pages, group_offsets(
        sorted_pages, num_pages)
