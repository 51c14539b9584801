"""Fixed-capacity block pool: a slab allocator over a preallocated KV buffer.

Bookkeeping mirrors the fixed-array style of the MARS engine
(``core.mars``): an occupancy bit-vector (``used``, the RequestQ
``rq_valid`` analogue), a refcount array, and first-arrival / last-use
ticks per block.  The physical KV storage is a pair of arrays of shape
``(n_layers, num_blocks, block_size, n_kv_heads, head_dim)`` allocated
once up front (host-resident, mutated in place; the engine stages them to
device per step) — block ids index directly into the paged-attention
kernel's ``k_pages``/``v_pages`` operands, so the allocator's placement
decisions *are* the kernel's gather addresses.

The leading **layer axis** makes one block id address a token-chunk's KV
for *every* model layer at once: a multi-layer LM (``kvcache.backend``)
keeps a single block table per sequence, and one placement decision
co-locates a token's per-layer blocks in the same DRAM row group — the
multi-layer rendering of MARS placement the single-layer engine of PR 1
could not express.

Blocks move through three states::

    free  --alloc-->  live (refcount >= 1)
    live  --decref(cache=True), refcount hits 0-->  cached (evictable)
    live  --decref(cache=False), refcount hits 0--> free
    cached --reuse--> live        cached --evict--> free

``content`` carries an opaque per-block payload tag (the token tuple the
block holds) used by prefix matching and by the soak tests to prove
copy-on-write never mutates a shared block.

A metadata-only pool (no KV buffer) is enough to watch the allocator
life-cycle:

>>> pool = BlockPool(PoolConfig(num_blocks=8, block_size=4))
>>> a = pool.alloc(2)
>>> pool.num_live, pool.num_free, pool.num_cached
(2, 6, 0)
>>> pool.decref(a[0])                 # free outright
>>> pool.decref(a[1], cache=True)     # retain as evictable prefix storage
>>> pool.num_live, pool.num_free, pool.num_cached
(0, 7, 1)
>>> pool.check_invariants()
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Iterable, Optional

import numpy as np

from repro.kvcache.evict import EvictionPolicy
from repro.kvcache.placement import PlacementPolicy, row_group_of
from repro.obs.metrics import StatGroup

# one block == one 4KB page of the DRAM model (64 x 64B lines)
LINES_PER_BLOCK = 64


def _np_dtype(name: str) -> np.dtype:
    """Resolve a dtype name, falling back to ml_dtypes for the low-precision
    names numpy lacks (bfloat16, float8_*) — available wherever jax is."""
    try:
        return np.dtype(name)
    except TypeError:
        import ml_dtypes
        return np.dtype(getattr(ml_dtypes, name))


@dataclasses.dataclass(frozen=True)
class PoolConfig:
    num_blocks: int = 256
    block_size: int = 16          # tokens per block
    blocks_per_group: int = 8     # DRAM row neighborhood = n_banks pages
    placement: str = "mars"       # "mars" | "naive"
    eviction: str = "fifo"        # "fifo" (PhyPageOrderQ) | "lru" | "cost"
    # KV buffer shape; None = metadata-only pool (simulation / tests)
    n_kv_heads: Optional[int] = None
    head_dim: Optional[int] = None
    n_layers: int = 1             # leading layer axis of the KV buffer
    dtype: str = "float32"
    # "kv": K and V buffers; "latent": one buffer of MLA rows (n_kv_heads
    # 1, head_dim the row width), shared by every head, and no V
    kv_kind: str = "kv"


class PoolStats(StatGroup):
    """Allocator counters, now an ``obs.metrics.StatGroup`` facade: the
    same attribute API the old dataclass had (``stats.allocs += n``),
    but the fields are live ``Counter`` objects a ``MetricsRegistry``
    adopts — the pool and the metrics snapshot share one copy of each
    number."""
    FIELDS = {"allocs": 0, "frees": 0, "evictions": 0, "cow_copies": 0,
              "prefix_hits": 0, "alloc_fails": 0}


class BlockPool:
    def __init__(self, cfg: PoolConfig):
        self.cfg = cfg
        n = cfg.num_blocks
        self.used = np.zeros(n, bool)            # occupancy bit-vector
        self.refcount = np.zeros(n, np.int32)
        self.arrival = np.zeros(n, np.int64)     # allocation tick
        self.last_use = np.zeros(n, np.int64)
        self.content: list[object] = [None] * n
        self._tick = 0
        self.placement = PlacementPolicy(n, cfg.blocks_per_group,
                                         cfg.placement)
        self.eviction = EvictionPolicy(cfg.eviction)
        # cached (refcount-0, still resident) blocks, insertion-ordered
        self._evictable: dict[int, None] = {}
        # prefix cache hook: called with a block id as it is evicted
        self.on_evict: Optional[Callable[[int], None]] = None
        # admission reservations (see reserve()): blocks promised to
        # admitted-but-not-yet-allocated work.  Held until the owning
        # request claims (allocates) or releases them — NOT dropped at
        # schedule time, otherwise lazily-allocated decode blocks would
        # over-commit the pool.
        self.reserved = 0
        self.stats = PoolStats()
        # telemetry (obs.Observer.attach): None = uninstrumented; events
        # carry obs_shard so sharded pools tag their shard index
        self.obs = None
        self.obs_shard = 0
        # blocks whose allocator state changed since the last incremental
        # invariant sweep (check_invariants(incremental=True)) — the
        # O(dirty) working set the --paranoid serve mode validates
        self._meta_dirty: set[int] = set()
        # KV payload: host-resident, mutated in place (a functional
        # .at[].set would copy the whole pool per token); staged to device
        # once per engine step when the kernel consumes it
        self.k_pages = self.v_pages = None
        # blocks whose payload changed since the last drain_dirty() —
        # lets a device mirror re-stage only what was written instead of
        # the whole pool every step (single consumer: whoever drains)
        self.dirty: set[int] = set()
        if cfg.n_kv_heads is not None and cfg.head_dim is not None:
            shape = (cfg.n_layers, n, cfg.block_size,
                     cfg.n_kv_heads, cfg.head_dim)
            self.k_pages = np.zeros(shape, _np_dtype(cfg.dtype))
            if cfg.kv_kind != "latent":
                self.v_pages = np.zeros(shape, _np_dtype(cfg.dtype))

    # -- capacity -----------------------------------------------------------

    @property
    def num_free(self) -> int:
        return self.placement.num_free

    @property
    def num_cached(self) -> int:
        return len(self._evictable)

    @property
    def num_live(self) -> int:
        return int(self.used.sum()) - self.num_cached

    def can_alloc(self, n: int) -> bool:
        """True iff ``alloc(n)`` would succeed right now (free blocks plus
        cached blocks reclaimable by eviction); ignores reservations —
        use ``can_reserve`` for admission decisions."""
        return self.num_free + self.num_cached >= n

    # -- admission reservations ---------------------------------------------

    def can_reserve(self, n: int) -> bool:
        """Admission capacity check: could ``n`` more blocks be promised
        on top of every outstanding reservation?  (free + cached −
        reserved ≥ n; cached counts because eviction reclaims it.)"""
        return self.num_free + self.num_cached - self.reserved >= n

    def reserve(self, n: int) -> None:
        """Promise ``n`` blocks to admitted-but-not-yet-allocated work.
        Reservations are bookkeeping only — they do not pin specific
        blocks; the holder converts them into real allocations over the
        sequence's lifetime and must ``unreserve`` the remainder."""
        self.reserved += n
        if self.obs is not None:
            self.obs.trace.event("pool.reserve", n=n, shard=self.obs_shard)

    def unreserve(self, n: int) -> None:
        """Release ``n`` previously reserved blocks (n ≤ reserved,
        asserted).  Invariant: 0 ≤ reserved ≤ num_blocks always holds."""
        assert n <= self.reserved, (n, self.reserved)
        self.reserved -= n
        if self.obs is not None:
            self.obs.trace.event("pool.unreserve", n=n,
                                 shard=self.obs_shard)

    # -- alloc / ref / free -------------------------------------------------

    def alloc(self, n: int = 1,
              hint_blocks: Iterable[int] = ()) -> list[int]:
        """Allocate ``n`` blocks at refcount 1.

        Args:
          n: block count; cached blocks are evicted (oldest-first per the
            eviction policy) when the free list is short.
          hint_blocks: blocks the requesting gang already holds — MARS
            placement packs the new blocks into (or next to) the DRAM row
            groups those occupy.
        Returns:
          the chosen block ids, placement-ordered.
        Raises:
          RuntimeError("pool exhausted ...") if free + cached < n; the
          pool is unchanged in that case (the check precedes eviction).
        """
        short = n - self.num_free
        if short > 0:
            if short > self.num_cached:
                self.stats.alloc_fails += 1
                if self.obs is not None:
                    self.obs.trace.event("pool.alloc_fail", n=n,
                                         shard=self.obs_shard)
                raise RuntimeError(
                    f"pool exhausted: want {n}, free {self.num_free}, "
                    f"cached {self.num_cached}")
            self._evict(short)
        hint_groups = self.placement.groups_of(list(hint_blocks))
        out = self.placement.choose(n, hint_groups)
        assert out is not None
        self._tick += 1
        for bid in out:
            self.used[bid] = True
            self.refcount[bid] = 1
            self.arrival[bid] = self._tick
            self.last_use[bid] = self._tick
            self.content[bid] = None
        self.stats.allocs += n
        self._meta_dirty.update(out)
        if self.obs is not None:
            self.obs.trace.event("pool.alloc", n=n, shard=self.obs_shard)
        return out

    def incref(self, bid: int) -> None:
        assert self.used[bid] and self.refcount[bid] > 0
        self.refcount[bid] += 1

    def decref(self, bid: int, cache: bool = False) -> None:
        """Drop one reference; at zero either retain as evictable prefix
        storage (``cache=True``) or free outright."""
        assert self.used[bid] and self.refcount[bid] > 0, bid
        self.refcount[bid] -= 1
        if self.refcount[bid] == 0:
            if cache:
                self._evictable[bid] = None
                self._meta_dirty.add(bid)
            else:
                self._free_block(bid)

    def reuse_cached(self, bid: int) -> None:
        """Revive a cached block (prefix hit): refcount 0 -> 1."""
        assert bid in self._evictable, bid
        del self._evictable[bid]
        self.refcount[bid] = 1
        self._tick += 1
        self.last_use[bid] = self._tick
        self.stats.prefix_hits += 1
        self._meta_dirty.add(bid)

    def touch(self, bid: int) -> None:
        self._tick += 1
        self.last_use[bid] = self._tick

    def _free_block(self, bid: int) -> None:
        self.used[bid] = False
        self.refcount[bid] = 0
        self.content[bid] = None
        # dirty-staging contract: a freed (evicted/demoted) id must not
        # linger in the dirty set — the single drain consumer would
        # re-scatter a dead slot's payload into the device mirror after
        # the slot is reused (demotion captures the pending payload
        # before this point; see kvcache.tiers.TierManager)
        self.dirty.discard(bid)
        self.placement.add_free(bid)
        self.stats.frees += 1
        self._meta_dirty.add(bid)

    def _evict(self, n: int) -> None:
        victims = self.eviction.select(self._evictable, self.arrival,
                                       self.last_use, n)
        for bid in victims:
            del self._evictable[bid]
            if self.on_evict is not None:
                self.on_evict(bid)
            self._free_block(bid)
            self.stats.evictions += 1
        if victims and self.obs is not None:
            self.obs.trace.event("pool.evict", n=len(victims),
                                 shard=self.obs_shard)

    # -- KV payload ---------------------------------------------------------

    def write_kv(self, bid: int, offset: int, k, v=None) -> None:
        """Write ``t`` token KV rows into a block at ``offset``, for every
        layer plane at once, and mark the block dirty for staging.

        Args:
          bid: destination block (must be live; offset + t ≤ block_size,
            asserted).
          offset: first token slot written within the block.
          k, v: (n_layers, t, n_kv_heads, head_dim) arrays; a layerless
            (t, n_kv_heads, head_dim) is accepted when the pool has a
            single layer plane (the PR-1 single-layer engine path).  A
            latent pool takes its rows as ``k`` and ignores ``v``.
        """
        k = np.asarray(k)
        if k.ndim == 3:
            assert self.cfg.n_layers == 1, "layered pool needs layered KV"
            k = k[None]
        t = k.shape[1]
        assert offset + t <= self.cfg.block_size
        self.k_pages[:, bid, offset:offset + t] = k
        if self.v_pages is not None:
            v = np.asarray(v)
            self.v_pages[:, bid, offset:offset + t] = \
                v[None] if v.ndim == 3 else v
        self.dirty.add(bid)

    def copy_block(self, src: int, dst: int) -> None:
        """Copy-on-write payload copy (content tag + all layer planes)."""
        self.content[dst] = self.content[src]
        if self.k_pages is not None:
            self.k_pages[:, dst] = self.k_pages[:, src]
            if self.v_pages is not None:
                self.v_pages[:, dst] = self.v_pages[:, src]
            self.dirty.add(dst)
        self.stats.cow_copies += 1
        if self.obs is not None:
            self.obs.trace.event("pool.cow", src=src, dst=dst,
                                 shard=self.obs_shard)

    def forget_dirty(self, bid: int) -> None:
        """Drop a block from the dirty-staging set without draining.

        For owners that invalidate a block's pending payload out of band
        (e.g. ``kvcache.tiers.TierManager`` capturing a demoted block's
        KV before the slot is reused) — everyone else goes through
        ``write_kv``/``copy_block``/``drain_dirty`` and must never touch
        ``dirty`` directly (enforced by ``tools/lint.py``,
        rule ``pool-kv-mutation``).
        """
        self.dirty.discard(bid)

    def drain_dirty(self) -> list[int]:
        """Block ids whose payload changed since the last drain (sorted),
        clearing the set.

        This is the dirty-block staging contract: the pool mutates its KV
        buffers host-side in place (``write_kv``/``copy_block`` add to
        ``dirty``); a **single consumer** — the owning backend's device
        mirror — drains the set once per decode step and re-uploads
        exactly those blocks instead of the whole pool.  Two consumers
        would each see only a partial dirty stream and serve stale pages,
        which is why a pool belongs to one backend (and, mesh-sharded,
        each shard's pool to that shard's backend/mirror/device).
        """
        out = sorted(self.dirty)
        self.dirty.clear()
        if out and self.obs is not None:
            self.obs.trace.event("pool.drain_dirty", n=len(out),
                                 shard=self.obs_shard)
        return out

    # -- invariants ---------------------------------------------------------

    def check_invariants(self, incremental: bool = False) -> None:
        """Allocator ground truth.

        ``incremental=False`` is the exhaustive O(num_blocks) sweep the
        tests run.  ``incremental=True`` validates only the blocks whose
        allocator state changed since the previous incremental sweep
        (``_meta_dirty`` — O(dirty), typically a handful of blocks per
        engine step) plus O(1) aggregate counts, cheap enough for the
        serving loop to run every N steps (``--metrics --paranoid``).
        Both modes raise AssertionError on the first violation.
        """
        if incremental:
            self._check_incremental()
            return
        free = self.placement.free_ids()
        assert len(free) == len(set(free)), "free list holds duplicates"
        free_set = set(free)
        group_union = set().union(*self.placement._group_free) \
            if self.placement._group_free else set()
        assert free_set == group_union, "stack / group free sets diverged"
        for bid in range(self.cfg.num_blocks):
            if bid in free_set:
                assert not self.used[bid], f"block {bid} free AND used"
                assert self.refcount[bid] == 0
            else:
                assert self.used[bid], f"block {bid} leaked (not free, not used)"
        cached = set(self._evictable)
        for bid in cached:
            assert self.used[bid] and self.refcount[bid] == 0
        live = [b for b in range(self.cfg.num_blocks)
                if self.used[b] and b not in cached]
        for bid in live:
            assert self.refcount[bid] > 0, f"live block {bid} has refcount 0"
        assert len(free_set) + len(cached) + len(live) == self.cfg.num_blocks
        assert 0 <= self.reserved <= self.cfg.num_blocks
        self._meta_dirty.clear()   # full sweep subsumes the pending one

    def _check_incremental(self) -> None:
        """O(dirty) slice of the invariant sweep: aggregate accounting
        plus per-block state for every block touched since the last
        sweep.  Free-set membership is O(1) via the placement policy's
        per-row-group free sets (kept in lockstep with the stack)."""
        n = self.cfg.num_blocks
        n_used = int(self.used.sum())
        assert self.num_free + n_used == n, \
            (self.num_free, n_used, "free/used partition lost blocks")
        assert self.num_cached <= n_used, (self.num_cached, n_used)
        assert 0 <= self.reserved <= n, self.reserved
        bpg = self.placement.blocks_per_group
        for bid in self._meta_dirty:
            in_free = bid in \
                self.placement._group_free[row_group_of(bid, bpg)]
            if in_free:
                assert not self.used[bid], f"block {bid} free AND used"
                assert self.refcount[bid] == 0, bid
            else:
                assert self.used[bid], \
                    f"block {bid} leaked (not free, not used)"
                if bid in self._evictable:
                    assert self.refcount[bid] == 0, bid
                else:
                    assert self.refcount[bid] > 0, \
                        f"live block {bid} has refcount 0"
        self._meta_dirty.clear()
