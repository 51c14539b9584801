"""Tiered KV memory: demote-on-evict / promote-on-miss round-trips,
MARS-reordered promotion batches, cost-aware eviction, the evict-while-
dirty staging regression (plain + sharded), tier-probe shard routing,
obs wiring, and end-to-end tiered serving parity under forced spill."""
import dataclasses

import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:          # property test skips below; the rest collects
    given = settings = st = None

from repro.kernels.paged_attention.paged_attention import fold_pages
from repro.kvcache import BlockPool, BlockTable, PoolConfig, PrefixCache, \
    ShardedBlockPool, TierManager, TierSpec, row_group_of
from repro.kvcache.evict import EvictionPolicy
from repro.kvcache.tiers import promotion_order
from repro.serving.scheduler import MarsScheduler, Request


def _tiered_pool(num_blocks=8, block_size=4, specs=None, *, kv=True, **kw):
    """(pool, cache, tiers) with KV buffers unless ``kv=False``."""
    cfg = PoolConfig(num_blocks=num_blocks, block_size=block_size,
                     **(dict(n_kv_heads=1, head_dim=2) if kv else {}), **kw)
    pool = BlockPool(cfg)
    cache = PrefixCache(block_size)
    cache.attach(pool)
    return pool, cache, TierManager(pool, cache, specs)


def _seq(pool, cache, tokens, kv=None):
    """Prefill a sequence's block table, registering full blocks."""
    t = BlockTable()
    t.extend(pool, tokens, seq_tokens=tokens, cache=cache, kv=kv)
    return t


# ---------------------------------------------------------------------------
# demotion
# ---------------------------------------------------------------------------

def test_demote_on_evict_captures_payload():
    pool, cache, tiers = _tiered_pool(num_blocks=4)
    t = _seq(pool, cache, [1, 2, 3, 4, 5, 6, 7, 8])
    blk = np.full((pool.cfg.n_layers, pool.cfg.block_size,
                   pool.cfg.n_kv_heads, pool.cfg.head_dim), 7.5)
    pool.write_kv(t.blocks[0], 0, blk, blk)     # payload + pending staging
    k0 = np.array(pool.k_pages[:, t.blocks[0]])
    bid0 = t.blocks[0]
    cache.release(t, pool)
    grab = pool.alloc(4)                        # pressure: demote both
    assert tiers.stats.demotes == 2
    assert pool.num_cached == 0
    # an evicted id must not linger in the dirty set (satellite bugfix:
    # the drain consumer would re-scatter a dead slot after reuse)
    assert bid0 not in pool.dirty
    e = tiers.tiers[0].get((1, 2, 3, 4))
    assert e is not None and e.content == (1, 2, 3, 4)
    np.testing.assert_array_equal(e.k, k0)      # freshest payload captured
    assert tiers.tiers[0].holds((1, 2, 3, 4, 5, 6, 7, 8))
    for b in grab:
        pool.decref(b)
    tiers.check()
    pool.check_invariants()


def test_unregistered_blocks_evict_without_demotion():
    pool, cache, tiers = _tiered_pool(num_blocks=4)
    t = BlockTable()
    t.extend(pool, [1, 2, 3], seq_tokens=[1, 2, 3])    # no cache: private
    for b in t.blocks:
        pool.decref(b, cache=True)
    pool.alloc(4)
    assert tiers.stats.demotes == 0 and len(tiers.tiers[0]) == 0


def test_tier_overflow_cascades_then_drops():
    specs = (TierSpec("host", 2), TierSpec("remote", 2))
    pool, cache, tiers = _tiered_pool(num_blocks=4, specs=specs)
    for i in range(6):
        t = _seq(pool, cache, [10 * i + 1, 10 * i + 2, 10 * i + 3,
                               10 * i + 4, 99])
        cache.release(t, pool)
        grab = pool.alloc(pool.num_free + pool.num_cached)
        for b in grab:
            pool.decref(b)
    assert tiers.stats.demotes == 6
    assert len(tiers.tiers[0]) == 2 and len(tiers.tiers[1]) == 2
    assert tiers.stats.drops == 2               # oldest fell off the end
    # newest demotions sit in the top tier, next-newest below
    assert tiers.tiers[0].holds((51, 52, 53, 54))
    assert tiers.tiers[1].holds((31, 32, 33, 34))
    tiers.check()


# ---------------------------------------------------------------------------
# promotion
# ---------------------------------------------------------------------------

def test_promote_on_miss_is_bitwise_roundtrip():
    from repro.analysis import refsan

    pool, cache, tiers = _tiered_pool(num_blocks=6)
    san = refsan.attach(pool)           # demote/promote path under sanitizer
    tokens = [1, 2, 3, 4, 5, 6, 7, 8, 9]
    rng = np.random.default_rng(0)
    kv = (rng.standard_normal((1, 9, 1, 2), np.float32),
          rng.standard_normal((1, 9, 1, 2), np.float32))
    t = _seq(pool, cache, tokens, kv=kv)
    k_before = np.array(pool.k_pages[:, t.blocks[:2]])
    v_before = np.array(pool.v_pages[:, t.blocks[:2]])
    cache.release(t, pool)
    grab = pool.alloc(6)                        # demote the two full blocks
    for b in grab:
        pool.decref(b)
    assert tiers.stats.demotes == 2
    bids, n = tiers.match(tokens)
    assert n == 8 and len(bids) == 2            # both promoted from tier
    assert tiers.pending == 2
    dsts = tiers.flush_promotions()
    assert sorted(dsts) == sorted(bids)
    np.testing.assert_array_equal(pool.k_pages[:, bids], k_before)
    np.testing.assert_array_equal(pool.v_pages[:, bids], v_before)
    # promoted blocks are dirty (the staged mirror re-uploads them) and
    # re-registered (a second match hits the pool, not the tier)
    assert set(bids) <= pool.dirty
    assert cache.is_registered(bids[0]) and cache.is_registered(bids[1])
    promotes = tiers.stats.promotes
    bids2, n2 = tiers.match(tokens)
    assert n2 == 8 and tiers.pending == 0 and tiers.stats.promotes == promotes
    assert tiers.stats.promoted_tokens == 8
    tiers.check()
    pool.check_invariants()
    san.check()                         # no double-frees / UAF on the path
    san.detach()


def test_promotion_dedup_within_one_batch():
    pool, cache, tiers = _tiered_pool(num_blocks=6)
    t = _seq(pool, cache, [1, 2, 3, 4, 5])
    cache.release(t, pool)
    grab = pool.alloc(6)
    for b in grab:
        pool.decref(b)
    bids_a, na = tiers.match([1, 2, 3, 4, 6])
    bids_b, nb = tiers.match([1, 2, 3, 4, 7])   # same pending key
    assert na == nb == 4 and bids_a == bids_b
    assert tiers.pending == 1, "second row must reference, not re-promote"
    assert pool.refcount[bids_a[0]] == 2
    tiers.flush_promotions()
    assert tiers.stats.promotes == 1
    tiers.check()


def test_inclusive_tier_makes_reeviction_a_clean_drop():
    pool, cache, tiers = _tiered_pool(num_blocks=4)
    t = _seq(pool, cache, [1, 2, 3, 4, 5])
    cache.release(t, pool)
    grab = pool.alloc(4)
    for b in grab:
        pool.decref(b)
    bids, _ = tiers.match([1, 2, 3, 4, 9])
    tiers.flush_promotions()
    pool.decref(bids[0], cache=True)            # release the promoted block
    demotes = tiers.stats.demotes
    pool.alloc(4)                               # evict it again
    assert tiers.stats.demotes == demotes, "tier copy was clean"
    assert tiers.stats.clean_drops == 1
    assert tiers.tiers[0].holds((1, 2, 3, 4))   # entry survived
    tiers.check()


def test_match_stops_cleanly_on_pool_exhaustion():
    pool, cache, tiers = _tiered_pool(num_blocks=4)
    t = _seq(pool, cache, list(range(1, 17)))   # 4 full blocks
    cache.release(t, pool)
    grab = pool.alloc(4)                        # demote all four
    assert tiers.stats.demotes == 4
    # keep 3 blocks held: only 1 destination available for 4 promotions
    pool.decref(grab[0])
    bids, n = tiers.match(list(range(1, 17)) + [99])
    assert n == 4 and len(bids) == 1 and tiers.pending == 1
    tiers.flush_promotions()
    tiers.check()
    pool.check_invariants()


def test_cancel_promotions_rolls_back_clean():
    pool, cache, tiers = _tiered_pool(num_blocks=6)
    t = _seq(pool, cache, [1, 2, 3, 4, 5])
    cache.release(t, pool)
    grab = pool.alloc(6)
    for b in grab:
        pool.decref(b)
    bids, n = tiers.match([1, 2, 3, 4, 6])
    assert tiers.pending == 1
    tiers.cancel_promotions()                   # rollback path
    assert tiers.pending == 0
    pool.decref(bids[0])                        # caller's table rollback
    assert tiers.tiers[0].holds((1, 2, 3, 4)), "tier entry never removed"
    # the same prefix still promotes on the next (successful) batch
    bids2, n2 = tiers.match([1, 2, 3, 4, 7])
    assert n2 == 4
    tiers.flush_promotions()
    tiers.check()
    pool.check_invariants()


# ---------------------------------------------------------------------------
# MARS promotion reorder
# ---------------------------------------------------------------------------

def test_promotion_order_matches_core_mars_order():
    """``promotion_order`` must be the numpy rendering of the jax
    ``core.reorder.mars_order`` (first-arrival row groups, FIFO within)."""
    from repro.core.reorder import mars_order
    rng = np.random.default_rng(1)
    for n in (1, 7, 32):
        groups = rng.integers(0, 5, n)
        want = list(np.asarray(mars_order(groups, num_pages=5, window=n)))
        assert promotion_order([int(g) for g in groups]) == want


def test_flush_groups_by_destination_row_group():
    pool, cache, tiers = _tiered_pool(num_blocks=32, block_size=2,
                                      kv=False)
    prompts = []
    for i in range(8):
        p = [100 * i + 1, 100 * i + 2, 9]
        t = _seq(pool, cache, p)
        cache.release(t, pool)
        prompts.append(p)
    grab = pool.alloc(pool.num_free + pool.num_cached)
    for b in grab:
        pool.decref(b)
    # scatter the free list so destinations interleave row groups
    grab = pool.alloc(32)
    rng = np.random.default_rng(2)
    for i in rng.permutation(32)[:16]:
        pool.decref(grab[i])
    for p in prompts:
        tiers.match(p)
    dsts = tiers.flush_promotions()
    bpg = pool.cfg.blocks_per_group
    groups = [row_group_of(d, bpg) for d in dsts]
    # copy order visits each destination row group exactly once
    switches = sum(1 for a, b in zip(groups, groups[1:]) if a != b)
    assert switches == len(set(groups)) - 1, \
        f"promotion batch not group-coherent: {groups}"
    tiers.check()


def test_write_trace_interleaves_bounded_queue():
    from repro.kvcache.pool import LINES_PER_BLOCK
    tr = TierManager.write_trace([3, 9], chunk_lines=8, queue_depth=4)
    assert len(tr) == 2 * LINES_PER_BLOCK
    # both descriptors in flight: chunks alternate between the blocks
    assert tr[0] == 3 * LINES_PER_BLOCK
    assert tr[8] == 9 * LINES_PER_BLOCK
    assert tr[16] == 3 * LINES_PER_BLOCK + 8
    assert len(np.unique(tr)) == len(tr)
    assert len(TierManager.write_trace([])) == 0


# ---------------------------------------------------------------------------
# cost-aware eviction
# ---------------------------------------------------------------------------

def test_cost_policy_requires_mode_and_hook():
    with pytest.raises(ValueError, match="unknown eviction mode"):
        EvictionPolicy("bogus")
    pool, cache, tiers = _tiered_pool(num_blocks=4, eviction="cost")
    assert pool.eviction.cost_fn == tiers.evict_cost


def test_cost_eviction_beats_lru_on_recurring_deep_prefixes():
    """Recurring deep prefix chains + a sliding shallow window over a
    pool (and tier) below the working set: cost mode ranks victims by
    re-acquisition cost and protects the chains LRU throws away.  Reuses
    the deterministic bench workload so the gated bench row and this
    test can only move together."""
    from benchmarks.kvcache_bench import tiered_eviction_comparison
    out = tiered_eviction_comparison(rounds=12)
    assert out["cost"]["reuse"] > out["lru"]["reuse"] + 0.2, out
    assert out["cost"]["recompute_tokens"] < out["lru"]["recompute_tokens"]
    assert out["cost"]["drops"] < out["lru"]["drops"]


def test_evict_cost_tiers_full_scales_with_depth():
    specs = (TierSpec("host", 1),)
    pool, cache, tiers = _tiered_pool(num_blocks=8, specs=specs)
    t = _seq(pool, cache, list(range(1, 9)) + [99])
    shallow_bid, deep_bid = t.blocks[0], t.blocks[1]
    assert tiers.evict_cost(t.blocks[2]) == 0.0       # unregistered tail
    fetch = tiers.evict_cost(shallow_bid)
    assert 0 < fetch < 100                            # refetchable: cheap
    # fill the tier: costs switch to causal recompute, deeper = dearer
    t2 = _seq(pool, cache, [301, 302, 303, 304, 99])
    cache.release(t2, pool)
    pool.alloc(pool.num_free + 1)               # evict + demote t2's block
    assert len(tiers.tiers[0]) == 1
    c_shallow = tiers.evict_cost(shallow_bid)
    c_deep = tiers.evict_cost(deep_bid)
    assert c_deep > c_shallow > fetch
    cache.release(t, pool)


# ---------------------------------------------------------------------------
# evict-while-dirty staging regression (plain + sharded backends)
# ---------------------------------------------------------------------------

def _model(arch="qwen1_5_0_5b"):
    import jax
    from repro import configs
    from repro.models import lm
    cfg = configs.get_smoke(arch)
    return cfg, lm.init(cfg, jax.random.key(0)).params


def test_evicted_dirty_block_never_restaged_plain():
    """A block evicted while still in ``pool.dirty`` must not be
    re-scattered into the staged device mirror after its slot is reused
    — the mirror must converge to the host pool regardless."""
    from repro.kvcache.backend import PagedBackend
    cfg, params = _model()
    backend = PagedBackend(cfg, num_blocks=8, block_size=4,
                           decode_mode="gather", tiered=True)
    pool = backend.pool
    sid, _, _ = backend.new_seq(params, list(range(1, 10)))   # 3 blocks
    # blocks are dirty (never decoded -> never drained) when the free
    # below evicts them under the next prefill's pressure
    assert len(pool.dirty) > 0
    backend.free_seq(sid)
    sid2, _, _ = backend.new_seq(params, list(range(20, 48)))  # 7 blocks
    assert backend.tiers.stats.demotes > 0
    assert all(pool.used[b] for b in pool.dirty), \
        "freed block id lingering in pool.dirty"
    backend.decode(params, [sid2], [3])
    backend._staged_pages()                     # drain the decode's tail
    np.testing.assert_array_equal(np.asarray(backend._k_dev),
                                  fold_pages(pool.k_pages))
    backend.release()
    pool.check_invariants()


def test_evicted_dirty_block_never_restaged_sharded():
    from repro.kvcache.backend import ShardedPagedBackend
    cfg, params = _model()
    backend = ShardedPagedBackend(cfg, n_shards=2, num_blocks=16,
                                  block_size=4, decode_mode="gather",
                                  tiered=True)
    sid, _, _ = backend.new_seq(params, list(range(1, 10)), shard=0)
    p0 = backend.pool.shards[0]
    assert len(p0.dirty) > 0
    backend.free_seq(sid)
    sid2, _, _ = backend.new_seq(params, list(range(20, 48)), shard=0)
    assert backend.backends[0].tiers.stats.demotes > 0
    for p in backend.pool.shards:
        assert all(p.used[b] for b in p.dirty), \
            "freed block id lingering in a shard's dirty set"
    backend.decode(params, [sid2], [3])
    backend.backends[0]._staged_pages()         # drain the decode's tail
    np.testing.assert_array_equal(np.asarray(backend.backends[0]._k_dev),
                                  fold_pages(p0.k_pages))
    backend.release()
    backend.pool.check_invariants()


def test_backend_rollback_cancels_pending_promotions():
    """Prefill exhaustion with promotions queued must cancel the queue
    and leave pool + tiers consistent (nothing flushed into freed
    slots)."""
    from repro.kvcache.backend import PagedBackend
    cfg, params = _model()
    backend = PagedBackend(cfg, num_blocks=8, block_size=4,
                           decode_mode="gather", tiered=True)
    pool, tiers = backend.pool, backend.tiers
    sid, _, _ = backend.new_seq(params, list(range(1, 10)))
    backend.free_seq(sid)
    grab = pool.alloc(pool.num_free + pool.num_cached)   # demote prefix
    assert tiers.stats.demotes > 0
    for b in grab[:-6]:                                  # leave 2 free
        pool.decref(b)
    # prompt re-promotes 2 blocks then exhausts mid-prefill
    with pytest.raises(RuntimeError, match="pool exhausted"):
        backend.new_seq(params, list(range(1, 10)) + list(range(50, 80)))
    assert tiers.pending == 0, "rollback left promotions queued"
    tiers.check()
    pool.check_invariants()
    backend.release()


# ---------------------------------------------------------------------------
# sharded routing (tier probe)
# ---------------------------------------------------------------------------

def test_route_prefers_tier_hint_over_load():
    sp = ShardedBlockPool(PoolConfig(num_blocks=16, block_size=4),
                          n_shards=2)
    sp.reserve(2)
    # least-loaded would pick shard 0; the tier hint overrides
    assert sp.route(rid=0, page="a", n=2, tier_hint=1) == 1
    # a full hint shard falls back to load routing
    sp.reserve(8)
    assert sp.route(rid=1, page="b", n=8, tier_hint=1) == 0
    sp.unreserve(2, rid=0)
    sp.unreserve(8, rid=1)
    sp.check_invariants()


def test_tier_shard_for_and_scheduler_probe():
    from repro.kvcache.backend import ShardedPagedBackend
    cfg, params = _model()
    backend = ShardedPagedBackend(cfg, n_shards=2, num_blocks=16,
                                  block_size=4, decode_mode="gather",
                                  tiered=True)
    assert backend.tiered
    prompt = list(range(1, 10))
    sid, _, _ = backend.new_seq(params, prompt, shard=1)
    backend.free_seq(sid)
    p1 = backend.pool.shards[1]
    grab = p1.alloc(p1.num_free + p1.num_cached)         # demote on shard 1
    for b in grab:
        p1.decref(b)
    assert backend.backends[1].tiers.stats.demotes > 0
    assert backend.tier_shard_for(prompt) == 1
    assert backend.tier_shard_for(list(range(900, 920))) is None
    # the scheduler's probe routes the request to the holding shard
    sched = MarsScheduler(pool=backend.pool)
    sched.tier_probe = backend.tier_shard_for
    assert sched.offer(Request(rid=7, prompt=tuple(prompt), prefix_len=4,
                               max_new=2))
    batch = sched.schedule_batch(4)
    assert len(batch) == 1 and batch[0]._shard == 1
    backend.pool.unreserve(batch[0].blocks_needed(4), rid=7)
    backend.release()


# ---------------------------------------------------------------------------
# end-to-end tiered serving parity under forced spill
# ---------------------------------------------------------------------------

def _spill_requests(cfg, n=18, n_prefixes=6, prefix_len=8, max_new=3):
    rng = np.random.default_rng(5)
    prefixes = [tuple(int(t) for t in rng.integers(1, cfg.vocab, prefix_len))
                for _ in range(n_prefixes)]
    reqs = []
    for i in range(n):
        p = prefixes[i % n_prefixes]
        tail = tuple(int(t) for t in rng.integers(1, cfg.vocab, 2))
        reqs.append(Request(rid=i, prompt=p + tail, arrival=i * 1e-3,
                            prefix_len=prefix_len, max_new=max_new))
    return reqs


@pytest.mark.parametrize("shards", [1, 2])
def test_tiered_serving_token_parity_under_spill(shards):
    """Dense-vs-paged token parity must survive tiering: a pool too
    small for the prefix working set spills and re-promotes mid-serve,
    and every request's tokens still match the dense greedy path."""
    import jax.numpy as jnp
    from repro.kvcache.backend import PagedBackend, ShardedPagedBackend
    from repro.serve.engine import PagedLM, ServeEngine
    from repro.serve.step import greedy_generate

    cfg, params = _model()
    if shards == 1:
        backend = PagedBackend(cfg, num_blocks=10, block_size=4,
                               decode_mode="gather", tiered=True)
        managers = [backend.tiers]
    else:
        backend = ShardedPagedBackend(cfg, n_shards=2, num_blocks=20,
                                      block_size=4, decode_mode="gather",
                                      tiered=True)
        managers = [b.tiers for b in backend.backends]
    sched = MarsScheduler(pool=backend.pool)
    if shards > 1:
        sched.tier_probe = backend.tier_shard_for
    eng = ServeEngine(backend.pool, sched, PagedLM(params, cfg, backend),
                      max_lanes=3)
    reqs = _spill_requests(cfg)
    out = eng.run(reqs)
    assert sorted(out) == list(range(len(reqs)))
    assert sum(t.stats.demotes for t in managers) > 0, "never spilled"
    assert sum(t.stats.promotes for t in managers) > 0, "never promoted"
    for t in managers:
        t.check()
    backend.pool.check_invariants()
    for req in reqs:
        want = greedy_generate(params, cfg,
                               jnp.asarray([req.prompt], jnp.int32),
                               req.max_new,
                               max_seq=len(req.prompt) + req.max_new + 1)
        assert out[req.rid][0] == list(np.asarray(want[0])), \
            f"rid {req.rid} diverged under tiered spill"


# ---------------------------------------------------------------------------
# observability
# ---------------------------------------------------------------------------

def test_observer_adopts_tier_stats_and_orders_events():
    from repro.kvcache.backend import PagedBackend
    from repro.obs import Observer
    from repro.serve.engine import PagedLM, ServeEngine

    cfg, params = _model()
    backend = PagedBackend(cfg, num_blocks=10, block_size=4,
                           decode_mode="gather", tiered=True)
    sched = MarsScheduler(pool=backend.pool)
    eng = ServeEngine(backend.pool, sched, PagedLM(params, cfg, backend),
                      max_lanes=3)
    obs = Observer(paranoid=True, paranoid_every=2).attach(eng)
    assert backend.tiers.obs is obs
    snap0 = obs.registry.snapshot()
    assert "tier.shard0.host.occupancy" in snap0["gauges"]
    eng.run(_spill_requests(cfg, n=12))
    assert backend.tiers.stats.demotes > 0
    snap = obs.registry.snapshot()
    assert snap["counters"]["tier.shard0.demotes"] \
        == backend.tiers.stats.demotes
    assert snap["counters"]["tier.shard0.promotes"] \
        == backend.tiers.stats.promotes
    assert 0.0 <= snap["gauges"]["tier.shard0.host.occupancy"] <= 1.0
    assert 0.0 <= snap["gauges"]["tier.promote_row_hit_pct"] <= 100.0
    # demote -> promote -> decode, per key, in the trace
    evs = list(obs.trace.events())
    demoted = {}
    saw_promote = False
    for e in evs:
        if e["ev"] == "tier.demote":
            demoted.setdefault(e["key"], e["ts"])
        elif e["ev"] == "tier.promote":
            saw_promote = True
            assert e["key"] in demoted and demoted[e["key"]] <= e["ts"]
    assert saw_promote
    first_promote = min(e["ts"] for e in evs if e["ev"] == "tier.promote")
    assert any(e["ev"] == "backend.decode" and e["ts"] >= first_promote
               for e in evs)
    backend.release()


# ---------------------------------------------------------------------------
# property: demote -> promote bitwise round-trip under interleaved
# sharing / CoW forks / eviction pressure
# ---------------------------------------------------------------------------

if st is not None:
    @settings(max_examples=12, deadline=None)
    @given(st.sampled_from(["float32", "bfloat16"]),   # KV dtype
           st.integers(2, 5),                          # block_size
           st.integers(1, 2),                          # kv heads
           st.integers(1, 3),                          # head_dim
           st.integers(2, 3),                          # layered pool depth
           st.integers(0, 10_000))                     # workload seed
    def test_tier_roundtrip_property(dtype, bs, hkv, dh, layers, seed):
        """Every promoted block's KV must be bitwise what was demoted,
        across dtypes and page shapes, while prompts share prefixes,
        fork CoW tails, and eviction pressure churns the pool —
        ``check_invariants`` + ``tiers.check`` clean after every round."""
        rng = np.random.default_rng(seed)
        pool = BlockPool(PoolConfig(num_blocks=8, block_size=bs,
                                    n_kv_heads=hkv, head_dim=dh,
                                    n_layers=layers, dtype=dtype))
        cache = PrefixCache(bs)
        cache.attach(pool)
        tiers = TierManager(pool, cache,
                            (TierSpec("host", 4), TierSpec("remote", 8)))
        from repro.analysis import refsan
        san = refsan.attach(pool)
        prompts = [[int(t) for t in rng.integers(1, 50, 2 * bs + 1)]
                   for _ in range(3)]
        prompts.append(list(prompts[0][:bs]) + [77])   # shared prefix
        golden: dict = {}                              # key -> (k, v)
        for _ in range(4):
            for p in prompts:
                bids, n = tiers.match(p)
                tiers.flush_promotions()       # payload lands before reads
                for j, bid in enumerate(bids):
                    key = tuple(p[:(j + 1) * bs])
                    if key in golden:                  # bitwise survival
                        np.testing.assert_array_equal(
                            pool.k_pages[:, bid], golden[key][0])
                        np.testing.assert_array_equal(
                            pool.v_pages[:, bid], golden[key][1])
                table = BlockTable(list(bids), n)
                kv = (rng.standard_normal(
                          (layers, len(p) - n, hkv, dh)).astype(dtype),
                      rng.standard_normal(
                          (layers, len(p) - n, hkv, dh)).astype(dtype))
                table.extend(pool, p[n:], seq_tokens=p, cache=cache, kv=kv)
                for j, bid in enumerate(table.blocks[:len(p) // bs]):
                    key = tuple(p[:(j + 1) * bs])
                    golden.setdefault(key,
                                      (np.array(pool.k_pages[:, bid]),
                                       np.array(pool.v_pages[:, bid])))
                if rng.random() < 0.4:                 # CoW fork churn
                    fork = table.fork(pool)
                    fork.extend(pool, [7], seq_tokens=p + [7])
                    for b in fork.blocks:
                        pool.decref(b)
                cache.release(table, pool)
                pool.check_invariants()
                tiers.check()
            # eviction pressure between rounds
            n_grab = rng.integers(1, pool.num_free + pool.num_cached + 1)
            grab = pool.alloc(int(n_grab))
            for b in grab:
                pool.decref(b)
            pool.check_invariants()
            tiers.check()
        san.check()
        san.detach()
else:
    def test_tier_roundtrip_property():
        pytest.importorskip("hypothesis")
