"""KV-cache placement benchmark: MARS-aware vs naive block placement.

Serving workload through the paper's DRAM model: a pool is churned by
arriving/finishing sequences until fragmented, then a decode batch's full
KV gather (``kernels.paged_attention.ops.kv_read_trace`` — per-lane block
reads interleaved by the parallel gather) is served by
``core.dram.simulate``.  MARS placement packs each sequence's blocks into
few DRAM row neighborhoods, so the interleaved lanes land in distinct
banks instead of thrashing rows; the naive LIFO free list scatters blocks
after churn.

Emits ``kvcache/<placement>/...`` rows plus the headline uplift, and the
same traces after a bounded-window ``reorder.mars_order`` pass (the MC-side
MARS reorder buffer) to show placement and reordering compose.

Decode-path section (``kvcache/decode/...``): the same fragmented pool
read two ways — the gather path's round-robin lane interleave
(``ops.kv_read_trace``) vs the Pallas kernel's sequence-major page walk
(``ops.kv_read_trace_kernel``) — through ``core.dram.simulate``, reporting
bandwidth and row-buffer hit rate.  The kernel path never interleaves
lanes, so its hit rate bounds the gather path's from above; this is the
bandwidth MARS placement actually delivers to the attention kernel.

Sharded section (``kvcache/placement/sharded/...``): the same churn
schedule run through a mesh-sharded pool (``sharded_placement_comparison``)
— sequences routed to the least-loaded shard, each shard's decode lanes
traced and replayed through ``core/dram.simulate`` as its *own* memory
device.  Per-device interleave is shallower than the single pool's, so
shard-routed MARS row-hit bounds single-pool MARS which bounds naive;
aggregate bandwidth sums across devices (the scale-out half).

Eviction section (ROADMAP "online eviction tuning"): a skewed-prefix
workload — request popularity Zipf-distributed over prompt prefixes —
drives the prefix cache under memory pressure and reports the FIFO
(PhyPageOrderQ first-arrival) vs LRU hit rates side by side.  FIFO evicts
hot prefixes simply because they are old; LRU keeps them resident, so its
hit rate should pull ahead as the skew sharpens.

Tier section (``kvcache/tier/...``): the tiered KV memory layer
(``kvcache.tiers``) at the tier boundary.  ``tiered_promotion_comparison``
replays the *write* stream of one batched promotion copy-in through
``core/dram.simulate`` twice — MARS-reordered by destination row group vs
naive arrival order over the identical scattered destination set — the
paper's source-side reorder applied to inter-tier traffic.
``tiered_eviction_comparison`` runs the same deep/shallow prefix stream
under cost-aware vs LRU eviction: cost mode spends evictions on blocks
that are cheap to re-acquire (clean tier copy, shallow recompute) and
keeps deep chains resident, so its token reuse pulls ahead and its
recompute bill drops.

Allocator soak section (``kvcache/alloc/...``): multi-round Zipf-sized
alloc/free churn over ``BlockPool`` and ``ShardedBlockPool`` — long-run
fragmentation (mean free-run length, live-table row-group locality) plus
per-alloc wall latency in the us column.

Decode-pipeline section (``kvcache/decode/pipeline/...``): wall-clock
A/B of the split-phase backend lifecycle (``flush -> dispatch_decode ->
sync``; KV write-back one step deferred, mirrors double-buffered)
against the synchronous ``decode()`` wrapper, twin real-LM backends
serving identical ragged lanes in single-pool, 2-shard, and tiered
configurations.  Decode runs a genuinely compiled path — the Pallas
kernel on a TPU, else the jitted XLA gather decode (CPU Pallas only
runs interpreted, which is not a wall-clock measurement).  Greedy
tokens must be bit-identical; the derived column is 100 * t_sequential
/ t_pipelined (>= 100: the pipeline at least matches sequential step
throughput).

Traffic-class section (``kvcache/sched/class/...``): SMS staged
scheduling + decode preemption under overload
(``mixed_traffic_comparison``) — an identical mixed chat/batch/long-
context stream (Zipf prefix popularity, fake step clock, deliberately
undersized pool) served by the class-aware scheduler and the class-blind
one, single-pool and 2-shard.  Gated rows are pinned ratios:
interactive-class p99 turnaround must improve (>= ~100) while batch-
class token throughput stays within 10% of class-blind.
"""
from __future__ import annotations

import time

import numpy as np

import dataclasses

from repro.core import dram
from repro.core.reorder import mars_order
from repro.core.streams import PAGE_SHIFT
from repro.kernels.paged_attention import ops
from repro.kvcache import BlockPool, PoolConfig, ShardedBlockPool
from repro.kvcache.prefix import BlockTable, PrefixCache


def churned_pool(placement: str, *, num_blocks: int = 512, n_live: int = 16,
                 churn_events: int = 400, seed: int = 0):
    """Alloc/free sequences until the free list is realistically scattered;
    return (pool, live decode batch tables)."""
    rng = np.random.default_rng(seed)
    pool = BlockPool(PoolConfig(num_blocks=num_blocks, placement=placement))
    live: list[BlockTable] = []

    def start_one():
        t = BlockTable()
        for _ in range(int(rng.integers(2, 9))):
            t.blocks.append(pool.alloc(1, hint_blocks=t.blocks)[0])
        t.num_tokens = len(t.blocks) * pool.cfg.block_size
        live.append(t)

    for _ in range(churn_events):
        if len(live) >= n_live or (live and rng.random() < 0.5):
            t = live.pop(int(rng.integers(len(live))))
            for b in t.blocks:
                pool.decref(b)
        else:
            start_one()
    while len(live) > n_live:
        t = live.pop(0)
        for b in t.blocks:
            pool.decref(b)
    while len(live) < n_live:       # top up to a full decode batch
        start_one()
    pool.check_invariants()
    return pool, live


def placement_comparison(*, n_live: int = 16, grant_beats: int = 2,
                         reorder_window=None, seed: int = 0) -> dict:
    """{placement: DramResult} for the same churn trace under both policies."""
    out = {}
    for placement in ("naive", "mars"):
        pool, tables = churned_pool(placement, n_live=n_live,
                                    churn_events=600, seed=seed)
        trace = ops.kv_read_trace(tables, grant_beats=grant_beats)
        if reorder_window is not None:
            perm = np.asarray(mars_order(
                np.asarray(trace, np.int64) >> PAGE_SHIFT,
                window=reorder_window))
            trace = np.asarray(trace)[perm]
        out[placement] = dram.simulate(trace)
    return out


def mean_uplift(n_live: int, seeds=(0, 1, 2), **kw) -> tuple[float, dict]:
    """Seed-averaged bandwidth uplift of MARS over naive placement."""
    ups, last = [], {}
    for seed in seeds:
        last = placement_comparison(n_live=n_live, seed=seed, **kw)
        ups.append(last["mars"].achieved_gbps
                   / last["naive"].achieved_gbps - 1)
    return float(np.mean(ups)), last


def row_hit_rate(res) -> float:
    """Row-buffer hit rate of a ``DramResult``: CAS that did not activate."""
    return 1.0 - res.n_act / max(res.n_requests, 1)


def decode_path_comparison(*, placement: str = "mars", n_live: int = 16,
                           grant_beats: int = 4, window_tokens: int = 0,
                           seed: int = 0, paths=("gather", "kernel"),
                           pool_tables=None) -> dict:
    """{path: DramResult} for one decode step over the same churned pool.

    ``gather``  the dense-view path: every lane's pages gathered in
                parallel, so the memory system sees the round-robin
                interleave of the per-lane streams.  A sliding window
                does not shrink this stream — the dense view gathers the
                whole table and masks afterwards.
    ``kernel``  the Pallas ``paged_attention`` path: the grid walks lanes
                one after another, each lane's pages in page-table order,
                page-contiguously — MARS placement finally reaches the
                attention kernel's address stream unflattened.  With
                ``window_tokens`` > 0 the kernel's window page gate also
                drops pages entirely outside the sliding window from the
                address stream.
    """
    if pool_tables is None:
        pool_tables = churned_pool(placement, n_live=n_live,
                                   churn_events=600, seed=seed)
    pool, tables = pool_tables
    out = {}
    if "gather" in paths:
        out["gather"] = dram.simulate(
            ops.kv_read_trace(tables, grant_beats=grant_beats))
    if "kernel" in paths:
        out["kernel"] = dram.simulate(ops.kv_read_trace_kernel(
            tables, window_tokens=window_tokens,
            block_size=pool.cfg.block_size))
    return out


@dataclasses.dataclass
class ShardedDramResult:
    """Aggregate of per-shard ``DramResult``s: every shard is its own
    memory device serving only its shard's lanes, in parallel.  Row-hit
    aggregates by summing CAS/ACT counts; bandwidth sums across devices
    (S devices deliver S memory systems' worth — the scaling half of the
    sharding story; the placement half is the row-hit rate)."""
    n_requests: int
    n_act: int
    achieved_gbps: float
    per_shard: list


def _aggregate_shards(results) -> ShardedDramResult:
    results = [r for r in results if r.n_requests > 0]
    return ShardedDramResult(
        n_requests=sum(r.n_requests for r in results),
        n_act=sum(r.n_act for r in results),
        achieved_gbps=float(sum(r.achieved_gbps for r in results)),
        per_shard=results)


def sharded_churned_pool(n_shards: int, *, num_blocks: int = 512,
                         n_live: int = 16, churn_events: int = 400,
                         seed: int = 0):
    """Churn a mesh-sharded pool with the same arrival/finish schedule as
    ``churned_pool`` (same rng draws), routing each arriving sequence to
    the least-loaded shard; returns (spool, [(shard, table), ...])."""
    rng = np.random.default_rng(seed)
    spool = ShardedBlockPool(
        PoolConfig(num_blocks=num_blocks, placement="mars"),
        n_shards=n_shards)
    live: list[tuple[int, BlockTable]] = []

    def start_one():
        s = min(range(n_shards),
                key=lambda i: (spool.shards[i].num_live, i))
        t = BlockTable()
        for _ in range(int(rng.integers(2, 9))):
            t.blocks.append(
                spool.shards[s].alloc(1, hint_blocks=t.blocks)[0])
        t.num_tokens = len(t.blocks) * spool.cfg.block_size
        live.append((s, t))

    for _ in range(churn_events):
        if len(live) >= n_live or (live and rng.random() < 0.5):
            s, t = live.pop(int(rng.integers(len(live))))
            for b in t.blocks:
                spool.shards[s].decref(b)
        else:
            start_one()
    while len(live) > n_live:
        s, t = live.pop(0)
        for b in t.blocks:
            spool.shards[s].decref(b)
    while len(live) < n_live:
        start_one()
    spool.check_invariants()
    return spool, live


def sharded_placement_comparison(*, n_shards: int = 4, n_live: int = 16,
                                 grant_beats: int = 2, churn_events: int = 600,
                                 seed: int = 0) -> dict:
    """Shard-routed MARS vs single-pool MARS vs naive, same churn trace.

    The single pool serves the whole decode batch from one memory device,
    so all ``n_live`` lanes interleave into one address stream.  The
    sharded pool routes sequences to ``n_shards`` devices; each device
    sees only its own lanes' interleave (shallower multi-stream merge)
    with MARS row-group packing *within* the shard — the leading shard
    coordinate of the placement key doing its job.  Expected ordering:
    shard-routed MARS row-hit >= single-pool MARS >= naive.
    """
    out = {}
    for placement in ("naive", "mars"):
        _, tables = churned_pool(placement, n_live=n_live,
                                 churn_events=churn_events, seed=seed)
        out[f"single/{placement}"] = dram.simulate(
            ops.kv_read_trace(tables, grant_beats=grant_beats))
    spool, live = sharded_churned_pool(n_shards, n_live=n_live,
                                       churn_events=churn_events, seed=seed)
    per_shard = []
    for s in range(n_shards):
        tables_s = [t for sh, t in live if sh == s]
        per_shard.append(dram.simulate(
            ops.kv_read_trace(tables_s, grant_beats=grant_beats)))
    out["sharded/mars"] = _aggregate_shards(per_shard)
    return out


def obs_overhead_comparison(*, n_requests: int = 12, max_new: int = 24,
                            max_lanes: int = 8, num_blocks: int = 256,
                            seed: int = 0) -> dict:
    """Median per-step wall time of the toy serve engine, bare vs fully
    instrumented (``obs.Observer`` attached: metrics registry adoption,
    trace spans, per-step row-locality feed, shard load sampling).

    The two engines run the identical request schedule and are stepped
    alternately, step for step, so ambient machine noise lands on both
    sides equally; the first few steps (prefill admission) are dropped as
    warm-up.  Returns median seconds per step for each side plus the
    ``efficiency`` ratio ``100 * bare / instrumented`` — 100 means free,
    95 means 5% overhead (the CI gate's floor).
    """
    from repro.obs import Observer
    from repro.serve.engine import ServeEngine
    from repro.serving.scheduler import MarsScheduler, Request

    def build(instrument: bool) -> ServeEngine:
        pool = BlockPool(PoolConfig(num_blocks=num_blocks, block_size=16,
                                    n_kv_heads=2, head_dim=32))
        eng = ServeEngine(pool, MarsScheduler(pool=pool),
                          max_lanes=max_lanes)
        if instrument:
            Observer().attach(eng)
        rng = np.random.default_rng(seed)
        pref = tuple(int(t) for t in rng.integers(1, 100, 32))
        for i in range(n_requests):
            tail = tuple(int(t) for t in rng.integers(1, 100, 3))
            assert eng.submit(Request(rid=i, prompt=pref + tail,
                                      prefix_len=16, max_new=max_new))
        return eng

    engines = {"bare": build(False), "instrumented": build(True)}
    times: dict = {k: [] for k in engines}
    while True:
        live = {k: e for k, e in engines.items()
                if len(e.finished) < n_requests}
        if not live:
            break
        for k, e in live.items():
            t0 = time.perf_counter()
            e.step()
            times[k].append(time.perf_counter() - t0)
    warmup = 3
    med = {k: float(np.median(v[warmup:])) for k, v in times.items()}
    med["efficiency"] = 100.0 * med["bare"] / med["instrumented"]
    return med


def zipf_requests(n_requests: int, n_prefixes: int, zipf_a: float,
                  prefix_tokens: int, seed: int = 0):
    """Skewed-prefix workload: request i reuses prefix p with
    P(p) ∝ 1/(rank+1)^a, plus a unique tail (never shareable)."""
    rng = np.random.default_rng(seed)
    prefixes = [tuple(int(t) for t in rng.integers(1, 10_000, prefix_tokens))
                for _ in range(n_prefixes)]
    probs = 1.0 / np.arange(1, n_prefixes + 1) ** zipf_a
    probs /= probs.sum()
    picks = rng.choice(n_prefixes, size=n_requests, p=probs)
    out = []
    for i, p in enumerate(picks):
        tail = (100_000 + 2 * i, 100_001 + 2 * i)
        out.append(prefixes[p] + tail)
    return out


def eviction_comparison(*, zipf_a: float = 1.1, n_prefixes: int = 48,
                        n_requests: int = 400, num_blocks: int = 48,
                        prefix_blocks: int = 2, block_size: int = 16,
                        seed: int = 0) -> dict:
    """{policy: prefix-cache hit rate} for the same Zipf request stream
    under FIFO and LRU eviction, with the pool sized well below the
    working set so eviction decides who stays resident."""
    assert num_blocks < n_prefixes * prefix_blocks, \
        "pool must be under memory pressure for eviction to matter"
    prompts = zipf_requests(n_requests, n_prefixes, zipf_a,
                            prefix_blocks * block_size, seed=seed)
    out = {}
    for policy in ("fifo", "lru"):
        pool = BlockPool(PoolConfig(num_blocks=num_blocks,
                                    block_size=block_size,
                                    eviction=policy))
        cache = PrefixCache(block_size)
        cache.attach(pool)
        hits = possible = 0
        for prompt in prompts:
            prompt = list(prompt)
            bids, n = cache.match(prompt, pool)
            table = BlockTable(list(bids), n)
            table.extend(pool, prompt[n:], seq_tokens=prompt, cache=cache)
            hits += n
            possible += prefix_blocks * block_size
            cache.release(table, pool)
        pool.check_invariants()
        out[policy] = hits / possible
    return out


def tiered_promotion_comparison(*, n_prefixes: int = 24,
                                num_blocks: int = 64, block_size: int = 16,
                                seed: int = 0) -> dict:
    """{mode: DramResult} for the same batched promotion copy-in, written
    MARS-reordered vs in arrival order.

    Setup (identical under both modes, same rng): register
    ``n_prefixes`` single-block prefixes, demote them all under pool
    pressure, fragment the free list with a shuffled alloc/free pass so
    promotion destinations scatter across row groups, then ``match`` all
    prompts in one lookahead batch and ``flush_promotions``.  The flush's
    destination order is replayed through ``core/dram.simulate`` as a
    write stream — the only difference between the two runs is the copy
    order (``TierManager(reorder=...)``), so the row-hit gap is the
    reorder's contribution.
    """
    from repro.kvcache.tiers import TierManager
    out = {}
    for mode, reorder in (("mars", True), ("naive", False)):
        rng = np.random.default_rng(seed)
        pool = BlockPool(PoolConfig(num_blocks=num_blocks,
                                    block_size=block_size,
                                    placement="naive"))
        cache = PrefixCache(block_size)
        cache.attach(pool)
        tiers = TierManager(pool, cache, reorder=reorder)
        prompts = []
        for i in range(n_prefixes):
            prompt = [int(t) for t in rng.integers(1, 10_000, block_size)]
            prompt.append(i + 1)           # tail token: prefix < prompt
            t = BlockTable()
            t.extend(pool, prompt, seq_tokens=prompt, cache=cache)
            cache.release(t, pool)
            prompts.append(prompt)
        grab = pool.alloc(pool.num_free + pool.num_cached)  # demote all
        assert tiers.stats.demotes == n_prefixes
        for b in grab:
            pool.decref(b)
        # fragment: re-grab everything, free a shuffled half — the free
        # list (= destination allocation order) now scatters across row
        # groups exactly like a churned serving pool
        grab = pool.alloc(num_blocks)
        freed = rng.permutation(num_blocks)[:num_blocks // 2]
        for i in freed:
            pool.decref(grab[i])
        for p in prompts:                  # one lookahead batch
            tiers.match(p)
        assert tiers.pending == n_prefixes
        dsts = tiers.flush_promotions()
        trace = TierManager.write_trace(dsts)
        out[mode] = dram.simulate(trace, is_write=np.ones(len(trace), bool))
    return out


def tiered_eviction_comparison(*, n_deep: int = 6, deep_blocks: int = 4,
                               n_shallow: int = 36, shallow_window: int = 12,
                               rounds: int = 24, num_blocks: int = 36,
                               block_size: int = 16, tier_blocks: int = 8,
                               seed: int = 0) -> dict:
    """Cost-aware vs LRU eviction over the same tiered prefix stream.

    The stream mixes ``n_deep`` deep prefixes (``deep_blocks`` chained
    blocks — a causal recompute reruns the whole chain) recurring every
    round with a sliding window of shallow single-block prefixes, over a
    pool well below the working set and a spill tier too small to hold
    everyone (so some evictions genuinely drop).  Cost mode ranks victims
    by re-acquisition cost and so protects the deep chains; LRU evicts by
    recency and keeps the fresher shallow blocks instead.  Returns per
    policy: ``reuse`` (matched / matchable prefix tokens, promoted blocks
    included — higher is better) and ``recompute_tokens`` (the prefill
    bill for what was lost).
    """
    from repro.kvcache.tiers import TierManager, TierSpec
    rng = np.random.default_rng(seed)
    deep = [tuple(int(t) for t in rng.integers(1, 10_000,
                                               deep_blocks * block_size))
            for _ in range(n_deep)]
    shallow = [tuple(int(t) for t in rng.integers(1, 10_000, block_size))
               for _ in range(n_shallow)]
    schedule = []
    for r in range(rounds):
        for p in deep:
            schedule.append(p + (9_000_000 + r,))      # unique tail
        for j in range(shallow_window):
            p = shallow[(r + j) % n_shallow]
            schedule.append(p + (9_500_000 + r,))
    out = {}
    for policy in ("cost", "lru"):
        pool = BlockPool(PoolConfig(num_blocks=num_blocks,
                                    block_size=block_size,
                                    eviction=policy))
        cache = PrefixCache(block_size)
        cache.attach(pool)
        tiers = TierManager(pool, cache,
                            specs=(TierSpec("host", tier_blocks,
                                            latency_us=5.0, gbps=20.0),))
        hits = possible = 0
        for prompt in schedule:
            prompt = list(prompt)
            bids, n = tiers.match(prompt)
            table = BlockTable(list(bids), n)
            table.extend(pool, prompt[n:], seq_tokens=prompt, cache=cache)
            tiers.flush_promotions()
            hits += n
            possible += len(prompt) - 1    # all full blocks are matchable
            cache.release(table, pool)
        pool.check_invariants()
        tiers.check()
        out[policy] = {"reuse": hits / possible,
                       "recompute_tokens": possible - hits,
                       "promoted_tokens": tiers.stats.promoted_tokens,
                       "drops": tiers.stats.drops}
    return out


def alloc_soak(kind: str = "single", *, num_blocks: int = 256,
               events: int = 2000, n_live_cap: int = 48,
               n_shards: int = 2, seed: int = 0) -> dict:
    """Multi-round Zipf-sized alloc/free soak over one pool (or a
    mesh-sharded pool, least-loaded routing) — the allocator's long-run
    behaviour under realistic churn.

    Sequence sizes are Zipf-distributed (many short, a heavy tail of
    long), frees are random, and the pool runs near capacity, so the free
    list scatters the way a serving pool's does.  Reports:

      ``locality``      mean over live tables of the fraction of blocks
                        in the table's modal row group (MARS placement's
                        long-run survival under fragmentation pressure)
      ``free_run``      mean contiguous free-block run length (classic
                        external-fragmentation measure; higher = less
                        fragmented)
      ``alloc_us``      mean wall microseconds per alloc() call
    """
    rng = np.random.default_rng(seed)
    if kind == "single":
        pools = [BlockPool(PoolConfig(num_blocks=num_blocks,
                                      placement="mars"))]
        route = lambda: 0
    else:
        spool = ShardedBlockPool(
            PoolConfig(num_blocks=num_blocks, placement="mars"),
            n_shards=n_shards)
        pools = spool.shards
        route = lambda: min(range(n_shards),
                            key=lambda i: (pools[i].num_live, i))
    live: list[tuple[int, BlockTable]] = []
    alloc_s = 0.0
    n_allocs = 0

    def start_one():
        nonlocal alloc_s, n_allocs
        z = int(min(8, rng.zipf(1.5)))
        s = route()
        if pools[s].num_free + pools[s].num_cached < z:
            return False
        t = BlockTable()
        for _ in range(z):
            t0 = time.perf_counter()
            t.blocks.append(pools[s].alloc(1, hint_blocks=t.blocks)[0])
            alloc_s += time.perf_counter() - t0
            n_allocs += 1
        t.num_tokens = len(t.blocks) * pools[s].cfg.block_size
        live.append((s, t))
        return True

    for _ in range(events):
        if live and (len(live) >= n_live_cap or rng.random() < 0.45):
            s, t = live.pop(int(rng.integers(len(live))))
            for b in t.blocks:
                pools[s].decref(b)
        else:
            start_one()
    for p in pools:
        p.check_invariants()
    # live-table row-group locality: modal-group fraction per table
    bpg = pools[0].cfg.blocks_per_group
    fracs = []
    for _, t in live:
        groups = [b // bpg for b in t.blocks]
        fracs.append(max(groups.count(g) for g in set(groups))
                     / len(groups))
    # free-list fragmentation: mean contiguous free run length
    runs = []
    for p in pools:
        run = 0
        for bid in range(p.cfg.num_blocks):
            if not p.used[bid]:
                run += 1
            elif run:
                runs.append(run)
                run = 0
        if run:
            runs.append(run)
    return {"locality": float(np.mean(fracs)) if fracs else 0.0,
            "free_run": float(np.mean(runs)) if runs else 0.0,
            "alloc_us": 1e6 * alloc_s / max(n_allocs, 1),
            "n_allocs": n_allocs}


_PIPELINE_MODEL = {}


def _pipeline_model(seed: int = 0):
    """Cached smoke-model (cfg, params) for the decode-pipeline bench —
    params init is the expensive part and every scenario shares it."""
    if seed not in _PIPELINE_MODEL:
        import jax
        from repro import configs
        from repro.models import lm as lm_mod
        cfg = configs.get_smoke("qwen1_5_0_5b")
        _PIPELINE_MODEL[seed] = (
            cfg, lm_mod.init(cfg, jax.random.key(seed)).params)
    return _PIPELINE_MODEL[seed]


def decode_pipeline_comparison(scenario: str = "single", *,
                               n_lanes: int = 4, warm_steps: int = 4,
                               timed_steps: int = 16, seed: int = 0) -> dict:
    """Wall-clock A/B: split-phase decode pipeline vs the synchronous
    ``decode()`` wrapper, twin backends serving the same ragged lanes.

    ``scenario``: "single" (one pool), "shards2" (mesh-sharded, 2
    shards, issue-then-gather dispatch), "tiered" (spill tiers behind
    the pool).  The decode path is compiled, never interpreted: the
    Pallas kernel on a TPU, the jitted XLA gather decode on CPU (where
    Pallas supports interpret mode only).  Prompt lengths and step
    counts stay inside one pow2 operand bucket so neither loop
    recompiles mid-flight.

    Returns ``{"seq_us", "pipe_us", "ratio"}`` — best per-step wall
    times and ``100 * t_seq / t_pipe`` (>= 100 means the pipeline at
    least matches the sequential path's step throughput).  The twin
    backends advance in lock-step within ONE loop (both paths sampled
    under the same machine noise each iteration) and the estimator is
    the per-step MINIMUM: scheduler/GC noise only ever inflates a wall
    clock, so the min converges on the true step cost where totals and
    even medians of few-ms steps drown in shared-CI jitter.  Greedy
    tokens from the two paths are asserted bit-identical first: same
    decode mode, same operand values, the pipeline only reorders work.
    """
    import jax
    from repro.kvcache.backend import make_backend

    mode = "kernel" if jax.default_backend() == "tpu" else "gather"
    cfg, params = _pipeline_model(seed)
    kw = dict(num_blocks=64, block_size=16, decode_mode=mode)
    if scenario == "shards2":
        kw["shards"] = 2
    elif scenario == "tiered":
        kw["tiered"] = True
    else:
        assert scenario == "single", scenario
    rng = np.random.default_rng(seed)
    # 33..36-token prompts sit just past a block boundary: 3 pages pads
    # to the 4-page pow2 bucket (block_size 16), which covers
    # num_tokens + 1 <= 64 — up to 27 decode steps with zero mid-loop
    # recompiles for either path
    assert 36 + warm_steps + timed_steps + 1 <= 64
    prompts = [rng.integers(1, cfg.vocab, 33 + i).tolist()
               for i in range(n_lanes)]

    def make() -> dict:
        backend = make_backend(cfg, "paged", **kw)
        return {"b": backend, "last": [p[-1] for p in prompts],
                "sids": [backend.new_seq(params, p)[0] for p in prompts],
                "toks": [], "dts": []}

    def advance(st: dict, pipelined: bool, timed: bool) -> None:
        backend = st["b"]
        t0 = time.perf_counter()
        if pipelined:
            backend.flush()               # commit step i-1's write-back
            step = backend.dispatch_decode(params, st["last"],
                                           sids=st["sids"])
            logits = backend.sync(step)
        else:
            logits = backend.decode(params, st["sids"], st["last"])
        st["last"] = [int(np.argmax(lg)) for lg in np.asarray(logits)]
        dt = time.perf_counter() - t0
        if timed:
            st["dts"].append(dt)
        st["toks"].append(list(st["last"]))

    seq, pipe = make(), make()
    for i in range(warm_steps + timed_steps):
        # alternate who goes first so a sustained noise burst lands on
        # both paths' samples, not systematically on one
        first, second = (seq, pipe) if i % 2 == 0 else (pipe, seq)
        advance(first, first is pipe, i >= warm_steps)
        advance(second, second is pipe, i >= warm_steps)
    pipe["b"].flush()
    assert seq["toks"] == pipe["toks"], \
        f"pipelined decode diverged from sequential ({scenario})"
    t_seq, t_pipe = (float(np.min(st["dts"])) for st in (seq, pipe))
    for st in (seq, pipe):
        st["b"].release()
    return {"seq_us": 1e6 * t_seq,
            "pipe_us": 1e6 * t_pipe,
            "ratio": 100.0 * t_seq / max(t_pipe, 1e-12)}


def mixed_traffic_comparison(scenario: str = "single", *,
                             max_lanes: int = 4, seed: int = 0) -> dict:
    """Class-aware SMS scheduling + decode preemption vs the class-blind
    scheduler, same overloaded mixed-class stream, fake step clock.

    The stream mixes three traffic classes the way a serving mix does:
    ``batch`` summarize jobs (long decodes) and long-context ``stream``
    requests arrive first and hog the deliberately undersized pool;
    ``interactive`` chat turns (short decodes, Zipf-popular prefixes)
    keep arriving while the pool is full.  Both engines serve identical
    requests through a real smoke-LM ``PagedBackend`` (single pool or 2
    mesh shards); bounced offers retry every step (client retry), so
    every request eventually completes and the only difference is WHEN.

    Returns per-class p99 turnaround (finish - arrival, in steps) for
    both schedulers plus the two gated ratios:

      ``interactive_gain``   100 * blind_p99 / aware_p99 for the
                             interactive class (> 100: class-aware
                             scheduling + preemption cut chat tail
                             latency under overload)
      ``batch_tput_ratio``   100 * aware / blind batch-class token
                             throughput (tokens per step) — the price
                             paid; the gate holds it within 10%

    Tokens are greedy over fixed params, the clock is the step counter,
    and the schedule is seeded, so both ratios are deterministic."""
    import jax
    from repro.kvcache.backend import make_backend
    from repro.serve.engine import PagedLM, ServeEngine
    from repro.serving.scheduler import MarsScheduler, Request, \
        default_classes

    mode = "kernel" if jax.default_backend() == "tpu" else "gather"
    cfg, params = _pipeline_model(seed)
    rng = np.random.default_rng(seed)
    prefixes = [tuple(int(t) for t in rng.integers(1, cfg.vocab, 16))
                for _ in range(4)]
    long_prefixes = [tuple(int(t) for t in rng.integers(1, cfg.vocab, 48))
                     for _ in range(2)]
    probs = 1.0 / np.arange(1, 5) ** 1.1
    probs /= probs.sum()
    # request spec: (class, prompt, arrival, max_new) — instantiated
    # fresh per engine (the scheduler stamps routing state on Request)
    spec = []
    for i in range(4):          # batch summarize: long decode, early
        spec.append(("batch", prefixes[i % 2], float(i), 16))
    for i in range(3):          # long-context stream: big prompt
        spec.append(("stream", long_prefixes[i % 2], 2.0 + 2 * i, 8))
    for i in range(12):         # interactive chat: Zipf prefix, steady
        p = prefixes[int(rng.choice(4, p=probs))]
        spec.append(("interactive", p, 4.0 + 2 * i, 4))

    def serve(classes) -> dict:
        kw = dict(num_blocks=16, block_size=16, decode_mode=mode)
        if scenario == "shards2":
            # 12 blocks/shard: a sequence never spans shards, so per-shard
            # pressure must stay comparable to the single-pool run for
            # overload (and preemption) to actually trigger
            kw.update(shards=2, num_blocks=24)
        else:
            assert scenario == "single", scenario
        backend = make_backend(cfg, "paged", **kw)
        pool = backend.pool
        sched = MarsScheduler(pool=pool, classes=classes)
        eng = ServeEngine(pool, sched, PagedLM(params, cfg, backend),
                          max_lanes=max_lanes)
        reqs = [Request(rid=i, prompt=pr + (1 + i, 2 + i), arrival=arr,
                        max_new=new, traffic_class=cname)
                for i, (cname, pr, arr, new) in enumerate(spec)]
        queue = sorted(reqs, key=lambda r: (r.arrival, r.rid))
        waiting: list = []
        finished_at: dict = {}
        t0 = time.perf_counter()
        step = 0
        while len(finished_at) < len(reqs):
            now = float(step)
            while queue and queue[0].arrival <= now:
                waiting.append(queue.pop(0))
            waiting = [r for r in waiting if not eng.submit(r)]
            eng.step(now=now)
            for rid in eng.finished:
                finished_at.setdefault(rid, now)
            step += 1
            assert step < 5000, "mixed-traffic serve did not drain"
        wall_us = (time.perf_counter() - t0) * 1e6
        backend.release()
        lat: dict = {}
        toks: dict = {}
        for r in reqs:
            lat.setdefault(r.traffic_class, []).append(
                finished_at[r.rid] - r.arrival)
            toks[r.traffic_class] = toks.get(r.traffic_class, 0) + r.max_new
        return {"p99": {c: float(np.percentile(v, 99))
                        for c, v in lat.items()},
                "batch_tput": toks["batch"] / step,
                "preempts": sum(cs.preempt
                                for cs in sched.class_stats.values()),
                "steps": step, "wall_us": wall_us}

    aware = serve(default_classes(3))
    blind = serve(None)
    return {"aware": aware, "blind": blind,
            "interactive_gain": 100.0 * blind["p99"]["interactive"]
            / max(aware["p99"]["interactive"], 1e-9),
            "batch_tput_ratio": 100.0 * aware["batch_tput"]
            / max(blind["batch_tput"], 1e-9),
            "wall_us": aware["wall_us"] + blind["wall_us"]}


def run(emit, smoke: bool = False) -> None:
    lanes = (8,) if smoke else (8, 32)
    seeds = (0,) if smoke else (0, 1, 2)
    for n_live in lanes:     # decode lanes: more lanes = deeper interleave
        t0 = time.perf_counter()
        uplift, res = mean_uplift(n_live, seeds=seeds)
        us = (time.perf_counter() - t0) * 1e6
        for placement, r in res.items():
            emit(f"kvcache/placement/{placement}/lanes{n_live}", us / 6,
                 f"{r.achieved_gbps:.2f}GB/s")
        emit(f"kvcache/placement/uplift/lanes{n_live}", us / 6,
             f"{100 * uplift:.2f}%")
    if not smoke:
        # with the MC-side MARS reorder buffer in front (window = RequestQ):
        # reordering recovers part of what naive placement lost, shrinking
        # the gap — the co-design point: placement helps where reordering
        # cannot
        t0 = time.perf_counter()
        res = placement_comparison(n_live=32, reorder_window=512)
        us = (time.perf_counter() - t0) * 1e6
        uplift = res["mars"].achieved_gbps / res["naive"].achieved_gbps - 1
        emit("kvcache/placement+reorder/uplift", us / 2,
             f"{100 * uplift:.2f}%")
    # decode-path bandwidth: gather-path interleave vs the kernel's
    # sequence-major page walk, same MARS-placed pool — the first
    # end-to-end measurement of placement reaching the attention kernel
    mars_pt = None
    for placement in ("naive", "mars"):
        t0 = time.perf_counter()
        pt = churned_pool(placement, n_live=16, churn_events=600, seed=0)
        res = decode_path_comparison(placement=placement, pool_tables=pt)
        us = (time.perf_counter() - t0) * 1e6
        if placement == "mars":
            mars_pt = pt
        for path, r in res.items():
            emit(f"kvcache/decode/{path}/{placement}", us / 2,
                 f"{r.achieved_gbps:.2f}GB/s")
            emit(f"kvcache/decode/{path}/{placement}/rowhit", us / 2,
                 f"{100 * row_hit_rate(r):.2f}%")
    # sliding-window decode: the kernel's window page gate drops
    # out-of-window pages from its walk; the gather path still fetches
    # the full table, so its window trace is identical to the
    # kvcache/decode/gather/mars rows above — only the kernel re-traces,
    # over the same churned pool
    t0 = time.perf_counter()
    res = decode_path_comparison(window_tokens=64, paths=("kernel",),
                                 pool_tables=mars_pt)
    us = (time.perf_counter() - t0) * 1e6
    r = res["kernel"]
    emit("kvcache/decode/kernel/mars/window64", us,
         f"{r.achieved_gbps:.2f}GB/s")
    emit("kvcache/decode/kernel/mars/window64/rowhit", us,
         f"{100 * row_hit_rate(r):.2f}%")
    # mesh-sharded placement: route streams to devices first, row-group-
    # -pack within each — per-shard traces replayed through the DRAM
    # model (each shard = its own memory device); shard-routed MARS
    # row-hit must bound single-pool MARS which bounds naive
    for i, n_shards in enumerate((2,) if smoke else (2, 4)):
        t0 = time.perf_counter()
        res = sharded_placement_comparison(n_shards=n_shards, n_live=16)
        us = (time.perf_counter() - t0) * 1e6
        emit(f"kvcache/placement/sharded/rowhit/shards{n_shards}", us / 3,
             f"{100 * row_hit_rate(res['sharded/mars']):.2f}%")
        if i == 0:      # single-pool baselines are shard-count-independent
            emit("kvcache/placement/sharded/rowhit/single-mars", us / 3,
                 f"{100 * row_hit_rate(res['single/mars']):.2f}%")
            emit("kvcache/placement/sharded/rowhit/single-naive", us / 3,
                 f"{100 * row_hit_rate(res['single/naive']):.2f}%")
        emit(f"kvcache/placement/sharded/gbps/shards{n_shards}", us / 3,
             f"{res['sharded/mars'].achieved_gbps:.2f}GB/s")
    # observability overhead: identical toy-engine schedules stepped
    # alternately, bare vs Observer-attached — efficiency is the ratio of
    # median per-step wall times (100 = free; the CI baseline gate fails
    # below 95, i.e. >5% metrics overhead)
    t0 = time.perf_counter()
    ov = obs_overhead_comparison(max_new=12 if smoke else 24)
    us = (time.perf_counter() - t0) * 1e6
    emit("kvcache/decode/obs/efficiency", us,
         f"{ov['efficiency']:.2f}%")
    # wall-clock detail row — named outside the gated namespace on purpose
    emit("kvcache/obs/decode-step", ov["instrumented"] * 1e6,
         f"{1e6 * (ov['instrumented'] - ov['bare']):.1f}us-overhead")
    # FIFO vs LRU under skewed prefix popularity
    n_requests = 150 if smoke else 400
    for zipf_a in (0.8, 1.3):
        t0 = time.perf_counter()
        rates = eviction_comparison(zipf_a=zipf_a, n_requests=n_requests)
        us = (time.perf_counter() - t0) * 1e6
        for policy, rate in rates.items():
            emit(f"kvcache/evict/{policy}/zipf{zipf_a}", us / 2,
                 f"{100 * rate:.1f}%hit")
    # tier boundary: MARS-reordered batched promotion vs arrival order —
    # the same scattered destination set written in two orders through
    # the DRAM model; the reordered stream must hold the row-hit bound
    t0 = time.perf_counter()
    res = tiered_promotion_comparison()
    us = (time.perf_counter() - t0) * 1e6
    for mode, r in res.items():
        emit(f"kvcache/tier/promote/{mode}/rowhit", us / 2,
             f"{100 * row_hit_rate(r):.2f}%")
    # cost-aware vs LRU eviction over the tiered prefix stream: cost mode
    # protects expensive-to-recompute deep chains, so reuse is higher and
    # the recompute bill lower
    t0 = time.perf_counter()
    tres = tiered_eviction_comparison()
    us = (time.perf_counter() - t0) * 1e6
    for policy, d in tres.items():
        emit(f"kvcache/tier/evict/{policy}/reuse", us / 2,
             f"{100 * d['reuse']:.2f}%")
        # recompute bill: detail row, outside the gated namespace
        # (lower is better — the gate only understands higher-is-better)
        emit(f"kvcache/tierdetail/evict/{policy}", us / 2,
             f"{d['recompute_tokens']}tok-recomputed")
    # allocator soak: Zipf-sized churn fragmentation + alloc latency over
    # the plain and mesh-sharded pools; locality/free-run are gated,
    # wall-clock lives in the us column
    events = 800 if smoke else 2000
    for kind in ("single", "sharded2"):
        soak = alloc_soak("single" if kind == "single" else "sharded",
                          events=events)
        emit(f"kvcache/alloc/{kind}/locality", soak["alloc_us"],
             f"{100 * soak['locality']:.2f}%")
        emit(f"kvcache/alloc/{kind}/freerun", soak["alloc_us"],
             f"{soak['free_run']:.2f}blocks")
    # split-phase decode pipeline vs the synchronous decode() wrapper:
    # real-LM twin backends, compiled (non-interpret) decode, bit-
    # identical tokens asserted inside.  The ratio row is gated against
    # the pinned 100.0 baseline with a wide wall-clock-jitter tolerance:
    # the pipeline must at least roughly hold the sequential path's step
    # throughput in every configuration
    for scen in ("single", "shards2", "tiered"):
        r = decode_pipeline_comparison(scen)
        emit(f"kvcache/decode/pipeline/{scen}", r["pipe_us"],
             f"{r['ratio']:.2f}%")
    # SMS traffic classes under overload: class-aware staged scheduling +
    # decode preemption vs the class-blind scheduler, identical mixed
    # stream on a fake step clock.  Both gated rows are pinned ratios:
    # interactive-p99 >= ~100 (chat tail latency must improve) and
    # batch-tput within 10% of class-blind (the throughput price cap)
    for scen in ("single", "shards2"):
        r = mixed_traffic_comparison(scen)
        emit(f"kvcache/sched/class/{scen}/interactive-p99",
             r["wall_us"] / 2, f"{r['interactive_gain']:.2f}%")
        emit(f"kvcache/sched/class/{scen}/batch-tput",
             r["wall_us"] / 2, f"{r['batch_tput_ratio']:.2f}%")
        # absolute tails + preempt count: detail rows, outside the gate
        emit(f"kvcache/scheddetail/{scen}/aware-p99", r["wall_us"] / 2,
             f"{r['aware']['p99']['interactive']:.1f}steps")
        emit(f"kvcache/scheddetail/{scen}/blind-p99", r["wall_us"] / 2,
             f"{r['blind']['p99']['interactive']:.1f}steps")
        emit(f"kvcache/scheddetail/{scen}/preempts", r["wall_us"] / 2,
             f"{r['aware']['preempts']}preempts")
