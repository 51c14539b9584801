"""Mesh-sharded block pools: partitioning/discovery, admission routing
(prefix-page affinity + shard load), cross-shard parity vs a single pool,
shard-local CoW forks, per-shard invariants under soak, and exhaustion
isolation."""
import dataclasses

import numpy as np
import pytest

from repro.kvcache import BlockPool, BlockTable, PoolConfig, \
    ShardedBlockPool, placement_key, row_group_of
from repro.serving.scheduler import MarsScheduler, Request


def _spool(num_blocks=32, n_shards=2, block_size=4, **kw):
    return ShardedBlockPool(
        PoolConfig(num_blocks=num_blocks, block_size=block_size, **kw),
        n_shards=n_shards)


# ---------------------------------------------------------------------------
# partitioning + mesh discovery
# ---------------------------------------------------------------------------

def test_shards_partition_the_pool():
    sp = _spool(num_blocks=32, n_shards=4)
    assert sp.n_shards == 4 and sp.shard_blocks == 8
    assert all(s.cfg.num_blocks == 8 for s in sp.shards)
    assert sp.num_free == 32 and sp.num_live == 0
    with pytest.raises(AssertionError):
        _spool(num_blocks=30, n_shards=4)   # must divide evenly


def test_mesh_discovery_from_model_axis():
    import jax
    from repro.launch.mesh import make_local_mesh
    from repro.sharding import rules
    from repro.sharding.context import use_mesh

    assert rules.pool_shard_count(None) == 1
    mesh = make_local_mesh()                 # model axis size 1
    assert rules.pool_shard_count(mesh) == 1
    sp = ShardedBlockPool(PoolConfig(num_blocks=16), mesh=mesh)
    assert sp.n_shards == 1
    with use_mesh(mesh):                     # ambient discovery
        assert ShardedBlockPool(PoolConfig(num_blocks=16)).n_shards == 1
    # no mesh anywhere -> single shard
    assert ShardedBlockPool(PoolConfig(num_blocks=16)).n_shards == 1


def test_serve_mesh_refuses_to_share_devices():
    """One distinct device per shard: a serving mesh with more shards than
    devices raises instead of mapping shards onto devices round-robin."""
    import jax
    from repro.launch.mesh import make_serve_mesh

    n = jax.local_device_count()
    mesh = make_serve_mesh(n)
    assert mesh.shape["model"] == n and len(set(mesh.devices.flat)) == n
    with pytest.raises(ValueError, match=f"{n + 1} shards need"):
        make_serve_mesh(n + 1)


def test_importing_the_server_leaves_cpu_devices_requestable():
    """``request_cpu_devices`` works only before JAX starts a backend, so
    importing the serving stack must not start one — a module-level jnp
    constant once did, and ``--shards N`` then ran every shard on one CPU
    device.  Checked in a fresh interpreter (this one has a backend)."""
    import os
    import subprocess
    import sys
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=src)
    env.pop("XLA_FLAGS", None)
    code = ("from repro.launch import mesh, serve\n"
            "mesh.request_cpu_devices(3)\n"
            "import jax\n"
            "assert jax.local_device_count() == 3, jax.devices()\n")
    subprocess.run([sys.executable, "-c", code], env=env, check=True,
                   timeout=300)


def test_placement_key_leads_with_shard():
    # the device/shard coordinate orders ahead of the bank+row-group key:
    # a later row group on an earlier shard sorts first
    assert placement_key(63, 8, shard=0) < placement_key(0, 8, shard=1)
    assert placement_key(5, 8) == (0, row_group_of(5, 8), 5)


# ---------------------------------------------------------------------------
# two-phase admission routing
# ---------------------------------------------------------------------------

def test_route_prefix_affinity_cohabits_pages():
    sp = _spool(num_blocks=32, n_shards=2)
    sp.reserve(2)
    s0 = sp.route(rid=0, page="hot", n=2)
    # same page keeps routing to the same shard even though the other
    # shard is now emptier
    sp.reserve(2)
    assert sp.route(rid=1, page="hot", n=2) == s0
    # a different page balances to the other shard (load = reserved)
    sp.reserve(2)
    assert sp.route(rid=2, page="cold", n=2) != s0
    assert sp.reserved == 6 and sp._pending == 0
    sp.check_invariants()


def test_route_defers_when_no_shard_has_headroom():
    sp = _spool(num_blocks=8, n_shards=2)    # 4 blocks per shard
    sp.reserve(4); assert sp.route(rid=0, page="a", n=4) is not None
    sp.reserve(4); assert sp.route(rid=1, page="b", n=4) is not None
    # both shards fully reserved: aggregate admission refuses too
    assert not sp.can_reserve(1)
    sp.reserve(2)
    assert sp.route(rid=2, page="c", n=2) is None   # queued, not lost
    assert sp._pending == 2
    # releasing rid 0 frees its shard; the deferred request routes now
    sp.unreserve(4, rid=0)
    assert sp.route(rid=2, page="c", n=2) is not None
    sp.check_invariants()


def test_can_reserve_requires_single_shard_fit():
    sp = _spool(num_blocks=16, n_shards=2)   # 8 per shard
    # 10 blocks fit the aggregate but can never sit on one shard: a
    # sequence (and its CoW forks) never spans shards
    assert not sp.can_reserve(10)
    assert sp.can_reserve(8)


def test_scheduler_routes_admissions_by_page_and_load():
    sp = _spool(num_blocks=64, n_shards=2, block_size=8)
    sched = MarsScheduler(pool=sp)
    # two hot prefixes, interleaved arrivals (prefix_len 8 = one block)
    pa = tuple(range(1, 9))
    pb = tuple(range(101, 109))
    reqs = [Request(rid=i, prompt=(pa if i % 2 == 0 else pb) + (200 + i,),
                    prefix_len=8, max_new=4) for i in range(6)]
    for r in reqs:
        assert sched.offer(r)
    batch = sched.schedule_batch(6)
    assert len(batch) == 6
    shard_of = {r.rid: r._shard for r in batch}
    # page-coherent co-location: each prefix's requests share one shard,
    # and the two prefixes landed on different shards (load balancing)
    sa = {shard_of[r.rid] for r in reqs if r.prompt[:8] == pa}
    sb = {shard_of[r.rid] for r in reqs if r.prompt[:8] == pb}
    assert len(sa) == 1 and len(sb) == 1 and sa != sb
    sp.check_invariants()


def test_scheduler_defers_until_a_shard_frees():
    sp = _spool(num_blocks=16, n_shards=2, block_size=8)  # 8 blocks/shard
    sched = MarsScheduler(pool=sp)
    # each request needs 5 blocks -> one per shard fits, third defers
    reqs = [Request(rid=i, prompt=tuple(range(1 + 32 * i, 33 + 32 * i)),
                    prefix_len=8, max_new=8) for i in range(3)]
    for r in reqs:
        assert sched.offer(r)
    batch = sched.schedule_batch(8)
    assert [r.rid for r in batch] == [0, 1]
    assert sched.stats.shard_defers == 1
    assert len(sched) == 1                    # rid 2 still buffered
    # a finished request frees its shard reservation -> rid 2 schedules
    sp.unreserve(5, rid=batch[0].rid)
    batch2 = sched.schedule_batch(8)
    assert [r.rid for r in batch2] == [2]
    sp.check_invariants()


# ---------------------------------------------------------------------------
# cross-shard parity vs a single pool / dense backend
# ---------------------------------------------------------------------------

def _model(arch="qwen1_5_0_5b", f32=False):
    import jax
    from repro import configs
    from repro.models import lm

    cfg = configs.get_smoke(arch)
    if f32:
        cfg = dataclasses.replace(cfg, param_dtype="float32",
                                  compute_dtype="float32")
    return cfg, lm.init(cfg, jax.random.key(0)).params


@pytest.mark.parametrize("decode_mode", ["gather", "kernel"])
def test_sharded_paged_parity_vs_dense(decode_mode):
    """Rows routed across two shard pools must decode to exactly the
    logits a single dense cache produces — the shard boundary is a pure
    storage partition, invisible to the math."""
    import jax
    import jax.numpy as jnp
    from repro.kvcache.backend import DenseBackend, ShardedPagedBackend
    from repro.models import lm

    cfg, params = _model(f32=decode_mode == "kernel")
    tokens = jax.random.randint(jax.random.key(1), (4, 9), 1, cfg.vocab)
    dense = DenseBackend(cfg, batch=4, max_seq=24)
    sharded = ShardedPagedBackend(cfg, n_shards=2, num_blocks=64,
                                  block_size=4, decode_mode=decode_mode)
    lg_d, _ = lm.prefill(params, cfg, tokens, backend=dense)
    lg_p, _ = lm.prefill(params, cfg, tokens, backend=sharded)
    np.testing.assert_allclose(np.asarray(lg_d, np.float32),
                               np.asarray(lg_p, np.float32),
                               rtol=1e-4, atol=1e-4)
    # the batch really is spread: both shards hold live blocks
    assert all(p.num_live > 0 for p in sharded.pool.shards)
    tok = jnp.argmax(lg_d[:, -1], -1).astype(jnp.int32)[:, None]
    for _ in range(4):
        lg_d, _ = lm.decode_step(params, cfg, tok, dense)
        lg_p, _ = lm.decode_step(params, cfg, tok, sharded)
        np.testing.assert_allclose(np.asarray(lg_d, np.float32),
                                   np.asarray(lg_p, np.float32),
                                   rtol=1e-4, atol=1e-4)
        a = np.argmax(np.asarray(lg_d[:, -1], np.float32), -1)
        assert (a == np.argmax(np.asarray(lg_p[:, -1], np.float32),
                               -1)).all()
        tok = jnp.asarray(a, jnp.int32)[:, None]
    assert (np.asarray(sharded.lengths) == np.asarray(dense.lengths)).all()
    sharded.release()
    sharded.pool.check_invariants()
    assert sharded.pool.num_live == 0
    with pytest.raises(RuntimeError, match="released"):
        sharded.decode_step(params, jnp.ones((4, 1), jnp.int32))


def test_sharded_matches_single_pool_backend():
    """Same tokens through a 2-shard backend and a plain single-pool
    PagedBackend: identical logits (both run the same per-shard math)."""
    import jax
    from repro.kvcache.backend import PagedBackend, ShardedPagedBackend
    from repro.models import lm

    cfg, params = _model()
    tokens = jax.random.randint(jax.random.key(2), (2, 9), 1, cfg.vocab)
    single = PagedBackend(cfg, num_blocks=32, block_size=4,
                          decode_mode="gather")
    sharded = ShardedPagedBackend(cfg, n_shards=2, num_blocks=64,
                                  block_size=4, decode_mode="gather")
    lg_s, _ = lm.prefill(params, cfg, tokens, backend=single)
    lg_h, _ = lm.prefill(params, cfg, tokens, backend=sharded)
    np.testing.assert_allclose(np.asarray(lg_s, np.float32),
                               np.asarray(lg_h, np.float32),
                               rtol=1e-5, atol=1e-5)
    # least-loaded row routing spreads one row per shard
    assert [p.num_live for p in sharded.pool.shards] == [3, 3]
    single.release()
    sharded.release()


def test_shard_backends_hold_params_on_their_device():
    """Each shard's backend places the parameters on its device once, on
    first use, and reuses that copy — no later prefill or decode moves
    the weights again; release drops the copy."""
    import jax
    from repro.kvcache.backend import ShardedPagedBackend

    cfg, params = _model()
    dev = jax.devices()[0]
    b = ShardedPagedBackend(cfg, n_shards=2, num_blocks=32, block_size=4,
                            decode_mode="gather", devices=[dev, dev])
    sids = [b.new_seq(params, [1, 2, 3, 4, 5], shard=s)[0] for s in (0, 1)]
    held = [inner._params_dev for inner in b.backends]
    for _ in range(3):
        b.decode(params, sids, [7, 7])
    for inner, h in zip(b.backends, held):
        assert inner._params_dev is h
        assert all(x.committed and x.devices() == {dev}
                   for x in jax.tree.leaves(h))
    b.release()
    assert all(inner._params_dev is None for inner in b.backends)


# ---------------------------------------------------------------------------
# shard-local CoW forks
# ---------------------------------------------------------------------------

def test_fork_stays_shard_local_and_cow_isolates():
    from repro.kvcache.backend import ShardedPagedBackend

    cfg, params = _model()
    backend = ShardedPagedBackend(cfg, n_shards=2, num_blocks=64,
                                  block_size=4, decode_mode="gather")
    sid, _, _ = backend.new_seq(params, list(range(1, 11)), shard=1)
    fid = backend.fork_seq(sid)
    assert backend.shard_of(sid) == backend.shard_of(fid) == 1
    pool1 = backend.pool.shards[1]
    # fork shares every block of the parent, all inside shard 1's pool
    assert backend.table(fid).blocks == backend.table(sid).blocks
    assert all(0 <= b < pool1.cfg.num_blocks and pool1.used[b]
               for b in backend.table(fid).blocks)
    assert backend.pool.shards[0].num_live == 0
    # diverging appends CoW the shared tail within the shard; the
    # parent's payload is untouched
    cow0 = pool1.stats.cow_copies
    backend.decode(params, [sid, fid], [3, 7])
    assert pool1.stats.cow_copies > cow0
    t_s, t_f = backend.table(sid), backend.table(fid)
    assert t_s.blocks[-1] != t_f.blocks[-1]
    assert pool1.content[t_s.blocks[-1]] != pool1.content[t_f.blocks[-1]]
    backend.release()
    backend.pool.check_invariants()


# ---------------------------------------------------------------------------
# soak: admit / fork / free with reservation routing
# ---------------------------------------------------------------------------

def test_sharded_soak_admit_fork_free_invariants(tmp_path):
    """Randomized admit (route + reserve + extend), fork (CoW), and free
    over a sharded metadata pool; every shard's allocator invariants and
    the reservation accounting must hold throughout.  The soak runs fully
    instrumented: every step is a trace span wrapping the shard pools'
    alloc/evict/CoW events, the O(dirty) incremental sweep runs each
    step, and the flushed trace must reconstruct cleanly."""
    import json

    from repro.analysis import refsan
    from repro.obs import Observer

    obs = Observer(paranoid=True)
    rng = np.random.default_rng(0)
    sp = _spool(num_blocks=64, n_shards=4, block_size=4)
    san = refsan.attach(sp)             # per-shard shadow refcounts
    sp.obs = obs
    for i, p in enumerate(sp.shards):
        p.obs = obs
        p.obs_shard = i
        obs.registry.adopt(f"pool.shard{i}", p.stats)
    live = []        # (rid, shard, table)
    next_rid = 0
    def soak_step(step: int) -> None:
        nonlocal next_rid
        r = rng.random()
        if r < 0.45 and len(live) < 12:
            n_tokens = int(rng.integers(1, 20))
            n_blocks = -(-n_tokens // 4)
            if not sp.can_reserve(n_blocks):
                return
            sp.reserve(n_blocks)
            shard = sp.route(next_rid, f"page{rng.integers(4)}", n_blocks)
            if shard is None:
                sp.cancel_pending(n_blocks)   # give up instead of waiting
                return
            t = BlockTable()
            toks = [int(x) for x in rng.integers(0, 99, n_tokens)]
            t.extend(sp.shards[shard], toks, seq_tokens=toks)
            sp.unreserve(n_blocks, rid=next_rid)
            live.append((next_rid, shard, t))
            next_rid += 1
        elif r < 0.65 and live:
            rid, shard, t = live[int(rng.integers(len(live)))]
            if sp.shards[shard].num_free + sp.shards[shard].num_cached > 2:
                f = t.fork(sp.shards[shard])
                live.append((next_rid, shard, f))
                next_rid += 1
        elif live:
            rid, shard, t = live.pop(int(rng.integers(len(live))))
            for b in t.blocks:
                sp.shards[shard].decref(b)

    for step in range(300):
        with obs.trace.span("soak.step", step=step):
            soak_step(step)
        sp.check_invariants(incremental=True)   # O(dirty), every step
        if step % 25 == 0:
            sp.check_invariants()
    for rid, shard, t in live:
        for b in t.blocks:
            sp.shards[shard].decref(b)
    sp.check_invariants()
    assert sp.num_live == 0 and sp.reserved == 0
    san.check(quiesced=True)            # no leaks, no double-frees, no UAF
    san.detach()
    # the adopted per-shard counters are the live stats objects
    snap = obs.snapshot()
    for i, p in enumerate(sp.shards):
        for f in p.stats.fields():
            assert snap["counters"][f"pool.shard{i}.{f}"] == \
                getattr(p.stats, f)
    assert sum(snap["counters"][f"pool.shard{i}.allocs"]
               for i in range(sp.n_shards)) == sp.stats.allocs > 0
    # spans wrapped every pool event: 300 step spans at depth 0, every
    # other event stamped inside some step's [ts, ts+dur] window
    evs = obs.trace.events()
    steps = [e for e in evs if e["ev"] == "soak.step"]
    assert len(steps) == 300
    assert all(e["depth"] == 0 for e in steps)
    spans = [(e["ts"], e["ts"] + e["dur_us"]) for e in steps]
    for e in evs:
        if e["ev"] != "soak.step":
            assert any(lo <= e["ts"] <= hi for lo, hi in spans), e
    # flush drains the ring to parseable JSONL
    path = str(tmp_path / "soak_trace.jsonl")
    n = obs.trace.flush(path)
    assert n == len(evs) and obs.trace.events() == []
    lines = [json.loads(l) for l in open(path)]
    assert len(lines) == n
    assert sum(1 for e in lines if e["ev"] == "pool.alloc") > 0


# ---------------------------------------------------------------------------
# exhaustion isolation
# ---------------------------------------------------------------------------

def test_exhaustion_on_one_shard_rolls_back_and_spares_others():
    """A prefill that exhausts its routed shard must roll back atomically
    on that shard and leave every other shard's pool untouched."""
    from repro.kvcache.backend import ShardedPagedBackend

    cfg, params = _model()
    backend = ShardedPagedBackend(cfg, n_shards=2, num_blocks=16,
                                  block_size=4, decode_mode="gather")
    p0, p1 = backend.pool.shards
    sid1, _, _ = backend.new_seq(params, list(range(50, 60)), shard=1)
    live1 = p1.num_live
    # 8 blocks/shard; 40 tokens need 10 blocks -> shard 0 exhausts
    with pytest.raises(RuntimeError, match="pool exhausted"):
        backend.new_seq(params, list(range(1, 41)), shard=0)
    p0.check_invariants()
    p1.check_invariants()
    assert p0.num_live == 0, "failed prefill leaked blocks on its shard"
    assert p1.num_live == live1, "exhaustion leaked onto another shard"
    # shard 0 still serves a fitting sequence afterwards
    sid2, _, _ = backend.new_seq(params, list(range(1, 9)), shard=0)
    assert backend.shard_of(sid2) == 0
    backend.release()
    backend.pool.check_invariants()
    assert backend.pool.num_live == 0


def test_batch_prefill_exhaustion_rolls_back_across_shards():
    """Batch prefill is atomic across shards too: if a later shard's
    batched ``_add_seqs`` exhausts its pool, rows already prefilled on
    earlier shards must be freed before the error re-raises."""
    import jax
    import jax.numpy as jnp
    from repro.kvcache.backend import ShardedPagedBackend

    cfg, params = _model()
    backend = ShardedPagedBackend(cfg, n_shards=2, num_blocks=8,
                                  block_size=4, decode_mode="gather")
    p0, p1 = backend.pool.shards
    # occupy shard 0 with one block so the planner sends 2 of 3 rows to
    # shard 1 (3 blocks each > 4 blocks/shard -> shard 1 exhausts after
    # shard 0's row already registered)
    backend.new_seq(params, [1, 2, 3], shard=0)
    live0 = (p0.num_live, p1.num_live)
    rows = jax.random.randint(jax.random.key(0), (3, 9), 1, cfg.vocab)
    with pytest.raises(RuntimeError, match="pool exhausted"):
        backend.prefill(params, rows)
    p0.check_invariants()
    p1.check_invariants()
    assert (p0.num_live, p1.num_live) == live0, \
        "cross-shard batch prefill leaked rows on a non-failing shard"
    assert backend._batch == [] and len(backend._seqs) == 1
    # the backend still serves (protocol lanes are rebuildable)
    small = jax.random.randint(jax.random.key(1), (2, 4), 1, cfg.vocab)
    backend.prefill(params, small)
    backend.decode_step(params, jnp.ones((2, 1), jnp.int32))
    backend.release()
    backend.pool.check_invariants()


def test_make_backend_sharded_sizes_whole_lanes_per_shard():
    """The registry's capacity request must survive sharding: a lane
    never spans shards, so each shard holds ceil(batch / n_shards) whole
    lanes — splitting the aggregate block budget would under-size shards
    whenever n_shards does not divide batch."""
    import jax
    from repro.kvcache.backend import ShardedPagedBackend, make_backend
    from repro.models import lm

    cfg, params = _model()
    # 3 lanes of 5 blocks over 2 shards -> 2 lanes/shard -> 20 total
    be = make_backend(cfg, "sharded-paged", batch=3, max_seq=64, n_shards=2)
    assert isinstance(be, ShardedPagedBackend)
    assert be.pool.shard_blocks == 2 * 5 and be.pool.cfg.num_blocks == 20
    # one long lane over 4 shards: the lane's 8 blocks must fit ONE shard
    be = make_backend(cfg, "sharded-paged", batch=1, max_seq=127,
                      n_shards=4)
    assert be.pool.shard_blocks == 8
    tokens = jax.random.randint(jax.random.key(0), (1, 120), 1, cfg.vocab)
    lm.prefill(params, cfg, tokens, backend=be)   # must not exhaust
    assert list(be.lengths) == [120]
    be.release()


def test_decode_precheck_is_atomic_across_shards():
    """Exhaustion on one shard's decode must be detected before ANY shard
    commits its write-back: a caller that catches and retries must not
    double-append KV on the shards that would have gone first."""
    from repro.kvcache.backend import ShardedPagedBackend

    cfg, params = _model()
    backend = ShardedPagedBackend(cfg, n_shards=2, num_blocks=8,
                                  block_size=4, decode_mode="gather",
                                  share_prefixes=False)
    s0, _, _ = backend.new_seq(params, [1, 2, 3, 4, 5], shard=0)
    # fill shard 1 completely: two 8-token sequences = 4/4 blocks live
    s1, _, _ = backend.new_seq(params, list(range(10, 18)), shard=1)
    backend.new_seq(params, list(range(20, 28)), shard=1)
    toks0 = list(backend.table(s0).blocks), backend.table(s0).num_tokens
    # s1's lane needs a fresh tail block (fill == 0) shard 1 cannot give;
    # shard 0 sorts first and must NOT have committed when this raises
    with pytest.raises(RuntimeError, match="pool exhausted on shard 1"):
        backend.decode(params, [s0, s1], [7, 9])
    assert (list(backend.table(s0).blocks),
            backend.table(s0).num_tokens) == toks0, \
        "shard 0 committed a step the batch then aborted"
    backend.pool.check_invariants()
    # the step is retryable once shard 1 has room
    backend.free_seq(s1)
    lg = backend.decode(params, [s0], [7])
    assert lg.shape[0] == 1 and backend.table(s0).num_tokens == 6
    backend.release()


def test_route_with_zero_blocks_keeps_invariants():
    """A degenerate request (empty prompt, max_new=0) reserves 0 blocks;
    routing it must still pick a shard without planting bookkeeping that
    can never be released."""
    sp = _spool(num_blocks=8, n_shards=2)
    sp.reserve(0)
    assert sp.route(rid=7, page="zero", n=0) is not None
    assert 7 not in sp._rid_reserved
    sp.unreserve(0, rid=7)        # no-op, must not KeyError
    sp.check_invariants()


def test_batch_api_accepts_empty_batch():
    """Protocol parity: a (0, S) prefill returns empty logits like the
    dense and single-pool paged backends do."""
    from repro.kvcache.backend import ShardedPagedBackend

    cfg, params = _model()
    backend = ShardedPagedBackend(cfg, n_shards=2, num_blocks=16,
                                  block_size=4, decode_mode="gather")
    lg = backend.prefill(params, np.zeros((0, 8), np.int32))
    assert lg.shape == (0, 1, cfg.vocab)
    assert backend.lengths.shape == (0,)
    backend.release()


def test_page_affinity_map_is_bounded():
    from repro.kvcache import sharded_pool as sm

    sp = _spool(num_blocks=1024, n_shards=2)
    cap = sm.PAGE_AFFINITY_CAP
    for i in range(cap + 50):
        sp.reserve(1)
        assert sp.route(rid=i, page=f"p{i}", n=1) is not None
        sp.unreserve(1, rid=i)
    assert len(sp._page_shard) == cap
    # oldest entries were trimmed, newest survive
    assert "p0" not in sp._page_shard and f"p{cap + 49}" in sp._page_shard
    sp.check_invariants()


# ---------------------------------------------------------------------------
# engine end-to-end over shards
# ---------------------------------------------------------------------------

def test_engine_sharded_serving_matches_dense_greedy():
    """Continuous batching over a 2-shard pool must emit exactly the
    dense backend's greedy tokens — routing, per-shard decode grouping,
    claims, and lane ordering all live under this one assertion."""
    import jax.numpy as jnp
    from repro.kvcache.backend import ShardedPagedBackend
    from repro.serve.engine import PagedLM, ServeEngine
    from repro.serve.step import greedy_generate

    cfg, params = _model()
    backend = ShardedPagedBackend(cfg, n_shards=2, num_blocks=96,
                                  block_size=8, decode_mode="gather")
    eng = ServeEngine(backend.pool, MarsScheduler(pool=backend.pool),
                      PagedLM(params, cfg, backend), max_lanes=3)
    rng = np.random.default_rng(3)
    shared = tuple(int(t) for t in rng.integers(1, cfg.vocab, 16))
    prompts = [shared + tuple(int(t) for t in rng.integers(1, cfg.vocab, 2))
               for _ in range(4)]
    prompts += [tuple(int(t) for t in rng.integers(1, cfg.vocab, 18))
                for _ in range(2)]
    reqs = [Request(rid=i, prompt=p, arrival=i * 1e-3, prefix_len=8,
                    max_new=4) for i, p in enumerate(prompts)]
    out = eng.run(reqs)
    assert sorted(out) == list(range(6))
    # the shared-prefix requests co-located: their shard's prefix cache hit
    assert backend.pool.stats.prefix_hits > 0
    for i, p in enumerate(prompts):
        want = greedy_generate(params, cfg, jnp.asarray([p], jnp.int32),
                               4, max_seq=len(p) + 5)
        assert out[i][0] == list(np.asarray(want[0])), f"lane {i} diverged"
    backend.pool.check_invariants()
    assert backend.pool.num_live == 0 and backend.pool.reserved == 0


def test_batch_lane_order_keeps_shards_distinct():
    """Shard-local block ids collide numerically across shards; the lane
    order key must lead with the shard coordinate so same-id lanes on
    different shards are not treated as row-group neighbors."""
    from repro.kernels.paged_attention import ops

    t0 = BlockTable(blocks=[0], num_tokens=4)    # shard 0, group 0
    t1 = BlockTable(blocks=[1], num_tokens=4)    # shard 1, group 0
    t2 = BlockTable(blocks=[2], num_tokens=4)    # shard 0, group 0
    order = ops.batch_lane_order([t0, t1, t2], blocks_per_group=8,
                                 shard_ids=[0, 1, 0])
    grouped = [([0, 1, 0][i]) for i in order]
    # lanes of each shard end up adjacent (0s together, the 1 alone)
    assert grouped in ([0, 0, 1], [1, 0, 0])
    # without shard ids all three share group 0 -> order stays FIFO
    assert list(ops.batch_lane_order([t0, t1, t2], 8)) == [0, 1, 2]
