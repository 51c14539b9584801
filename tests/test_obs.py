"""Observability layer: registry/facade semantics, trace spans, the
incremental open-row model vs the DRAM reference, shard load snapshots,
the O(dirty) incremental pool sweep, and the Observer end-to-end."""
import glob
import json

import jax
import numpy as np
import pytest

from repro.core import dram
from repro.kernels.paged_attention import ops
from repro.kvcache.pool import BlockPool, PoolConfig, PoolStats
from repro.kvcache.prefix import BlockTable
from repro.kvcache.sharded_pool import ShardedBlockPool
from repro.obs import (Counter, Histogram, MetricsRegistry, Observer,
                       OpenRowCounter, StatGroup, TraceLog,
                       shard_load_snapshot)
from repro.obs.trace import span
from repro.serve.engine import ServeEngine
from repro.serving.scheduler import MarsScheduler, Request


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------

def test_counter_is_monotonic():
    c = Counter()
    c.inc(); c.inc(2)
    assert c.value == 3
    with pytest.raises(ValueError):
        c.inc(-1)
    assert c.value == 3


def test_registry_get_or_create_and_kind_mismatch():
    reg = MetricsRegistry()
    assert reg.counter("a.b") is reg.counter("a.b")
    with pytest.raises(TypeError):
        reg.gauge("a.b")
    with pytest.raises(TypeError):
        reg.histogram("a.b")


def test_histogram_bucket_edges_and_quantiles():
    h = Histogram(edges=(1.0, 2.0, 4.0))
    for v in [0.5] * 50 + [3.0] * 50:
        h.observe(v)
    assert h.counts == [50, 0, 50, 0]
    # p50 sits at the top of the first bucket (0..1), p99 interpolates
    # inside the (2..4] bucket: 2 + 2 * (99-50)/50
    assert h.quantile(0.50) == pytest.approx(1.0)
    assert h.quantile(0.99) == pytest.approx(3.96)
    # an exact edge value lands in the bucket it bounds (bisect_left)
    h2 = Histogram(edges=(1.0, 2.0))
    h2.observe(2.0)
    assert h2.counts == [0, 1, 0]


def test_histogram_overflow_clamps_to_last_edge():
    h = Histogram(edges=(1.0, 2.0))
    h.observe(100.0)
    assert h.counts[-1] == 1
    assert h.quantile(0.99) == 2.0
    snap = h.to_snapshot()
    assert snap["count"] == 1 and snap["sum"] == 100.0


def test_snapshot_is_deterministic_across_insertion_order():
    a, b = MetricsRegistry(), MetricsRegistry()
    a.inc("x.one", 2); a.set("y.g", 0.25); a.observe("z.h", 1.5)
    b.observe("z.h", 1.5); b.inc("x.one", 2); b.set("y.g", 0.25)
    assert json.dumps(a.snapshot(), sort_keys=True) == \
        json.dumps(b.snapshot(), sort_keys=True)


def test_adopt_aliases_the_live_counters():
    class S(StatGroup):
        FIELDS = {"allocs": 0}
    reg = MetricsRegistry()
    s = S()
    reg.adopt("pool", s)
    s.allocs += 3
    assert reg.snapshot()["counters"]["pool.allocs"] == 3
    reg.adopt("pool", s)                      # idempotent
    with pytest.raises(ValueError):           # same name, different group
        reg.adopt("pool", S())


def test_statgroup_facade_keeps_dataclass_ergonomics():
    s = PoolStats(allocs=2)
    assert s.allocs == 2 and s.frees == 0
    s.evictions += 5
    assert s.as_dict()["evictions"] == 5
    assert s == PoolStats(allocs=2, evictions=5)
    assert "evictions=5" in repr(s)
    assert set(s.fields()) == set(PoolStats.FIELDS)
    with pytest.raises(TypeError):
        PoolStats(bogus=1)
    with pytest.raises(AttributeError):
        s.bogus = 1
    with pytest.raises(AttributeError):
        s.bogus


# ---------------------------------------------------------------------------
# trace spans
# ---------------------------------------------------------------------------

def _fake_clock(step_us: float = 10.0):
    t = [0.0]

    def clk():
        t[0] += step_us * 1e-6
        return t[0]
    return clk


def test_trace_spans_nest_and_time_deterministically():
    t = TraceLog(clock=_fake_clock())
    with t.span("outer") as sp:
        sp["k"] = 1
        t.event("point", rid=7)
        with t.span("inner"):
            pass
    evs = t.events()
    assert [e["ev"] for e in evs] == ["outer", "point", "inner"]
    outer, point, inner = evs
    assert outer["depth"] == 0 and inner["depth"] == 1
    assert outer["k"] == 1 and point["rid"] == 7
    # fake clock ticks 10us per read: spans carry entry ts + duration
    assert outer["ts"] < point["ts"] < inner["ts"]
    assert outer["dur_us"] > inner["dur_us"] > 0


def _host_events(tmp_path, fn) -> list:
    """``(name, start_ns, end_ns, stats)`` of every program span (names
    ``engine.*`` and ``backend.*``) a CPU profiler trace of ``fn()``
    holds."""
    jax.profiler.start_trace(str(tmp_path))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    pd = jax.profiler.ProfileData.from_file(path)
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
            for plane in pd.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name.startswith(("engine.", "backend."))]


def test_span_lands_in_the_profiler_with_its_fields(tmp_path):
    def run():
        with span("backend.dispatch", step=3, lanes=4):
            with span("backend.stage", step=3, lanes=4):
                pass
    evs = {e[0]: e for e in _host_events(tmp_path, run)}
    outer, inner = evs["backend.dispatch"], evs["backend.stage"]
    assert outer[3] == {"step": 3, "lanes": 4}
    assert outer[1] <= inner[1] and inner[2] <= outer[2]


def test_span_writes_jsonl_only_given_a_log():
    log = TraceLog(clock=_fake_clock())
    with span("engine.prefill", rid=1) as sp:
        sp["lanes"] = 2                       # a scratch dict, no log
    assert log.total == 0
    with span("engine.prefill", log, rid=1) as sp:
        sp["lanes"] = 2
    ev, = log.events()
    assert (ev["ev"], ev["rid"], ev["lanes"], ev["depth"]) == \
        ("engine.prefill", 1, 2, 0)
    assert ev["dur_us"] > 0


# the program's spans and where each sits: the innermost span around it
SPAN_PARENTS = {
    "engine.schedule": {"engine.step"},
    "engine.prefill": {"engine.step"},
    "engine.lane_order": {"engine.step"},
    "engine.sample": {"engine.step"},
    "backend.flush": {"engine.step", "engine.prefill"},
    "backend.prefill": {"engine.prefill"},
    "backend.prefill.wait": {"backend.prefill"},
    "backend.prefill.fetch": {"backend.prefill"},
    "backend.prefill.store": {"backend.prefill"},
    "backend.commit": {"backend.flush"},
    "backend.commit.fetch": {"backend.commit"},
    "backend.commit.store": {"backend.commit"},
    "backend.dispatch": {"engine.step"},
    "backend.stage": {"backend.dispatch"},
    "backend.state_pack": {"backend.dispatch"},
    "backend.launch": {"backend.dispatch"},
    "backend.decode": {"engine.step"},
    "backend.decode.wait": {"backend.decode"},
    "backend.decode.fetch": {"backend.decode"},
}


@pytest.mark.parametrize("arch", ["qwen1_5_0_5b", "hymba_1_5b"])
def test_served_run_holds_every_span_nested(tmp_path, arch):
    """A pipelined served run under the profiler: every program span
    occurs, each inside the span the catalogue says, and ``engine.step``
    carries the backend's byte counters at entry."""
    from repro import configs
    from repro.models import lm
    from repro.serve.engine import make_paged_lm
    cfg = configs.get_smoke(arch)
    params = lm.init(cfg, jax.random.key(0)).params
    model = make_paged_lm(params, cfg, num_blocks=64, block_size=4,
                          decode_mode="gather")
    pool = model.backend.pool
    eng = ServeEngine(pool, MarsScheduler(pool=pool), model, max_lanes=4)
    # prompts of whole SSM chunks (8 tokens), one or two of them
    reqs = [Request(rid=i, prompt=tuple(range(1 + i, 9 + i + 8 * (i % 2))),
                    arrival=i * 1e-3, max_new=4) for i in range(4)]
    evs = _host_events(tmp_path, lambda: eng.run(reqs))
    names = {e[0] for e in evs}
    want = set(SPAN_PARENTS) | {"engine.step"}
    if not cfg.has_ssm:
        want.discard("backend.state_pack")
    assert names == want
    for name, s, t, _ in evs:
        around = [e for e in evs if e[1] <= s and t <= e[2]
                  and (e[1], e[2]) != (s, t)]
        if name == "engine.step":
            assert not around
            continue
        parent = max(around, key=lambda e: (e[1], -e[2]))[0]
        assert parent in SPAN_PARENTS[name], (name, parent)
    steps = [e[3] for e in evs if e[0] == "engine.step"]
    assert steps[0]["h2d_bytes"] == 0
    assert steps[-1]["h2d_bytes"] > 0 and steps[-1]["decode_steps"] > 0
    assert [st["step_num"] for st in steps] == list(range(len(steps)))


def test_trace_ring_drops_oldest_and_counts():
    t = TraceLog(capacity=4, clock=_fake_clock())
    for i in range(6):
        t.event("e", i=i)
    assert t.total == 6 and t.dropped == 2
    assert [e["i"] for e in t.events()] == [2, 3, 4, 5]


def test_trace_flush_appends_jsonl_and_clears(tmp_path):
    t = TraceLog(clock=_fake_clock())
    t.event("a"); t.event("b")
    path = str(tmp_path / "trace.jsonl")
    assert t.flush(path) == 2
    assert t.events() == []
    t.event("c")
    assert t.flush(path) == 1
    lines = [json.loads(l) for l in open(path)]
    assert [e["ev"] for e in lines] == ["a", "b", "c"]
    assert all(isinstance(e["ts"], int) for e in lines)


# ---------------------------------------------------------------------------
# incremental open-row model vs the DRAM reference
# ---------------------------------------------------------------------------

def _churned_tables(placement="mars", num_blocks=256, n_live=12, seed=0):
    """Fragment a pool realistically, return (pool, live decode tables)."""
    rng = np.random.default_rng(seed)
    pool = BlockPool(PoolConfig(num_blocks=num_blocks, placement=placement))
    live = []

    def start():
        t = BlockTable()
        for _ in range(int(rng.integers(2, 7))):
            t.blocks.append(pool.alloc(1, hint_blocks=t.blocks)[0])
        t.num_tokens = len(t.blocks) * pool.cfg.block_size
        live.append(t)

    for _ in range(200):
        if len(live) >= n_live or (live and rng.random() < 0.5):
            for b in live.pop(int(rng.integers(len(live)))).blocks:
                pool.decref(b)
        else:
            start()
    while len(live) < n_live:
        start()
    return pool, live


def _sim_hit_rate(trace) -> float:
    res = dram.simulate(trace)
    return 1.0 - res.n_act / max(res.n_requests, 1)


def test_inorder_model_matches_dram_on_kernel_walk():
    """On the kernel decode path's sequence-major page walk the stream has
    no interleaving left for FR-FCFS lookahead to exploit, so the O(n)
    in-order model must match the full windowed controller replay —
    this is what licenses the live gauge (pinned to within 0.1%)."""
    pool, tables = _churned_tables()
    trace = np.asarray(ops.kv_read_trace_kernel(
        tables, block_size=pool.cfg.block_size))
    rc = OpenRowCounter()
    for i in range(0, len(trace), 173):      # incremental, odd chunking
        rc.observe(trace[i:i + 173])
    assert rc.served == len(trace)
    assert abs(rc.row_hit_rate - _sim_hit_rate(trace)) < 1e-3


def test_inorder_model_is_chunking_invariant():
    pool, tables = _churned_tables(seed=3)
    trace = np.asarray(ops.kv_read_trace_kernel(
        tables, block_size=pool.cfg.block_size))
    one = OpenRowCounter(); one.observe(trace)
    chunked = OpenRowCounter()
    for i in range(0, len(trace), 7):
        chunked.observe(trace[i:i + 7])
    assert (one.hits, one.served) == (chunked.hits, chunked.served)


def test_windowed_model_matches_dram_on_interleaved_trace():
    """The gather path's round-robin interleave is where in-order and
    FR-FCFS genuinely diverge; the windowed replay mode must still
    reproduce the controller's hit accounting exactly."""
    pool, tables = _churned_tables(seed=1)
    trace = np.asarray(ops.kv_read_trace(tables, grant_beats=4))
    inorder = OpenRowCounter(); inorder.observe(trace)
    win = OpenRowCounter(window=int(dram.DramConfig().window))
    for i in range(0, len(trace), 61):
        win.observe(trace[i:i + 61])
    win.drain()
    assert win.served == len(trace)
    assert win.row_hit_rate == pytest.approx(_sim_hit_rate(trace), abs=1e-9)
    # and lookahead really buys hits on this trace
    assert win.row_hit_rate > inorder.row_hit_rate


def test_rowsim_rejects_bad_window_and_handles_empty():
    with pytest.raises(ValueError):
        OpenRowCounter(window=0)
    rc = OpenRowCounter()
    rc.observe(np.empty(0, np.int64))
    assert rc.row_hit_rate == 0.0 and rc.served == 0


# ---------------------------------------------------------------------------
# shard load snapshot
# ---------------------------------------------------------------------------

def test_shard_load_snapshot_single_pool():
    pool = BlockPool(PoolConfig(num_blocks=16, block_size=4))
    pool.alloc(3)
    pool.reserve(2)
    reg = MetricsRegistry()
    (row,) = shard_load_snapshot(pool, reg)
    assert row == {"shard": 0, "blocks": 16, "live": 3, "cached": 0,
                   "free": 13, "reserved": 2, "load": 5, "headroom": 11,
                   "occupancy": 3 / 16}
    g = reg.snapshot()["gauges"]
    assert g["pool.shard0.load"] == 5
    assert g["pool.shard0.occupancy"] == pytest.approx(3 / 16)


def test_shard_load_snapshot_headroom_is_can_reserve():
    sp = ShardedBlockPool(PoolConfig(num_blocks=32, block_size=4),
                          n_shards=2)
    sp.shards[0].alloc(5)
    sp.shards[1].reserve(3)
    rows = shard_load_snapshot(sp)
    assert [r["shard"] for r in rows] == [0, 1]
    for row, shard in zip(rows, sp.shards):
        # the headroom column is definitionally the reservation capacity
        assert shard.can_reserve(row["headroom"])
        assert not shard.can_reserve(row["headroom"] + 1)
        assert row["load"] == shard.num_live + shard.reserved


# ---------------------------------------------------------------------------
# incremental pool invariants (--paranoid)
# ---------------------------------------------------------------------------

def test_incremental_sweep_is_o_dirty_and_clears():
    pool = BlockPool(PoolConfig(num_blocks=32, block_size=4))
    bids = pool.alloc(4)
    assert set(bids) <= pool._meta_dirty
    pool.check_invariants(incremental=True)
    assert not pool._meta_dirty               # consumed by the sweep
    pool.decref(bids[0])
    assert pool._meta_dirty == {bids[0]}      # only the touched block
    pool.check_invariants(incremental=True)
    pool.check_invariants()                   # full sweep still clean


def test_incremental_sweep_catches_planted_corruption():
    pool = BlockPool(PoolConfig(num_blocks=32, block_size=4))
    bids = pool.alloc(2)
    pool.check_invariants(incremental=True)
    pool.refcount[bids[1]] = 0                # live block, refcount zeroed
    pool._meta_dirty.add(bids[1])
    with pytest.raises(AssertionError):
        pool.check_invariants(incremental=True)
    pool.refcount[bids[1]] = 1                # repair; sweep passes again
    pool._meta_dirty.add(bids[1])
    pool.check_invariants(incremental=True)


def test_incremental_sweep_catches_aggregate_drift():
    pool = BlockPool(PoolConfig(num_blocks=16, block_size=4))
    pool.alloc(2)
    pool.used[5] = True                       # used without leaving free
    with pytest.raises(AssertionError):
        pool.check_invariants(incremental=True)


# ---------------------------------------------------------------------------
# Observer end-to-end (toy engine)
# ---------------------------------------------------------------------------

class _RecObserver(Observer):
    """Observer that also records every kv walk it is fed, so tests can
    replay the exact concatenated stream through ``dram.simulate``."""

    def __init__(self, **kw):
        super().__init__(**kw)
        self.walks = []

    def observe_kv_walk(self, shard, addrs):
        self.walks.append(np.asarray(addrs))
        super().observe_kv_walk(shard, addrs)


def _toy_served(obs_cls=Observer, **obs_kw):
    pool = BlockPool(PoolConfig(num_blocks=96, block_size=16,
                                n_kv_heads=2, head_dim=32))
    eng = ServeEngine(pool, MarsScheduler(pool=pool), max_lanes=4)
    obs = obs_cls(**obs_kw).attach(eng)
    rng = np.random.default_rng(0)
    pref = tuple(int(t) for t in rng.integers(1, 100, 20))
    reqs = [Request(rid=i,
                    prompt=pref + tuple(int(t) for t in
                                        rng.integers(1, 100, 3)),
                    arrival=i * 1e-3, prefix_len=16, max_new=5,
                    n_samples=3 if i == 2 else 1)
            for i in range(8)]
    out = eng.run(reqs)
    assert sorted(out) == list(range(8))
    return eng, obs


def test_observer_live_row_gauge_matches_dram_replay():
    """The ISSUE parity gate: the running row-hit gauge (incremental
    in-order model, open rows carried across steps) must agree with a
    ``dram.simulate`` replay of the concatenated per-step kernel walks
    to within 0.1%."""
    eng, obs = _toy_served(_RecObserver, paranoid=True, paranoid_every=2)
    gauge = obs.registry.gauge("dram.row_hit_pct").value
    replay = 100.0 * _sim_hit_rate(np.concatenate(obs.walks))
    assert abs(gauge - replay) < 0.1
    assert obs.registry.counter("dram.kv_lines").value == \
        sum(len(w) for w in obs.walks)


def test_observer_snapshot_aliases_component_stats():
    eng, obs = _toy_served()
    snap = obs.snapshot()
    c, g, h = snap["counters"], snap["gauges"], snap["histograms"]
    # adopted counters read the very numbers the components hold
    assert c["engine.decode_tokens"] == eng.stats.decode_tokens == 10 * 5
    assert c["engine.prefill_tokens"] == eng.stats.prefill_tokens
    assert c["pool.allocs"] == eng.pool.stats.allocs
    assert c["sched.scheduled"] == eng.scheduler.stats.scheduled == 8
    assert h["engine.step_ms"]["count"] == eng.stats.steps
    assert h["engine.step_ms"]["p50"] <= h["engine.step_ms"]["p99"]
    assert 0.0 <= g["kvcache.prefix_hit_rate"] <= 1.0
    assert g["kvcache.prefix_hit_rate"] > 0   # the shared prefix was hit
    assert snap["trace"]["events"] == obs.trace.total
    assert snap["trace"]["dropped"] == 0


def test_observer_trace_reconstructs_request_lifecycle():
    eng, obs = _toy_served()
    evs = [e for e in obs.trace.events() if e.get("rid") == 2]
    names = [e["ev"] for e in evs]
    order = [names.index(k) for k in ("sched.offer", "engine.admit",
                                      "engine.prefill", "engine.token",
                                      "engine.free")]
    assert order == sorted(order)
    assert names.count("engine.token") == 3 * 5      # 3 forks x 5 tokens
    assert names.count("engine.free") == 3
    prefill = next(e for e in evs if e["ev"] == "engine.prefill")
    assert prefill["lanes"] == 3 and prefill["dur_us"] >= 0


def test_observer_off_leaves_no_trace_hooks():
    """Uninstrumented serving must not grow any obs state (the hot-path
    contract: one attribute test when obs is None)."""
    pool = BlockPool(PoolConfig(num_blocks=96, block_size=16,
                                n_kv_heads=2, head_dim=32))
    eng = ServeEngine(pool, MarsScheduler(pool=pool), max_lanes=4)
    assert eng.obs is None and pool.obs is None
    eng.run([Request(rid=0, prompt=tuple(range(1, 20)), prefix_len=16,
                     max_new=3)])
    assert eng.obs is None
    assert eng.stats.decode_tokens == 3
