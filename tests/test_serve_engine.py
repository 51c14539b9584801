"""Serving scheduler coverage (page-major order, starvation, pool-capacity
admission) and the continuous-batching engine over the paged pool."""
import numpy as np
import pytest

from repro.kvcache import BlockPool, PoolConfig
from repro.serve.engine import ServeEngine
from repro.serving.scheduler import MarsScheduler, Request


def _req(rid, prompt, max_new=4, arrival=None):
    return Request(rid=rid, prompt=tuple(prompt),
                   arrival=rid * 1e-3 if arrival is None else arrival,
                   prefix_len=4, max_new=max_new)


def _prefix(i):
    return (i * 1000 + 1, i * 1000 + 2, i * 1000 + 3, i * 1000 + 4)


# ---------------------------------------------------------------------------
# scheduler: page-major batch order
# ---------------------------------------------------------------------------

def test_batches_are_page_major_oldest_first():
    sched = MarsScheduler(mars=True)
    # interleaved arrivals: pages 0,1,2,0,1,2,...
    reqs = [_req(i, _prefix(i % 3) + (100 + i,)) for i in range(12)]
    for r in reqs:
        assert sched.offer(r)
    batch = sched.schedule_batch(8, now=1.0)
    pages = [r.page for r in batch]
    # page-major: each page appears as one contiguous run
    runs = [p for i, p in enumerate(pages) if i == 0 or pages[i - 1] != p]
    assert len(runs) == len(set(pages))
    # oldest page first, FIFO within a page
    assert batch[0].page == reqs[0].page
    rids = [r.rid for r in batch if r.page == reqs[0].page]
    assert rids == sorted(rids)


def test_no_starvation_under_adversarial_arrival():
    """A lone cold request must not wait forever while a hot page keeps
    refilling (oldest-page-first drains to exhaustion, then moves on)."""
    sched = MarsScheduler(mars=True)
    hot = 0
    cold = _req(999, _prefix(7) + (5,))
    assert sched.offer(_req(hot, _prefix(1) + (hot,))); hot += 1
    assert sched.offer(cold)
    waited = 0
    for _ in range(50):
        # adversary: keep the hot page full
        for _ in range(4):
            sched.offer(_req(hot, _prefix(1) + (hot,))); hot += 1
        batch = sched.schedule_batch(4, now=1.0)
        assert batch
        if any(r.rid == 999 for r in batch):
            break
        waited += 1
    else:
        pytest.fail("cold request starved")
    assert waited <= 2   # bounded delay: scheduled once its page is oldest


# ---------------------------------------------------------------------------
# scheduler: pool-capacity admission
# ---------------------------------------------------------------------------

def test_pool_admission_bounds_accepts():
    pool = BlockPool(PoolConfig(num_blocks=16, block_size=4))
    sched = MarsScheduler(pool=pool)
    # each request needs ceil((6 + 4)/4) = 3 blocks -> only 5 fit
    reqs = [_req(i, _prefix(i) + (1, 2)) for i in range(10)]
    accepted = [r for r in reqs if sched.offer(r)]
    assert len(accepted) == 5
    assert sched.stats.pool_rejects == 5
    assert pool.reserved == 15
    # reservations outlive scheduling: the engine converts them into real
    # allocations as sequences grow and releases the rest at finish
    batch = sched.schedule_batch(8, now=1.0)
    assert len(batch) == 5 and pool.reserved == 15


def test_admission_accounts_live_blocks():
    pool = BlockPool(PoolConfig(num_blocks=16, block_size=4))
    pool.alloc(12)                      # live KV already in the pool
    sched = MarsScheduler(pool=pool)
    assert sched.offer(_req(0, _prefix(0) + (1, 2)))     # needs 3: fits
    assert not sched.offer(_req(1, _prefix(1) + (1, 2)))  # needs 3 more: no
    assert sched.stats.pool_rejects == 1


# ---------------------------------------------------------------------------
# engine: continuous batching end-to-end
# ---------------------------------------------------------------------------

def _engine(num_blocks=96, max_lanes=4):
    pool = BlockPool(PoolConfig(num_blocks=num_blocks, block_size=16,
                                n_kv_heads=2, head_dim=32))
    return ServeEngine(pool, MarsScheduler(pool=pool), max_lanes=max_lanes)


def test_engine_serves_all_and_frees_everything():
    rng = np.random.default_rng(0)
    pref = tuple(rng.integers(1, 100, 20).tolist())
    reqs = [_req(i, pref + tuple(rng.integers(1, 100, 3).tolist()),
                 max_new=5) for i in range(12)]
    eng = _engine()
    out = eng.run(reqs)
    assert sorted(out) == list(range(12))
    assert all(len(v[0]) == 5 for v in out.values())
    eng.pool.check_invariants()
    assert eng.pool.num_live == 0
    assert eng.pool.stats.prefix_hits > 0       # shared prompt prefix


def test_engine_prefix_sharing_is_transparent():
    """Served tokens are identical with and without a cache-warm pool."""
    prompt = tuple(range(1, 25))
    cold = _engine().run([_req(0, prompt, max_new=6)])
    warm_eng = _engine()
    warm = warm_eng.run([_req(0, prompt, max_new=6),
                         _req(1, prompt, max_new=6)])
    assert warm_eng.pool.stats.prefix_hits > 0
    assert cold[0] == warm[0] == warm[1]


def test_engine_forks_cow_and_diverge():
    r = Request(rid=0, prompt=tuple(range(1, 20)), prefix_len=4,
                max_new=5, n_samples=3)
    eng = _engine()
    out = eng.run([r])
    assert len(out[0]) == 3
    assert len({tuple(t) for t in out[0]}) == 3  # salts diverge the samples
    assert eng.pool.stats.cow_copies > 0         # forked tails were CoW'd
    eng.pool.check_invariants()
    assert eng.pool.num_live == 0


def test_engine_reservation_covers_lazy_decode_blocks():
    """Admission must not over-commit: blocks a running sequence will
    allocate mid-decode stay reserved until it finishes (regression for a
    crash where reservations were dropped at schedule time)."""
    pool = BlockPool(PoolConfig(num_blocks=4, block_size=16,
                                n_kv_heads=2, head_dim=32))
    eng = ServeEngine(pool, MarsScheduler(pool=pool), max_lanes=4)
    a = _req(0, tuple(range(100, 116)), max_new=18)   # needs 3 blocks
    b = _req(1, tuple(range(200, 215)), max_new=16)   # needs 2 blocks
    out = eng.run([a, b])
    assert sorted(out) == [0, 1]
    pool.check_invariants()
    assert pool.reserved == 0 and pool.num_live == 0


def test_engine_fork_reservation_counts_every_sample():
    """n_samples multiplies the worst-case block need at admission
    (regression for a mid-decode pool-exhausted crash on forks)."""
    # needs 2 blocks x 3 samples = 6 > 3: rejected up front, clean error
    pool = BlockPool(PoolConfig(num_blocks=3, block_size=16,
                                n_kv_heads=2, head_dim=32))
    eng = ServeEngine(pool, MarsScheduler(pool=pool), max_lanes=4)
    r = Request(rid=0, prompt=tuple(range(1, 17)), max_new=4, n_samples=3)
    with pytest.raises(RuntimeError, match="needs 6 blocks"):
        eng.run([r])
    # exactly enough capacity: must serve all forks without exhaustion
    pool = BlockPool(PoolConfig(num_blocks=6, block_size=16,
                                n_kv_heads=2, head_dim=32))
    eng = ServeEngine(pool, MarsScheduler(pool=pool), max_lanes=4)
    out = eng.run([Request(rid=0, prompt=tuple(range(1, 17)), max_new=4,
                           n_samples=3)])
    assert len(out[0]) == 3
    pool.check_invariants()
    assert pool.reserved == 0 and pool.num_live == 0


def test_engine_lane_budget_counts_forked_samples():
    """running lanes never exceed max_lanes even when requests fan out
    into n_samples forks (regression: forks used to multiply the batch)."""
    eng = _engine(num_blocks=96, max_lanes=4)
    reqs = [Request(rid=i, prompt=tuple(range(10 * i + 1, 10 * i + 17)),
                    max_new=4, n_samples=4) for i in range(4)]
    for r in reqs:
        assert eng.submit(r)
    for step_i in range(200):
        eng.step(now=float(step_i))
        assert len(eng.running) <= 4
        if not eng.running and not len(eng.scheduler):
            break
    assert sorted(eng.finished) == [0, 1, 2, 3]
    # a fan-out wider than the lane budget can never run: clean error
    eng = _engine(max_lanes=2)
    with pytest.raises(RuntimeError, match="max_lanes"):
        eng.run([Request(rid=9, prompt=tuple(range(1, 17)), max_new=2,
                         n_samples=3)])


def test_step_is_noop_when_idle():
    """No active sequences and nothing queued: step() must not run an
    empty prefill/decode round (scheduler stats untouched, 0 returned)."""
    eng = _engine()
    for _ in range(3):
        assert eng.step(now=1.0) == 0
    assert eng.stats.steps == 0 and eng.stats.prefills == 0
    assert eng.scheduler.stats.batches == 0
    assert eng.scheduler.stats.scheduled == 0


def test_stats_count_prefill_and_decode_tokens_separately():
    reqs = [_req(i, _prefix(i) + tuple(range(10 + i, 16)), max_new=3)
            for i in range(4)]
    eng = _engine()
    eng.run(reqs)
    assert eng.stats.prefill_tokens == sum(len(r.prompt) for r in reqs)
    assert eng.stats.decode_tokens == 4 * 3


def test_token_accounting_counts_forked_lanes_once_each():
    """The commit-path contract: prompt tokens count once per *request*
    (forks share the prefill), decode tokens once per *sequence stepped*
    — so a 3-sample fork contributes 3x max_new decode tokens."""
    reqs = [_req(0, _prefix(0) + (7, 8), max_new=4),
            Request(rid=1, prompt=_prefix(1) + (9, 10), arrival=1e-3,
                    prefix_len=4, max_new=4, n_samples=3)]
    eng = _engine()
    out = eng.run(reqs)
    assert len(out[1]) == 3
    assert eng.stats.prefills == 2
    assert eng.stats.prefill_tokens == sum(len(r.prompt) for r in reqs)
    assert eng.stats.decode_tokens == (1 + 3) * 4


# ---------------------------------------------------------------------------
# engine: real multi-layer LM through PagedBackend
# ---------------------------------------------------------------------------

def _lm_engine(num_blocks=96, max_lanes=3, block_size=8,
               decode_mode="gather", f32=False):
    import dataclasses
    import jax
    from repro import configs
    from repro.kvcache.backend import PagedBackend
    from repro.models import lm
    from repro.serve.engine import PagedLM

    cfg = configs.get_smoke("qwen1_5_0_5b")
    if f32:
        cfg = dataclasses.replace(cfg, param_dtype="float32",
                                  compute_dtype="float32")
    params = lm.init(cfg, jax.random.key(0)).params
    backend = PagedBackend(cfg, num_blocks=num_blocks,
                           block_size=block_size, decode_mode=decode_mode)
    eng = ServeEngine(backend.pool, MarsScheduler(pool=backend.pool),
                      PagedLM(params, cfg, backend), max_lanes=max_lanes)
    return eng, cfg, params


def test_engine_real_lm_matches_dense_greedy():
    """Continuous-batched paged serving of a real 2-layer config must emit
    exactly the dense backend's greedy tokens, lane for lane (gather-path
    decode: bit-identical math to the dense backend)."""
    import jax.numpy as jnp
    from repro.serve.step import greedy_generate

    eng, cfg, params = _lm_engine()
    rng = np.random.default_rng(3)
    shared = tuple(int(t) for t in rng.integers(1, cfg.vocab, 16))
    prompts = [shared + tuple(int(t) for t in rng.integers(1, cfg.vocab, 2))
               for _ in range(6)]
    reqs = [Request(rid=i, prompt=p, arrival=i * 1e-3, prefix_len=8,
                    max_new=4) for i, p in enumerate(prompts)]
    out = eng.run(reqs)
    assert sorted(out) == list(range(6))
    assert eng.pool.stats.prefix_hits > 0      # storage-shared hot prefix
    for i, p in enumerate(prompts):
        want = greedy_generate(params, cfg, jnp.asarray([p], jnp.int32),
                               4, max_seq=len(p) + 5)
        assert out[i][0] == list(np.asarray(want[0])), f"lane {i} diverged"
    eng.pool.check_invariants()
    assert eng.pool.num_live == 0 and eng.pool.reserved == 0


def test_engine_real_lm_kernel_decode_matches_dense_greedy():
    """Kernel-path decode (per-layer Pallas paged_attention over the pool)
    through the full engine loop must emit exactly the dense backend's
    greedy tokens in f32 compute — the tentpole invariant end-to-end."""
    import jax.numpy as jnp
    from repro.serve.step import greedy_generate

    eng, cfg, params = _lm_engine(decode_mode="kernel", f32=True)
    assert eng.use_kernel
    rng = np.random.default_rng(5)
    shared = tuple(int(t) for t in rng.integers(1, cfg.vocab, 16))
    prompts = [shared + tuple(int(t) for t in rng.integers(1, cfg.vocab, 2))
               for _ in range(4)]
    reqs = [Request(rid=i, prompt=p, arrival=i * 1e-3, prefix_len=8,
                    max_new=3) for i, p in enumerate(prompts)]
    out = eng.run(reqs)
    assert sorted(out) == list(range(4))
    for i, p in enumerate(prompts):
        want = greedy_generate(params, cfg, jnp.asarray([p], jnp.int32),
                               3, max_seq=len(p) + 4)
        assert out[i][0] == list(np.asarray(want[0])), f"lane {i} diverged"
    eng.pool.check_invariants()
    assert eng.pool.num_live == 0 and eng.pool.reserved == 0


def test_engine_real_lm_forks_cow_and_diverge():
    eng, cfg, _ = _lm_engine()
    r = Request(rid=0, prompt=tuple(range(1, 20)), prefix_len=8,
                max_new=4, n_samples=3)
    out = eng.run([r])
    assert len(out[0]) == 3
    assert len({tuple(t) for t in out[0]}) == 3  # salts diverge the samples
    assert eng.pool.stats.cow_copies > 0         # forked tails were CoW'd
    eng.pool.check_invariants()
    assert eng.pool.num_live == 0


def test_lane_order_runs_no_device_program(tmp_path):
    """Decode lanes are ordered on the host: a step at 3 lanes after steps
    at 4 (one power-of-two decode shape) compiles nothing, and the
    ``engine.lane_order`` profiler event carries ``groups`` and ``moved``
    — those of ``mars_order`` over the step's lane keys."""
    import glob
    import jax
    from repro.core.reorder import mars_order
    from repro.kernels.paged_attention import ops

    jax.clear_caches()       # earlier tests may have compiled any lane count
    eng, cfg, _ = _lm_engine(max_lanes=4)
    rng = np.random.default_rng(0)
    # 4-token prompts, so every step fits one page; two samples of the
    # first request share a row group, the third request ends first
    for i, (n_samples, max_new) in enumerate([(2, 5), (1, 5), (1, 3)]):
        eng.submit(Request(
            rid=i, prompt=tuple(int(t) for t in rng.integers(1, cfg.vocab, 4)),
            arrival=i * 1e-3, max_new=max_new, n_samples=n_samples))
    for _ in range(3):       # prefill, then two decodes at 4 lanes
        assert eng.step() == 4
    assert [s.rid for s in eng.running] == [0, 0, 1]
    # an arrival order the lane order has to move: the samples apart
    eng.running = [eng.running[i] for i in (0, 2, 1)]
    keys = ops.lane_keys([s.table for s in eng.running],
                         eng.pool.cfg.blocks_per_group)
    lanes = list(eng.running)

    compiles = []

    def on_duration(event, secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(secs)

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.profiler.start_trace(str(tmp_path))
    try:
        assert eng.step() == 3
    finally:
        jax.profiler.stop_trace()
        jax.monitoring.unregister_event_duration_listener(on_duration)
    assert compiles == []
    # the reference runs only now: its own programs are not the step's
    want = np.asarray(mars_order(keys))
    want_fields = {"lanes": 3, "groups": len(set(keys.tolist())),
                   "moved": int(np.count_nonzero(want != np.arange(3)))}
    assert want_fields == {"lanes": 3, "groups": 2, "moved": 2}
    assert eng.running == [lanes[i] for i in want]
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    pd = jax.profiler.ProfileData.from_file(path)
    fields, = [dict(e.stats) for plane in pd.planes
               if plane.name.startswith("/host:")
               for line in plane.lines for e in line.events
               if e.name == "engine.lane_order"]
    assert fields == want_fields


@pytest.mark.parametrize("decode_mode", ["gather", "kernel"])
def test_token_accounting_identical_across_decode_modes(decode_mode):
    """Regression pin for the prefill/decode token split: both decode
    paths (dense gather and Pallas kernel), forks included, must account
    exactly sum(prompts) prefill tokens and lanes x max_new decode
    tokens — the single ``_commit_token`` path counts per sequence
    stepped, never per batch or per backend call."""
    eng, cfg, _ = _lm_engine(decode_mode=decode_mode)
    rng = np.random.default_rng(7)
    prompts = [tuple(int(t) for t in rng.integers(1, cfg.vocab, 10 + i))
               for i in range(3)]
    reqs = [Request(rid=i, prompt=p, arrival=i * 1e-3, prefix_len=8,
                    max_new=4, n_samples=2 if i == 1 else 1)
            for i, p in enumerate(prompts)]
    out = eng.run(reqs)
    assert sorted(out) == [0, 1, 2] and len(out[1]) == 2
    lanes = sum(r.n_samples for r in reqs)
    assert eng.stats.prefills == len(reqs)
    assert eng.stats.prefill_tokens == sum(len(p) for p in prompts)
    assert eng.stats.decode_tokens == lanes * 4
    assert all(len(t) == 4 for lane in out.values() for t in lane)
    eng.pool.check_invariants()


def test_engine_backpressure_tiny_pool():
    """More requests than the pool fits at once: admission defers, engine
    drains, everything is eventually served exactly once."""
    rng = np.random.default_rng(1)
    reqs = [_req(i, tuple(rng.integers(1, 50, 18).tolist()), max_new=4)
            for i in range(10)]
    eng = _engine(num_blocks=12, max_lanes=3)
    out = eng.run(reqs)
    assert sorted(out) == list(range(10))
    assert eng.scheduler.stats.pool_rejects > 0
    eng.pool.check_invariants()
    assert eng.pool.num_live == 0
