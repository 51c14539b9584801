"""Unified KV-backend API: dense-vs-paged decode parity (gathered dense
view AND per-layer Pallas kernel path), layer-axis placement, ragged
continuous-batching decode, and the full-LM engine."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.kernels.paged_attention.paged_attention import fold_pages
from repro.kvcache import row_group_of
from repro.kvcache.backend import DenseBackend, PagedBackend, make_backend
from repro.models import lm

ARCHS = ["qwen1_5_0_5b", "starcoder2_7b", "phi3_medium_14b"]


def _model(arch, seed=0, f32=False):
    cfg = configs.get_smoke(arch)
    if f32:
        # f32 compute removes compute-dtype near-ties, so the kernel
        # path's f32 attention accumulation (vs the dense path's rounding
        # through bf16) still yields identical argmaxes
        cfg = dataclasses.replace(cfg, param_dtype="float32",
                                  compute_dtype="float32")
    params = lm.init(cfg, jax.random.key(seed)).params
    return cfg, params


# ---------------------------------------------------------------------------
# dense vs paged logit parity — gather path and kernel path
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch", ARCHS)
@pytest.mark.parametrize("decode_mode", ["gather", "kernel"])
def test_dense_paged_decode_parity(arch, decode_mode):
    """DenseBackend and PagedBackend must produce matching logits across
    prefill + several greedy decode steps — gathered-dense-view decode
    runs bit-identical math; kernel-path decode (Pallas paged_attention
    per layer) must agree to accumulation-order tolerance with identical
    argmaxes (checked in f32 compute, where no near-ties exist)."""
    cfg, params = _model(arch, f32=decode_mode == "kernel")
    tokens = jax.random.randint(jax.random.key(1), (2, 9), 1, cfg.vocab)

    dense = DenseBackend(cfg, batch=2, max_seq=24)
    paged = PagedBackend(cfg, num_blocks=64, block_size=4,
                         decode_mode=decode_mode)
    assert paged.decode_mode == decode_mode
    lg_d, _ = lm.prefill(params, cfg, tokens, backend=dense)
    lg_p, _ = lm.prefill(params, cfg, tokens, backend=paged)
    np.testing.assert_allclose(np.asarray(lg_d, np.float32),
                               np.asarray(lg_p, np.float32),
                               rtol=1e-4, atol=1e-4)
    tok = jnp.argmax(lg_d[:, -1], -1).astype(jnp.int32)[:, None]
    for _ in range(5):
        lg_d, _ = lm.decode_step(params, cfg, tok, dense)
        lg_p, _ = lm.decode_step(params, cfg, tok, paged)
        np.testing.assert_allclose(np.asarray(lg_d, np.float32),
                                   np.asarray(lg_p, np.float32),
                                   rtol=1e-4, atol=1e-4)
        a = np.argmax(np.asarray(lg_d[:, -1], np.float32), -1)
        b = np.argmax(np.asarray(lg_p[:, -1], np.float32), -1)
        assert (a == b).all()
        tok = jnp.asarray(a, jnp.int32)[:, None]
    assert (np.asarray(paged.lengths) == np.asarray(dense.lengths)).all()
    paged.release()
    paged.pool.check_invariants()
    assert paged.pool.num_live == 0


@pytest.mark.parametrize("sharded", [False, True])
@pytest.mark.parametrize("decode_mode", ["gather", "kernel"])
def test_device_mirror_is_folded(decode_mode, sharded):
    """The device mirror a backend stages and hands its decode step is
    the folded ``(L, P, page, Hkv·dh)`` array — the host pool with each
    token's heads side by side — and both decode modes read it to the
    dense backend's logits, on one pool and on two shards."""
    from repro.kvcache.backend import ShardedPagedBackend
    cfg, params = _model(ARCHS[0], f32=decode_mode == "kernel")
    tokens = jax.random.randint(jax.random.key(2), (4, 7), 1, cfg.vocab)
    dense = DenseBackend(cfg, batch=4, max_seq=24)
    if sharded:
        paged = ShardedPagedBackend(cfg, n_shards=2, num_blocks=64,
                                    block_size=4, decode_mode=decode_mode)
        backends = paged.backends
    else:
        paged = PagedBackend(cfg, num_blocks=64, block_size=4,
                             decode_mode=decode_mode)
        backends = [paged]
    lg_d, _ = lm.prefill(params, cfg, tokens, backend=dense)
    lm.prefill(params, cfg, tokens, backend=paged)
    tok = jnp.argmax(lg_d[:, -1], -1).astype(jnp.int32)[:, None]
    for _ in range(3):
        lg_d, _ = lm.decode_step(params, cfg, tok, dense)
        lg_p, _ = lm.decode_step(params, cfg, tok, paged)
        np.testing.assert_allclose(np.asarray(lg_d, np.float32),
                                   np.asarray(lg_p, np.float32),
                                   rtol=1e-4, atol=1e-4)
        tok = jnp.argmax(lg_d[:, -1], -1).astype(jnp.int32)[:, None]
    paged.flush()
    for be in backends:
        be._staged_pages()                  # stage the last write-back
        L, P, page = be.pool.k_pages.shape[:3]
        for dev, host in ((be._k_dev, be.pool.k_pages),
                          (be._v_dev, be.pool.v_pages)):
            assert dev.shape == (L, P, page, cfg.n_kv_heads * cfg.d_head)
            np.testing.assert_array_equal(np.asarray(dev),
                                          host.reshape(dev.shape))
    assert all(be.pool.num_live > 0 for be in backends)
    paged.release()


def test_make_backend_registry():
    cfg, _ = _model(ARCHS[0])
    assert isinstance(make_backend(cfg, "dense", batch=1, max_seq=8),
                      DenseBackend)
    assert isinstance(make_backend(cfg, "paged", num_blocks=16),
                      PagedBackend)
    # paged sizing honors the caller's capacity request: batch lanes of
    # max_seq tokens (+1 decode slot), ceil-divided into blocks
    be = make_backend(cfg, "paged", batch=2, max_seq=64)
    assert be.pool.cfg.num_blocks == 2 * (-(-(64 + 1) // 16))
    with pytest.raises(ValueError):
        make_backend(cfg, "holographic")
    # families whose decode state the pool cannot hold are refused, not
    # silently mis-served
    with pytest.raises(NotImplementedError):
        make_backend(configs.get_smoke("mamba2_370m"), "paged")


def test_kernel_decode_parity_moe_layer_offsets():
    """MoE config with a leading dense block stack (kimi: n_dense_layers=1)
    — the kernel path's scanned absolute layer index must address the
    right plane of the layered pool in both stacks."""
    cfg, params = _model("kimi_k2_1t_a32b", f32=True)
    assert cfg.is_moe and cfg.n_dense_layers > 0
    tokens = jax.random.randint(jax.random.key(3), (2, 9), 1, cfg.vocab)
    dense = DenseBackend(cfg, batch=2, max_seq=24)
    paged = PagedBackend(cfg, num_blocks=64, block_size=4)
    lg_d, _ = lm.prefill(params, cfg, tokens, backend=dense)
    lg_p, _ = lm.prefill(params, cfg, tokens, backend=paged)
    tok = jnp.argmax(lg_d[:, -1], -1).astype(jnp.int32)[:, None]
    for _ in range(3):
        lg_d, _ = lm.decode_step(params, cfg, tok, dense)
        lg_p, _ = lm.decode_step(params, cfg, tok, paged)
        np.testing.assert_allclose(np.asarray(lg_d, np.float32),
                                   np.asarray(lg_p, np.float32),
                                   rtol=1e-4, atol=1e-4)
        a = np.argmax(np.asarray(lg_d[:, -1], np.float32), -1)
        assert (a == np.argmax(np.asarray(lg_p[:, -1], np.float32),
                               -1)).all()
        tok = jnp.asarray(a, jnp.int32)[:, None]
    paged.release()
    paged.pool.check_invariants()


def test_paged_decode_mode_selection():
    cfg, _ = _model(ARCHS[0])
    # kernel is the default decode path; gather stays as fallback/oracle
    assert PagedBackend(cfg, num_blocks=16).decode_mode == "kernel"
    assert PagedBackend(cfg, num_blocks=16,
                        decode_mode="gather").decode_mode == "gather"
    with pytest.raises(ValueError):
        PagedBackend(cfg, num_blocks=16, decode_mode="telepathic")
    # sliding-window configs stay on the kernel path — the kernel masks
    # the window natively (per-layer flag for global_every hybrids)
    swin = dataclasses.replace(cfg, sliding_window=8)
    assert PagedBackend(swin, num_blocks=16).decode_mode == "kernel"


@pytest.mark.parametrize("decode_mode", ["gather", "kernel"])
def test_dense_paged_parity_sliding_window(decode_mode):
    """Pure-window config (starcoder2-style: every layer windowed): the
    kernel's sliding-window mask must reproduce the dense backend's
    window mask exactly — decoded past the window edge so the mask is
    actually cutting keys."""
    cfg, params = _model("starcoder2_7b", f32=decode_mode == "kernel")
    cfg = dataclasses.replace(cfg, sliding_window=5)
    params = lm.init(cfg, jax.random.key(0)).params
    tokens = jax.random.randint(jax.random.key(11), (2, 9), 1, cfg.vocab)

    dense = DenseBackend(cfg, batch=2, max_seq=24)
    paged = PagedBackend(cfg, num_blocks=64, block_size=4,
                         decode_mode=decode_mode)
    assert paged.decode_mode == decode_mode
    lg_d, _ = lm.prefill(params, cfg, tokens, backend=dense)
    lg_p, _ = lm.prefill(params, cfg, tokens, backend=paged)
    np.testing.assert_allclose(np.asarray(lg_d, np.float32),
                               np.asarray(lg_p, np.float32),
                               rtol=1e-4, atol=1e-4)
    tok = jnp.argmax(lg_d[:, -1], -1).astype(jnp.int32)[:, None]
    for _ in range(7):          # lengths reach 16 >> window 5
        lg_d, _ = lm.decode_step(params, cfg, tok, dense)
        lg_p, _ = lm.decode_step(params, cfg, tok, paged)
        np.testing.assert_allclose(np.asarray(lg_d, np.float32),
                                   np.asarray(lg_p, np.float32),
                                   rtol=1e-4, atol=1e-4)
        a = np.argmax(np.asarray(lg_d[:, -1], np.float32), -1)
        b = np.argmax(np.asarray(lg_p[:, -1], np.float32), -1)
        assert (a == b).all()
        tok = jnp.asarray(a, jnp.int32)[:, None]
    paged.release()
    paged.pool.check_invariants()


@pytest.mark.parametrize("decode_mode", ["gather", "kernel"])
def test_dense_paged_parity_hybrid_ssm_state(decode_mode):
    """Hybrid (hymba: parallel attention+SSM heads, window + global_every
    layers): PagedBackend pages the KV and carries the per-sequence
    SSM/conv side state — logits must match the dense backend whose cache
    pytree holds the same state."""
    cfg, params = _model("hymba_1_5b", f32=decode_mode == "kernel")
    # shrink the window below the decoded length so the mask really cuts
    cfg = dataclasses.replace(cfg, sliding_window=6)
    params = lm.init(cfg, jax.random.key(0)).params
    assert cfg.has_ssm and cfg.sliding_window and cfg.global_every
    # prompt length must be a multiple of the SSD chunk (smoke: 8)
    tokens = jax.random.randint(jax.random.key(5), (2, 8), 1, cfg.vocab)

    dense = DenseBackend(cfg, batch=2, max_seq=24)
    paged = PagedBackend(cfg, num_blocks=64, block_size=4,
                         decode_mode=decode_mode)
    assert paged.decode_mode == decode_mode
    lg_d, _ = lm.prefill(params, cfg, tokens, backend=dense)
    lg_p, _ = lm.prefill(params, cfg, tokens, backend=paged)
    np.testing.assert_allclose(np.asarray(lg_d, np.float32),
                               np.asarray(lg_p, np.float32),
                               rtol=1e-4, atol=1e-4)
    tok = jnp.argmax(lg_d[:, -1], -1).astype(jnp.int32)[:, None]
    for _ in range(7):
        lg_d, _ = lm.decode_step(params, cfg, tok, dense)
        lg_p, _ = lm.decode_step(params, cfg, tok, paged)
        np.testing.assert_allclose(np.asarray(lg_d, np.float32),
                                   np.asarray(lg_p, np.float32),
                                   rtol=1e-4, atol=1e-4)
        a = np.argmax(np.asarray(lg_d[:, -1], np.float32), -1)
        assert (a == np.argmax(np.asarray(lg_p[:, -1], np.float32),
                               -1)).all()
        tok = jnp.asarray(a, jnp.int32)[:, None]
    paged.release()
    paged.pool.check_invariants()
    assert paged.pool.num_live == 0


def test_hybrid_fork_copies_side_state():
    """A forked hybrid sequence must own its SSM/conv state: diverging
    forks advance independent recurrences (CoW shares only KV blocks)."""
    cfg, params = _model("hymba_1_5b")
    backend = PagedBackend(cfg, num_blocks=64, block_size=4,
                           decode_mode="gather")
    sid, _, _ = backend.new_seq(params, list(range(1, 9)))
    fid = backend.fork_seq(sid)
    s, f = backend._seqs[sid], backend._seqs[fid]
    assert s.ssm is not None and f.ssm is not None
    assert s.ssm is not f.ssm and np.array_equal(s.ssm, f.ssm)
    backend.decode(params, [sid, fid], [7, 9])   # forks diverge
    assert not np.array_equal(backend._seqs[sid].ssm,
                              backend._seqs[fid].ssm)
    backend.release()
    backend.pool.check_invariants()


def test_dense_backend_exposes_concrete_cache_reads():
    """Migration compatibility: .k/.v/.length forward to the pytree."""
    cfg, params = _model(ARCHS[0])
    be = lm.init_cache(cfg, batch=2, max_seq=16)
    assert be.k.shape == (cfg.n_layers, 2, 16,  # lint: ok(dense-kv-read)
                          cfg.n_kv_heads, cfg.d_head)
    tokens = jax.random.randint(jax.random.key(2), (2, 4), 1, cfg.vocab)
    _, be = lm.prefill(params, cfg, tokens, backend=be)
    assert int(be.length) == 4


# ---------------------------------------------------------------------------
# layer-axis placement
# ---------------------------------------------------------------------------

def test_layer_axis_keeps_token_blocks_in_one_row_group():
    """A token's per-layer KV blocks must land in one DRAM row group: the
    pool's layer axis makes one block id (= one placement decision) cover
    every layer, and MARS placement packs a sequence's blocks into few
    groups."""
    cfg, params = _model(ARCHS[0])
    backend = PagedBackend(cfg, num_blocks=64, block_size=4)
    pool = backend.pool
    prompt = list(range(1, 19))
    sid, _, _ = backend.new_seq(params, prompt)
    for _ in range(3):
        backend.decode(params, [sid], [5])
    table = backend.table(sid)
    bpg = pool.cfg.blocks_per_group
    for t in range(table.num_tokens):
        groups = {row_group_of(backend.block_of(sid, layer, t), bpg)
                  for layer in range(cfg.n_layers)}
        assert len(groups) == 1, \
            f"token {t} scattered across row groups {groups}"
    # MARS placement on a fresh pool: the whole sequence packs into the
    # minimum number of row neighborhoods
    seq_groups = {row_group_of(b, bpg) for b in table.blocks}
    assert len(seq_groups) == -(-len(table.blocks) // bpg)
    # and the pool buffer really is layered: one plane per model layer
    assert pool.k_pages.shape[0] == cfg.n_layers


def test_paged_ragged_decode_matches_isolated():
    """Lanes at different lengths decoding in one batched call must see
    exactly the logits they would get decoding alone."""
    cfg, params = _model(ARCHS[1])
    together = PagedBackend(cfg, num_blocks=64, block_size=4,
                            share_prefixes=False)
    a, la, _ = together.new_seq(params, list(range(1, 14)))   # 13 tokens
    b, lb, _ = together.new_seq(params, list(range(20, 25)))  # 5 tokens
    lg = together.decode(params, [a, b], [7, 9])
    for prompt, nxt, want0 in ((list(range(1, 14)), 7, la),
                               (list(range(20, 25)), 9, lb)):
        alone = PagedBackend(cfg, num_blocks=64, block_size=4,
                             share_prefixes=False)
        s, l0, _ = alone.new_seq(params, prompt)
        np.testing.assert_allclose(l0, want0, rtol=1e-4, atol=1e-4)
        lg1 = alone.decode(params, [s], [nxt])
        idx = 0 if nxt == 7 else 1
        np.testing.assert_allclose(lg[idx], lg1[0], rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# bugfix regressions: exhaustion rollback, released backends, dirty staging
# ---------------------------------------------------------------------------

def test_pool_exhaustion_rolls_back_partial_prefill():
    """If ``table.extend`` exhausts the pool mid-prefill, the partial
    table (prefix-matched increfed blocks + blocks allocated before the
    failure) must be rolled back — nothing stays live, invariants hold,
    and the error still surfaces."""
    cfg, params = _model(ARCHS[0])
    backend = PagedBackend(cfg, num_blocks=8, block_size=4,
                           decode_mode="gather")
    pool = backend.pool
    # seed the prefix cache: a 20-token sequence fills 5 blocks, all
    # registered; freeing it leaves them cached (evictable), none live
    sid, _, _ = backend.new_seq(params, list(range(1, 21)))
    backend.free_seq(sid)
    assert pool.num_live == 0 and pool.num_cached == 5
    live0, cached0 = pool.num_live, pool.num_cached
    # same prefix + a tail that needs 10 blocks total > 8 in the pool:
    # the prefix match revives 4 cached blocks, extension allocates a few
    # more, then the pool runs out mid-extend
    prompt = list(range(1, 17)) + list(range(100, 124))
    with pytest.raises(RuntimeError, match="pool exhausted"):
        backend.new_seq(params, prompt)
    pool.check_invariants()
    assert pool.num_live == live0, "partial prefill leaked live blocks"
    assert pool.num_cached >= 1    # matched prefix blocks returned to cache
    # the pool still serves: a fitting request succeeds afterwards
    sid2, _, _ = backend.new_seq(params, list(range(1, 13)))
    backend.free_seq(sid2)
    pool.check_invariants()
    assert pool.num_live == 0


def test_pool_exhaustion_rolls_back_whole_batch():
    """Batched prefill is atomic: rows added before the failing row are
    freed too, so ``num_live`` returns to its pre-call value."""
    cfg, params = _model(ARCHS[0])
    backend = PagedBackend(cfg, num_blocks=6, block_size=4,
                           decode_mode="gather", share_prefixes=False)
    pool = backend.pool
    with pytest.raises(RuntimeError, match="pool exhausted"):
        # row 0 fits (3 blocks), row 1 wants 4 more of the remaining 3
        backend._add_seqs(params, np.asarray(
            [list(range(1, 13)) + [0, 0], list(range(20, 34))], np.int32))
    pool.check_invariants()
    assert pool.num_live == 0 and not backend._seqs


def test_released_dense_backend_raises_clear_error():
    cfg, params = _model(ARCHS[0])
    be = DenseBackend(cfg, batch=1, max_seq=8)
    tokens = jax.random.randint(jax.random.key(0), (1, 4), 1, cfg.vocab)
    lm.prefill(params, cfg, tokens, backend=be)
    be.release()
    with pytest.raises(RuntimeError, match="released"):
        be.decode_step(params, jnp.ones((1, 1), jnp.int32))
    with pytest.raises(RuntimeError, match="released"):
        be.prefill(params, tokens)
    with pytest.raises(RuntimeError, match="released"):
        _ = be.lengths
    with pytest.raises(RuntimeError, match="released"):
        _ = be.k    # compatibility reads too; lint: ok(dense-kv-read)


def test_released_paged_backend_raises_clear_error():
    cfg, params = _model(ARCHS[0])
    be = PagedBackend(cfg, num_blocks=32, block_size=4)
    tokens = jax.random.randint(jax.random.key(0), (1, 4), 1, cfg.vocab)
    lm.prefill(params, cfg, tokens, backend=be)
    be.release()
    be.pool.check_invariants()
    for fn in (lambda: be.decode_step(params, jnp.ones((1, 1), jnp.int32)),
               lambda: be.prefill(params, tokens),
               lambda: be.lengths,
               lambda: be.new_seq(params, [1, 2, 3]),
               lambda: be.fork_seq(0),
               lambda: be.free_seq(0),
               lambda: be.table(0)):
        with pytest.raises(RuntimeError, match="released"):
            fn()


def test_decode_stages_only_dirty_blocks():
    """Per-step staging uploads only recently-written blocks — never the
    whole pool (the first step pays the full upload to build the device
    mirrors).  The mirrors are double-buffered: the slot staged for step
    N last scattered at step N-2, so each step's staged set is the union
    of the last TWO steps' dirty blocks — a single tail block in steady
    state, two only when the lane crosses a block boundary."""
    cfg, params = _model(ARCHS[0])
    backend = PagedBackend(cfg, num_blocks=64, block_size=4,
                           share_prefixes=False)
    pool = backend.pool
    sid, _, _ = backend.new_seq(params, list(range(1, 10)))
    backend.decode(params, [sid], [3])
    assert backend.staged_blocks_last_step == pool.cfg.num_blocks
    prev_dirty = set(pool.dirty)
    for tok in (5, 7, 9, 11):
        cur_dirty = set(pool.dirty)
        backend.decode(params, [sid], [tok])
        assert backend.staged_blocks_last_step \
            == len(prev_dirty | cur_dirty) <= 2, \
            "decode restaged more than the last two steps' dirty blocks"
        prev_dirty = cur_dirty
    # a second sequence's prefill dirties its blocks; the next decode
    # stages those plus the first lane's tail — still not the whole pool
    sid2, _, _ = backend.new_seq(params, list(range(30, 45)))
    cur_dirty = set(pool.dirty)
    assert 1 < len(prev_dirty | cur_dirty) < pool.cfg.num_blocks
    backend.decode(params, [sid, sid2], [2, 4])
    assert backend.staged_blocks_last_step == len(prev_dirty | cur_dirty)
    # the mirror converges to the host pool once pending writes stage
    backend._staged_pages()
    np.testing.assert_array_equal(np.asarray(backend._k_dev),
                                  fold_pages(pool.k_pages))
    backend.release()


@pytest.mark.parametrize("latent", [False, True])
def test_staging_a_burst_in_chunks(monkeypatch, latent):
    """A burst of dirty blocks larger than ``STAGE_CHUNK`` stages in
    several scatters of at most that many blocks each; the mirror ends
    equal to the host pool, for the K/V kind and the latent kind."""
    from repro.configs.deepseek_v2_lite import smoke
    from repro.kvcache import backend as backend_mod
    from repro.kvcache.backend import mirror_rows
    monkeypatch.setattr(backend_mod, "STAGE_CHUNK", 4)
    if latent:
        cfg = smoke()
        params = lm.init(cfg, jax.random.key(0)).params
    else:
        cfg, params = _model(ARCHS[0])
    backend = PagedBackend(cfg, num_blocks=64, block_size=4,
                           share_prefixes=False)
    pool = backend.pool
    sid, _, _ = backend.new_seq(params, list(range(1, 6)))
    backend.decode(params, [sid], [3])
    backend.decode(params, [sid], [4])
    # two prompts of 9 and 10 blocks land before the next decode
    sids = [backend.new_seq(params, list(range(10, 10 + n)))[0]
            for n in (35, 39)]
    pending = len(set(pool.dirty))
    scatters = []
    real = backend_mod._scatter_blocks
    monkeypatch.setattr(backend_mod, "_scatter_blocks",
                        lambda dev, idx, vals: scatters.append(
                            idx.shape[0]) or real(dev, idx, vals))
    backend.decode(params, [sid] + sids, [5, 6, 7])
    assert backend.staged_blocks_last_step >= pending > 4
    assert max(scatters) <= 4 and sum(scatters) >= pending
    backend._staged_pages()
    np.testing.assert_array_equal(np.asarray(backend._k_dev),
                                  mirror_rows(pool.k_pages, latent))
    backend.release()


def test_paged_prefix_sharing_shares_storage():
    cfg, params = _model(ARCHS[0])
    backend = PagedBackend(cfg, num_blocks=64, block_size=4)
    prompt = list(range(1, 18))
    s1, l1, n1 = backend.new_seq(params, prompt)
    s2, l2, n2 = backend.new_seq(params, prompt)
    assert n1 == 0 and n2 == 16          # 4 full blocks matched
    assert backend.table(s1).blocks[:4] == backend.table(s2).blocks[:4]
    np.testing.assert_allclose(l1, l2, rtol=1e-5, atol=1e-5)
    backend.release()
    assert backend.pool.num_live == 0


# ---------------------------------------------------------------------------
# host<->device byte counters
# ---------------------------------------------------------------------------

def _pow2(n):
    return 1 << max(n - 1, 0).bit_length()


def test_backend_counts_the_bytes_it_moves():
    """After one prefill and three decode steps the counters hold what
    the shapes say crossed.  Up: the prompt's tokens; both mirror slots'
    K and V whole at the first stage, then the dirty blocks padded to a
    power of two with their ids; each step's tokens, page table and
    lengths (lanes and pages padded to powers of two).  Down: the
    prompt's last logits and K/V; each step's logits and new K/V."""
    from repro.obs import Observer
    from repro.serve.engine import PagedLM, ServeEngine
    from repro.serving.scheduler import MarsScheduler
    cfg, params = _model("qwen1_5_0_5b")
    N, bs, S = 32, 4, 6
    be = PagedBackend(cfg, num_blocks=N, block_size=bs,
                      decode_mode="gather")
    L, K, dh, V = cfg.n_layers, cfg.n_kv_heads, cfg.d_head, cfg.vocab
    kv = jnp.dtype(cfg.kvdtype).itemsize
    lg = jnp.dtype(cfg.cdtype).itemsize
    i32 = 4
    plane = L * bs * K * dh * kv          # one block's K (or V), all layers
    sid, logits, _ = be.new_seq(params, list(range(1, S + 1)))
    h2d, d2h = S * i32, V * lg + 2 * L * S * K * dh * kv
    staged = []
    for i in range(3):
        tok = int(np.argmax(logits if logits.ndim == 1 else logits[0]))
        logits = be.decode(params, [sid], [tok])
        staged.append(be.staged_blocks_last_step)
        n_pages = _pow2(-(-(S + i + 1) // bs))
        h2d += i32 + n_pages * i32 + i32      # one lane: Bp = 1
        d2h += V * lg + 2 * L * K * dh * kv
    assert staged[0] == N
    h2d += 2 * 2 * N * plane
    h2d += sum(_pow2(n) * (i32 + 2 * plane) for n in staged[1:] if n)
    st = be.stats
    assert (st.h2d_bytes, st.d2h_bytes) == (h2d, d2h)
    assert st.staged_blocks == sum(staged)
    assert st.decode_steps == 3

    eng = ServeEngine(be.pool, MarsScheduler(pool=be.pool),
                      PagedLM(params, cfg, be))
    snap = Observer().attach(eng).snapshot()["counters"]
    assert snap["backend.h2d_bytes"] == h2d
    assert snap["backend.d2h_bytes"] == d2h
    assert snap["backend.staged_blocks"] == sum(staged)
