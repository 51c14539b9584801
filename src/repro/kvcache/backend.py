"""Unified KV-backend API: dense and paged serving caches, one interface.

The model (``models.lm``) speaks to its KV storage only through
``KVBackend``: ``prefill`` runs a prompt batch and stores every layer's
K/V, ``decode_step`` advances every lane one token.  Two implementations:

  DenseBackend   wraps the concrete per-layer ``lm.Cache`` pytree — the
                 training/dry-run storage.  Reads of ``.k``/``.v``/
                 ``.length`` forward to the cache, so code written against
                 the old concrete-Cache API keeps working.
  PagedBackend   per-sequence block tables over a layered ``BlockPool``
                 (one block id addresses a token-chunk's KV for *every*
                 layer — a single MARS placement decision co-locates a
                 token's per-layer blocks in one DRAM row group).  Supports
                 ragged continuous-batching decode, prefix sharing and
                 copy-on-write forks, and is what ``serve.engine`` drives.
                 Hybrid (attention + SSM) families keep their per-sequence
                 SSM/conv decode state host-side next to the block tables
                 (forked with the sequence, freed with it).

A third implementation scales the paged path across a device mesh:
``ShardedPagedBackend`` drives a ``kvcache.sharded_pool
.ShardedBlockPool`` with one complete ``PagedBackend`` per shard (own
pool, prefix cache, device mirror, optionally own mesh device) — the
kernel runs per shard over shard-local page tables, sequences never span
shards, and the scheduler routes admissions so shared prefixes co-locate.

Decode through the paged backend has two modes (``decode_mode``):

  "kernel"   the default: ``lm.paged_decode_step`` reads each layer's KV
             straight from the pool's layered page buffers via the Pallas
             ``paged_attention`` kernel (online-softmax merge of the
             in-flight token) — the MARS placement decisions *are* the
             kernel's page-walk addresses, nothing is flattened first.
             Sliding-window configs run natively: the scan flips the
             kernel's window mask per layer (``global_every`` hybrids
             keep their global layers unmasked).
  "gather"   the fallback/oracle: gather each lane's pages into a dense
             per-layer view and run the *same* ``lm.dense_decode_step``
             math as the dense backend, so gather-path logits agree with
             the dense backend bit-for-bit.

Either way the new token's K/V is extracted from the step and written
back into the pool host-side after attention (the pool mutates in place,
exactly like the single-layer engine of PR 1), so the kernel never reads
a partially-written page.  The pool buffers are staged to device through
double-buffered mirrors that re-upload only the blocks dirtied since the
slot was last staged (``BlockPool.drain_dirty``) — never the whole pool
per token.

Decode is a split-phase pipeline (MARS's lookahead buffer applied to the
serving loop — enough in-flight work ahead of the memory system to
overlap data movement with compute):

    step = backend.dispatch_decode(params, tokens, sids=...)  # launch
    logits = backend.sync(step)        # block on logits only
    ...                                # sample / emit while KV is in flight
    backend.flush()                    # commit the deferred KV write-back

``dispatch_decode`` launches the jitted step (jax dispatches
asynchronously) against a freshly staged mirror slot and returns a
``DecodeStep`` handle; ``sync`` blocks on the logits and starts the
non-blocking device→host copy of the new K/V; ``commit`` (normally via
``flush`` or the next ``dispatch_decode``) appends that K/V to the pool
one step late.  Every path that could observe or allocate pool state —
``new_seq``/``prefill``, ``fork_seq``, ``free_seq``, ``release`` —
flushes first, so a dispatched step's capacity precheck stays valid
until its commit and CoW forks always see committed KV.  ``decode`` /
``decode_step`` remain as thin compatibility wrappers (dispatch + sync
+ commit) for call sites that want the old synchronous semantics.

A released backend (``release()``) drains any pending deferred
write-back (no dirty block is dropped at shutdown), then raises a clear
"backend released" error from every serving entry point instead of an
opaque NoneType / KeyError; build a new backend to serve again.

Construction goes through ``make_backend`` — the single documented
entry point (``decode_mode`` / ``tiered`` / ``shards`` / ``device``
keyword surface).  Whether the kernel runs compiled or interpreted is
not an option: ``repro.kernels.pallas_interpret`` decides it from the
platform (compiled on a TPU, interpreted on the CPU).  Passing a pool
positionally to ``PagedBackend``/``ShardedPagedBackend`` is deprecated;
pass ``pool=``.  Adding a backend: implement the protocol against
``lm.prefill_parts`` (storage-agnostic prompt run) and
``lm.dense_decode_step`` (ragged one-token step), register a
constructor in ``make_backend``.
"""
from __future__ import annotations

import dataclasses
import functools
import warnings
from typing import Any, Callable, Optional, Protocol, Sequence, \
    runtime_checkable

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.paged_attention.paged_attention import fold_pages
from repro.kvcache.pool import BlockPool, PoolConfig
from repro.kvcache.prefix import BlockTable, PrefixCache
from repro.models.config import ModelConfig
from repro.obs.metrics import StatGroup
from repro.obs.trace import span


@dataclasses.dataclass
class DecodeStep:
    """Handle for one in-flight decode step.

    ``dispatch_decode`` returns one; ``sync(step)`` fills ``logits`` and
    flips ``synced``; ``commit(step)`` (or ``flush()``, or the next
    ``dispatch_decode``) lands the deferred KV write-back and flips
    ``committed``.  ``dev`` holds the backend's in-flight device futures
    (logits, new K/V, hybrid state) and ``parts`` the per-shard inner
    steps of a sharded dispatch — both backend-internal.
    """
    index: int                       # per-backend dispatch counter
    sids: list                       # sequences this step advances
    tokens: list                     # tokens[i] fed to sids[i]
    staged: int = 0                  # mirror blocks staged at dispatch
    synced: bool = False
    committed: bool = False
    batch_api: bool = False          # dispatched via the (B, 1) batch API
    logits: Any = None               # host logits after sync
    dev: dict = dataclasses.field(default_factory=dict)
    seqs: Optional[list] = None      # resolved _PagedSeq refs (plain)
    on_alloc: Optional[Callable[[int, int], None]] = None
    parts: Optional[list] = None     # sharded: (shard, inner step, idxs)


@runtime_checkable
class KVBackend(Protocol):
    """What the model needs from its KV storage — nothing more.

    Decode is split-phase: ``dispatch_decode`` → ``sync`` → ``commit``
    with a ``DecodeStep`` handle (``flush()`` is the sync+commit
    barrier); ``decode_step`` remains the synchronous compatibility
    wrapper over the three phases.
    """

    cfg: ModelConfig

    def prefill(self, params, tokens, frontend_emb=None):
        """Run a prompt batch and store every layer's K/V.

        Args:
          params: the model parameter tree (``lm.init(cfg).params``).
          tokens: (B, S) int32 prompt batch; replaces any lanes a prior
            ``prefill`` stored (the batch-level API serves one fixed
            batch at a time).
          frontend_emb: precomputed modality embeddings for families with
            frontends; backends that hold no frontend state reject it.
        Returns:
          last-position logits, shape (B, 1, V).
        Invariant: after the call ``lengths[b] == S`` for every lane.
        """
        ...

    def decode_step(self, params, tokens):
        """Advance every prefill lane one token.

        Compatibility wrapper: equivalent to ``dispatch_decode`` +
        ``sync`` + ``commit`` in one synchronous call.

        Args:
          params: the model parameter tree.
          tokens: (B, 1) int32 — lane ``b``'s next input token.
        Returns:
          next-token logits, shape (B, 1, V).
        Invariant: each call appends exactly one cached position per lane
        (``lengths`` increases by 1 elementwise); must follow ``prefill``.
        """
        ...

    def dispatch_decode(self, params, tokens, *, sids=None,
                        on_alloc=None) -> DecodeStep:
        """Launch one decode step without blocking on its results.

        Commits any pending prior step first (the one-step-deferred
        write-back), prechecks pool capacity so the eventual commit
        cannot fail, stages the dirty-block mirror, and dispatches the
        jitted step.  ``sids=None`` advances the ``prefill`` batch lanes
        (``tokens`` is the (B, 1) batch); paged backends also take the
        sequence-level form (``sids`` + per-sid token list).  At most
        one step may be in flight (dispatched, un-synced) per backend.
        Returns the ``DecodeStep`` handle to pass to ``sync``/``commit``.
        """
        ...

    def sync(self, step: DecodeStep):
        """Block on a dispatched step's logits (KV write-back stays
        deferred; the device→host KV copy starts here, non-blocking).
        Idempotent — a synced step returns its stored logits.  Returns
        float32 (len(sids), V) row-aligned to sids, or (B, 1, V) for a
        batch-API step."""
        ...

    def commit(self, step: Optional[DecodeStep] = None) -> None:
        """Land the pending synced step's KV write-back into the pool
        (host-side ``table.extend`` per lane, ``on_alloc`` callbacks).
        ``step=None`` commits whatever is pending; a committed step is a
        no-op.  Normally driven by ``flush()`` or the next
        ``dispatch_decode`` — decode step N commits step N-1."""
        ...

    def flush(self) -> None:
        """Barrier: sync any in-flight step and commit any pending
        write-back.  Idempotent.  Required before anything that must see
        committed KV — parity checks, ``fork_seq``/``free_seq``/prefill
        (which call it themselves), and shutdown."""
        ...

    @property
    def lengths(self) -> np.ndarray:
        """Per-lane cached token counts, int32 (B,) — what a position
        index may address in the next ``decode_step``."""
        ...

    def release(self) -> None:
        """Drain any pending deferred write-back (an implicit ``flush``
        — no dirty block is dropped at shutdown), then drop all storage
        (paged: decref every block back to the pool — registered prefix
        blocks stay evictable, private ones free).  Idempotence is not
        promised; every subsequent entry point raises a clear "backend
        released" ``RuntimeError``."""
        ...


# ---------------------------------------------------------------------------
# Dense backend
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("cfg",))
def _dense_decode(params, cfg, tokens, cache):
    from repro.models import lm
    return lm.dense_decode_step(params, cfg, tokens, cache)


class DenseBackend:
    """The old concrete ``lm.Cache`` behind the backend interface."""

    def __init__(self, cfg: ModelConfig, batch: int, max_seq: int,
                 enc_len: int = 0):
        from repro.models import lm
        self.cfg = cfg
        self.batch = batch
        self.max_seq = max_seq
        self._cache = lm.init_dense_cache(cfg, batch, max_seq, enc_len)
        self._steps = 0

    def _check_released(self) -> None:
        if self._cache is None:
            raise RuntimeError(
                "DenseBackend released: release() dropped the cache "
                "storage; build a new backend to serve again")

    # -- backend API --------------------------------------------------------

    def prefill(self, params, tokens, frontend_emb=None):
        """Dense prompt run: builds a fresh ``lm.Cache`` sized ``max_seq``
        and fills positions [0, S).  tokens: (B, S) int32 with
        B == ``self.batch``.  Returns last-position logits (B, 1, V)."""
        from repro.models import lm
        self._check_released()
        logits, self._cache = lm.dense_prefill(
            params, self.cfg, tokens, self.max_seq, frontend_emb)
        return logits

    def decode_step(self, params, tokens):
        """One dense decode step at slot ``length`` (jitted; the cache
        pytree is threaded functionally).  tokens: (B, 1) int32.
        Returns next-token logits (B, 1, V).  Compatibility wrapper over
        the split-phase lifecycle."""
        step = self.dispatch_decode(params, tokens)
        logits = self.sync(step)
        self.commit(step)
        return logits

    # -- split-phase decode lifecycle ----------------------------------------
    # The dense cache is updated functionally inside the jitted step, so
    # "dispatch" already carries the write-back: sync marks the step
    # committed and commit/flush are no-ops (no deferred state exists).

    def dispatch_decode(self, params, tokens, *, sids=None,
                        on_alloc=None) -> DecodeStep:
        """Launch one dense decode step (jax dispatches asynchronously;
        nothing blocks until ``sync``).  The dense backend has no
        sequence-level lanes: ``sids`` must be None."""
        self._check_released()
        if sids is not None:
            raise ValueError("DenseBackend has no sequence-level lanes; "
                             "dispatch with sids=None (the (B, 1) batch)")
        logits, self._cache = _dense_decode(params, self.cfg, tokens,
                                            self._cache)
        step = DecodeStep(index=self._steps, sids=[], tokens=[],
                          batch_api=True)
        step.dev["logits"] = logits
        self._steps += 1
        return step

    def sync(self, step: DecodeStep):
        """Return the step's (B, 1, V) logits (blocking happens when the
        caller materializes them).  The dense write-back landed inside
        the jitted step, so the step is committed here too."""
        if not step.synced:
            step.logits = step.dev.pop("logits")
            step.synced = step.committed = True
        return step.logits

    def commit(self, step: Optional[DecodeStep] = None) -> None:
        """No deferred write-back exists on the dense path."""

    def flush(self) -> None:
        """No-op barrier (nothing is ever pending); raises once
        released, like every other entry point."""
        self._check_released()

    @property
    def inflight_steps(self) -> int:
        """Dispatched-or-pending step count — always 0: the dense cache
        commits inside the jitted step."""
        return 0

    @property
    def lengths(self) -> np.ndarray:
        """(B,) int32 — the dense cache keeps one shared scalar length
        (all lanes advance in lockstep), broadcast to per-lane form."""
        self._check_released()
        ln = np.asarray(self._cache.length, np.int32)
        return np.broadcast_to(np.atleast_1d(ln), (self.batch,)).copy()

    def release(self) -> None:
        """Drop the cache pytree; later reads raise "backend released"."""
        self._cache = None

    # -- concrete-Cache compatibility reads ---------------------------------

    @property
    def cache(self):
        return self._cache

    def __getattr__(self, name):
        # k / v / ssm / conv / xk / xv / length forwarded to the pytree
        if name in ("k", "v", "ssm", "conv", "xk", "xv", "length"):
            if self.__dict__.get("_cache") is None:
                raise RuntimeError(
                    f"DenseBackend released: cannot read .{name} after "
                    "release(); build a new backend to serve again")
            if name in ("k", "v"):
                # legacy concrete-Cache reads; removal note in README
                warnings.warn(
                    f"DenseBackend.{name} is a deprecated concrete-Cache "
                    f"compatibility read; use backend.cache.{name} "
                    "(scheduled for removal — see README)",
                    DeprecationWarning, stacklevel=2)
            return getattr(self._cache, name)
        raise AttributeError(name)


# ---------------------------------------------------------------------------
# Paged backend
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("cfg",))
def _paged_decode(params, cfg, tokens, k_pages, v_pages, page_tables,
                  lengths, ssm, conv):
    """Gather each lane's pages into a dense per-layer view, run the ragged
    dense decode step, and extract the new token's K/V for write-back.

    k/v_pages: the folded device mirror (L, P, page, K·dh); page_tables:
    (B, n_pages) int32; lengths: (B,) int32 — the padded view always has
    room for slot ``lengths[b]`` (the backend pads the table before
    calling).  ssm/conv: hybrid side state (L, B, H, P, N) /
    (L, B, k-1, ch), or None for attention-only families.
    The latent kind (MLA) passes its mirror as k_pages and None as
    v_pages.  Returns (logits, k_new (L, B, 1, K, dh), v_new, ssm_new,
    conv_new, None): no routed-token count on this path.
    """
    from repro.models import lm
    L = k_pages.shape[0]
    B = tokens.shape[0]
    if cfg.is_mla:
        # the latent mirror's rows, their tile padding dropped
        W = cfg.latent_width
        k = k_pages[:, page_tables][..., :W].reshape(L, B, -1, 1, W)
        v = None
    else:
        K, dh = cfg.n_kv_heads, cfg.d_head
        k = k_pages[:, page_tables].reshape(L, B, -1, K, dh)
        v = v_pages[:, page_tables].reshape(L, B, -1, K, dh)
    cache = lm.Cache(k=k, v=v, ssm=ssm, conv=conv, xk=None, xv=None,
                     length=lengths)
    logits, new = lm.dense_decode_step(params, cfg, tokens, cache)
    idx = lengths.astype(jnp.int32)[None, :, None, None, None]
    k_new = jnp.take_along_axis(new.k, idx, axis=2)
    v_new = None if v is None else jnp.take_along_axis(new.v, idx, axis=2)
    return logits, k_new, v_new, new.ssm, new.conv, None


@functools.partial(jax.jit, static_argnames=("cfg", "interpret"))
def _paged_decode_kernel(params, cfg, tokens, k_pages, v_pages,
                         page_tables, lengths, ssm, conv, interpret=None):
    """Kernel-path decode: per-layer Pallas paged attention straight over
    the folded device mirror (no dense gather, no relayout).  Same operand
    and result shapes as ``_paged_decode``, and the routed-token count of
    a model with experts (``lm.paged_decode_step``).  ``interpret=None``
    lets the platform decide (``repro.kernels.pallas_interpret``)."""
    from repro.models import lm
    return lm.paged_decode_step(params, cfg, tokens, k_pages, v_pages,
                                page_tables, lengths, ssm_state=ssm,
                                conv_state=conv, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("cfg",))
def _jit_prefill_parts(params, cfg, tokens):
    from repro.models import lm
    return lm.prefill_parts(params, cfg, tokens)


def pool_config(cfg: ModelConfig, **kw) -> PoolConfig:
    """The layered pool ``cfg`` caches into: K and V per KV head, or for
    the latent kind one row of ``cfg.latent_width`` per token and layer.
    ``kw``: the pool's sizes and policies."""
    if cfg.is_mla:
        heads, dim = 1, cfg.latent_width
    else:
        heads, dim = cfg.n_kv_heads, cfg.d_head
    return PoolConfig(n_kv_heads=heads, head_dim=dim, n_layers=cfg.n_layers,
                      dtype=str(cfg.kvdtype), kv_kind=cfg.kv_kind, **kw)


def mirror_rows(pages: np.ndarray, latent: bool) -> np.ndarray:
    """Host pool pages ``(L, n, page, Hkv, dh)`` as the device mirror holds
    them: folded, ``(L, n, page, Hkv·dh)``.  A ``latent`` pool's rows are
    also padded with zeros to whole 128-lane tiles (576 -> 640), the
    width the kernel copies a page in; K/V pools keep their width."""
    rows = fold_pages(pages)
    if latent and rows.shape[-1] % 128:
        pad = -rows.shape[-1] % 128
        rows = np.pad(rows, ((0, 0),) * 3 + ((0, pad),))
    return rows


@functools.partial(jax.jit, donate_argnums=(0,))
def _scatter_blocks(dev, idx, vals):
    """Write dirty block planes into the device mirror.  The mirror is
    donated so XLA updates it in place — no pool-sized device copy per
    step.  ``idx`` may repeat (pow2 padding); duplicate indices write the
    same value twice, harmlessly."""
    return dev.at[:, idx].set(vals)


# the most pool blocks one scatter stages into a device mirror
STAGE_CHUNK = 512


def _pow2(n: int) -> int:
    return 1 << max(n - 1, 0).bit_length()


class BackendStats(StatGroup):
    """What a ``PagedBackend`` moves between host and device: bytes up
    (every upload goes through ``_put``), bytes down (every read-back
    through ``_fetch``), pool blocks staged into the device mirrors, and
    decode steps dispatched; and, for a model with experts, the live
    lanes' (token, expert) assignments to the experts it holds, summed
    over layers and decode steps (``moe_routed_here``, returned by the
    decode program beside its logits).  Plain counters, always on;
    adopted as ``backend.<field>`` by ``Observer.attach``."""
    FIELDS = {"h2d_bytes": 0, "d2h_bytes": 0, "staged_blocks": 0,
              "decode_steps": 0, "moe_routed_here": 0}


@dataclasses.dataclass
class _PagedSeq:
    sid: int
    table: BlockTable
    tokens: list            # tokens whose KV is cached
    # hybrid side state the pool cannot hold: per-sequence SSM recurrent
    # state (L, H, P, N) float32 and conv trailing context (L, k-1, ch),
    # host-side, forked with the sequence, freed with it
    ssm: Optional[np.ndarray] = None
    conv: Optional[np.ndarray] = None


class PagedBackend:
    """Per-sequence block tables over a layered ``BlockPool``.

    Sequence-level API (what the serve engine drives): ``new_seq`` /
    ``fork_seq`` / ``decode`` / ``free_seq``.  The batch-level
    ``KVBackend`` API (``prefill`` / ``decode_step``) runs the same
    machinery over a fixed batch, giving drop-in parity with
    ``DenseBackend``.

    Prompt K/V is always recomputed (prefill logits need the full
    context); prefix sharing is at the *storage* level — matched blocks
    are referenced instead of re-allocated, which is what bounds pool
    occupancy under hot prefixes.
    """

    def __init__(self, cfg: ModelConfig, *_legacy_pool,
                 pool: Optional[BlockPool] = None,
                 num_blocks: int = 256, block_size: int = 16,
                 placement: str = "mars", eviction: str = "fifo",
                 share_prefixes: bool = True, decode_mode: str = "kernel",
                 device=None, tiered: bool = False, tier_specs=None):
        """Build a paged backend over ``pool`` (or a fresh pool sized by
        ``num_blocks``/``block_size`` matching the model config).
        Prefer ``make_backend(cfg, "paged", ...)`` — the one documented
        construction surface.

        Args:
          cfg: model config; must be an attention-bearing decoder-only
            family (encoder-decoder / VLM state is not paged yet).
          pool: existing layered ``BlockPool`` to share; its KV buffer
            shape must match ``cfg`` (asserted).  Keyword-only in
            spirit: passing it positionally is deprecated.
          placement/eviction: pool policies when building a fresh pool
            ("cost" eviction pairs naturally with ``tiered``: the tier
            manager installs its recompute-vs-refetch scoring hook).
          share_prefixes: storage-level prefix sharing via ``PrefixCache``.
          decode_mode: "kernel" (Pallas paged_attention per layer, the
            default) or "gather" (dense-view oracle).  The kernel runs
            compiled on a TPU and interpreted on the CPU
            (``kernel_interpret``, resolved from the platform).
          device: jax device the staged KV mirror, decode operands and
            this backend's copy of the parameters are committed to;
            ``None`` uses the default device.  A mesh-sharded deployment
            (``ShardedPagedBackend``) gives each shard's backend its own
            device.
          tiered: put host/mock-remote spill tiers behind the pool
            (``kvcache.tiers.TierManager``): eviction demotes registered
            prefix blocks instead of dropping them, and prefix misses
            that hit a lower tier promote blocks back through a
            MARS-reordered batched copy-in.  Requires prefix sharing.
          tier_specs: ``TierSpec`` sequence overriding
            ``tiers.default_tiers`` (capacity / latency / bandwidth).
        """
        if _legacy_pool:
            if len(_legacy_pool) > 1 or pool is not None:
                raise TypeError("PagedBackend takes at most one pool")
            warnings.warn(
                "passing the pool positionally to PagedBackend is "
                "deprecated; pass pool= by keyword (or use make_backend)",
                DeprecationWarning, stacklevel=2)
            pool = _legacy_pool[0]
        if not cfg.has_attention or cfg.enc_layers \
                or cfg.family in ("encdec", "vlm"):
            raise NotImplementedError(
                f"PagedBackend pages attention KV plus per-sequence "
                f"SSM/conv decode state; family {cfg.family!r} needs "
                f"state the pool does not hold yet (encoder KV / "
                f"frontend prefixes, or has no attention KV at all)")
        if decode_mode not in ("kernel", "gather"):
            raise ValueError(f"unknown decode_mode {decode_mode!r}")
        from repro.kernels import pallas_interpret
        self.decode_mode = decode_mode
        self.kernel_interpret = pallas_interpret()
        self.device = device
        # the caller's parameter tree and this backend's copy of it on
        # ``device`` (see _device_params)
        self._params_src = self._params_dev = None
        self.cfg = cfg
        want = pool_config(cfg, num_blocks=num_blocks,
                           block_size=block_size, placement=placement,
                           eviction=eviction)
        if pool is None:
            pool = BlockPool(want)
        assert pool.k_pages is not None, "paged backend needs a KV pool"
        assert (pool.cfg.n_layers, pool.cfg.n_kv_heads, pool.cfg.head_dim,
                pool.cfg.kv_kind) == (want.n_layers, want.n_kv_heads,
                                      want.head_dim, want.kv_kind), \
            "pool KV buffer does not match the model config"
        self.pool = pool
        self.prefix = PrefixCache(pool.cfg.block_size)
        if share_prefixes:
            self.prefix.attach(pool)
        self.share_prefixes = share_prefixes
        # tiered KV memory: demote-on-evict / promote-on-miss behind the
        # pool (kvcache.tiers).  The manager interposes on pool.on_evict
        # AFTER prefix.attach so demotion captures the payload before
        # the prefix cache unregisters the block.
        self.tiers = None
        if tiered:
            assert share_prefixes, \
                "tiered KV spills registered prefix blocks; enable " \
                "share_prefixes"
            from repro.kvcache.tiers import TierManager
            self.tiers = TierManager(pool, self.prefix, tier_specs)
        self._seqs: dict[int, _PagedSeq] = {}
        self._next_sid = 0
        self._batch: list[int] = []      # batch-level API lane order
        self._released = False
        # telemetry (obs.Observer.attach): JSONL spans + the live
        # row-locality feed; obs_shard tags events with this backend's
        # shard index.  The byte counters are always on.
        self.obs = None
        self.obs_shard = 0
        self.stats = BackendStats()
        # double-buffered device mirrors of the pool's KV buffers: two
        # (k, v) slots, swapped every stage, each with its own pending-
        # dirty set (both fed from pool.drain_dirty — this backend is the
        # pool's single drain_dirty consumer).  Staging slot A writes
        # only blocks dirtied since A was last staged, and can overlap
        # the kernel still reading slot B.
        self._mirrors: list = [None, None]
        self._slot_dirty: list = [set(), set()]
        self._slot = 0                   # slot the next stage writes
        self._staged_slot: Optional[int] = None  # slot staged last
        self.staged_blocks_last_step = 0
        # split-phase decode pipeline: at most one dispatched-un-synced
        # step (_inflight) and one synced-un-committed step (_pending)
        self._inflight: Optional[DecodeStep] = None
        self._pending: Optional[DecodeStep] = None
        self._steps = 0

    def _check_released(self) -> None:
        if self._released:
            raise RuntimeError(
                "PagedBackend released: release() returned every block "
                "to the pool; build a new backend to serve again")

    # -- device staging ------------------------------------------------------

    @property
    def _log(self):
        """The attached observer's JSONL trace, or None."""
        return None if self.obs is None else self.obs.trace

    def _put(self, x: np.ndarray):
        """Upload a host array to this backend's device (default device
        when unset) — per-shard backends keep their mirrors and decode
        inputs on their own mesh device.  Counts ``h2d_bytes``."""
        self.stats.h2d_bytes += x.nbytes
        a = jnp.asarray(x)
        return a if self.device is None else jax.device_put(a, self.device)

    def _fetch(self, x) -> np.ndarray:
        """Read a device array back to the host (blocking until it is
        computed).  Counts ``d2h_bytes``."""
        a = np.asarray(x)
        self.stats.d2h_bytes += a.nbytes
        return a

    def _device_params(self, params):
        """``params`` as this backend computes with them: placed on its
        device once, on first use, and reused for as long as the caller
        passes the same tree — so no prefill or decode call moves the
        weights between devices.  Without a device the tree is used as
        given."""
        if self.device is None:
            return params
        if params is not self._params_src:
            self._params_src = params
            self._params_dev = jax.device_put(params, self.device)
        return self._params_dev

    def _staged_pages(self):
        """Stage the pool's host-mutated KV buffers into the next mirror
        slot, uploading only blocks written since *that slot* was last
        staged (both slots are built with a full upload the first time).
        Alternating slots lets this scatter overlap a kernel still
        reading the other slot, and the donated scatter keeps it free of
        pool-sized copies.  ``staged_blocks_last_step`` records how many
        blocks moved — steady-state that is the union of the last two
        steps' dirty sets (one step per slot).  Returns the freshly
        staged ``(k, v)`` device pair (``(rows, None)`` for a latent
        pool).

        A mirror is folded, ``(L, P, page, Hkv·dh)``: the layout the
        device gives it by default and the kernel reads as it is (the
        host pool's ``(L, P, page, Hkv, dh)`` would be relaid out on the
        device, whole, at every decode step).  The host reshape is free;
        a latent row is padded on the host to whole lane tiles as it is
        staged (``mirror_rows``)."""
        pool = self.pool
        if self._mirrors[0] is None:
            pool.drain_dirty()           # full upload covers everything
            for s in (0, 1):
                self._mirrors[s] = tuple(
                    None if pages is None
                    else self._put(mirror_rows(pages, self.cfg.is_mla))
                    for pages in (pool.k_pages, pool.v_pages))
                self._slot_dirty[s].clear()
            self.staged_blocks_last_step = pool.cfg.num_blocks
            self._staged_slot, self._slot = 0, 1
        else:
            fresh = pool.drain_dirty()
            self._slot_dirty[0].update(fresh)
            self._slot_dirty[1].update(fresh)
            s = self._slot
            pend = sorted(self._slot_dirty[s])
            self.staged_blocks_last_step = len(pend)
            for i in range(0, len(pend), STAGE_CHUNK):
                # at most STAGE_CHUNK blocks per scatter, so a burst of
                # long prefills never needs a pool-sized upload beside
                # the mirrors; each chunk's id list is padded to a power
                # of two (repeating the last id) so the donated scatter
                # compiles O(log) variants
                part = pend[i:i + STAGE_CHUNK]
                pad = part + [part[-1]] * (_pow2(len(part)) - len(part))
                idx = self._put(np.asarray(pad, np.int32))
                self._mirrors[s] = tuple(
                    None if dev is None else _scatter_blocks(
                        dev, idx, self._put(mirror_rows(pages[:, pad],
                                                      self.cfg.is_mla)))
                    for dev, pages in zip(self._mirrors[s],
                                          (pool.k_pages, pool.v_pages)))
            self._slot_dirty[s].clear()
            self._staged_slot, self._slot = s, 1 - s
        self.stats.staged_blocks += self.staged_blocks_last_step
        if self.obs is not None:
            self.obs.trace.event("backend.stage", shard=self.obs_shard,
                                 blocks=self.staged_blocks_last_step,
                                 slot=self._staged_slot)
        return self._mirrors[self._staged_slot]

    @property
    def _k_dev(self):
        """K of the most recently staged mirror slot, folded (None before
        the first stage) — the buffer the next kernel launch reads."""
        return None if self._staged_slot is None \
            else self._mirrors[self._staged_slot][0]

    @property
    def _v_dev(self):
        return None if self._staged_slot is None \
            else self._mirrors[self._staged_slot][1]

    # -- sequence-level API (continuous batching) ---------------------------

    def new_seq(self, params, prompt: Sequence[int],
                on_alloc: Optional[Callable[[int, int], None]] = None
                ) -> tuple[int, Any, int]:
        """Prefill one sequence into the pool.

        Args:
          params: model parameter tree.
          prompt: token ids; the prompt's full-block prefix is matched
            against the prefix cache first (matched blocks are referenced,
            not re-stored).
          on_alloc: callback ``(sid, n_fresh_blocks)`` fired once with the
            number of blocks this prefill actually allocated (the engine
            converts admission reservations into claims with it).
        Returns:
          (sid, last-position logits (V,) float32, shared-prefix tokens).
        Invariant: atomic under pool exhaustion — on RuntimeError nothing
        stays live (see ``_add_seqs``).
        """
        logits, sids, shared = self._add_seqs(
            params, np.asarray([list(prompt)], np.int32), on_alloc)
        return sids[0], logits[0], shared[0]

    def _add_seqs(self, params, tokens: np.ndarray,
                  on_alloc=None) -> tuple[Any, list[int], list[int]]:
        """Batched prompt prefill -> one new sequence per row.

        Atomic under pool exhaustion: if any row's ``table.extend``
        raises, the partial table (prefix-matched increfed blocks plus
        blocks allocated before the failure) is decref'd back and rows
        already added by this call are freed, then the error re-raises —
        nothing stays live.
        """
        self._check_released()
        # flush barrier: prefill allocates, and the prefix match reads
        # refcounts/tokens — both must see the deferred step committed
        # (this is also what keeps a dispatched step's capacity precheck
        # valid until its own commit)
        self.flush()
        B, S = tokens.shape
        with span("backend.prefill", self._log, shard=self.obs_shard,
                  rows=B, kv_kind=self.cfg.kv_kind) as sp:
            logits, parts = _jit_prefill_parts(
                self._device_params(params), self.cfg,
                self._put(np.asarray(tokens, np.int32)))
            kvd = self.cfg.kvdtype
            k_dev = parts["k"].astype(kvd)
            v_dev = None if parts["v"] is None else parts["v"].astype(kvd)
            state = (parts["ssm"], parts["conv"]) if self.cfg.has_ssm \
                else None
            with span("backend.prefill.wait", rows=B, tokens=S):
                jax.block_until_ready((logits, k_dev, v_dev, state))
            with span("backend.prefill.fetch", rows=B, tokens=S):
                logits = self._fetch(logits)
                k_all = self._fetch(k_dev)            # (L, B, S, K, dh)
                v_all = None if v_dev is None else self._fetch(v_dev)
                ssm_all = conv_all = None
                if state is not None:
                    ssm_all = np.asarray(self._fetch(state[0]), np.float32)
                    conv_all = self._fetch(state[1])
            with span("backend.prefill.store", rows=B, tokens=S,
                      kv_kind=self.cfg.kv_kind):
                sids, shared = self._store_prompts(
                    tokens, k_all, v_all, ssm_all, conv_all, on_alloc)
            sp["shared_tokens"] = int(sum(shared))
        return np.asarray(logits[:, 0], np.float32), sids, shared

    def _store_prompts(self, tokens, k_all, v_all, ssm_all, conv_all,
                       on_alloc) -> tuple[list[int], list[int]]:
        """The host half of a prefill: match each row's prompt against
        the prefix cache, extend a fresh block table with the rest of
        its K/V (written into the numpy pool), register the sequence.
        Returns ``(sids, shared prefix tokens)``, rolled back whole on
        pool exhaustion (see ``_add_seqs``)."""
        B = tokens.shape[0]
        sids, shared = [], []
        for b in range(B):
            prompt = [int(t) for t in tokens[b]]
            if not self.share_prefixes:
                bids, n = [], 0
            elif self.tiers is not None:
                # tier-aware match: in-pool chain first, then promotable
                # lower-tier blocks — copy-ins queue in the manager's
                # lookahead buffer and land batched (flushed below)
                bids, n = self.tiers.match(prompt)
            else:
                bids, n = self.prefix.match(prompt, self.pool)
            table = BlockTable(list(bids), n)
            allocs0 = self.pool.stats.allocs
            try:
                table.extend(
                    self.pool, prompt[n:], seq_tokens=prompt,
                    cache=self.prefix if self.share_prefixes else None,
                    kv=(k_all[:, b, n:],
                        None if v_all is None else v_all[:, b, n:]))
            except RuntimeError:
                # roll back: queued promotions first (their destination
                # blocks are released with the tables below; the tier
                # entries were never removed), then this row's partial
                # table (registered blocks stay as evictable cache,
                # private ones free), then the rows this call already
                # created — batched prefill is all-or-nothing
                if self.tiers is not None:
                    self.tiers.cancel_promotions()
                self.prefix.release(table, self.pool)
                for sid in sids:
                    self.free_seq(sid)
                raise
            sid = self._next_sid
            self._next_sid += 1
            seq = _PagedSeq(sid, table, list(prompt))
            if ssm_all is not None:
                seq.ssm = np.ascontiguousarray(ssm_all[:, b])
                seq.conv = np.ascontiguousarray(conv_all[:, b])
            self._seqs[sid] = seq
            if on_alloc is not None:
                on_alloc(sid, self.pool.stats.allocs - allocs0)
            sids.append(sid)
            shared.append(n)
        if self.tiers is not None:
            # the whole batch's promotions land in one MARS-reordered
            # copy-in; the dirtied blocks re-stage to the device mirror
            # before the next decode step touches them
            self.tiers.flush_promotions()
        return sids, shared

    def fork_seq(self, sid: int) -> int:
        """Fork a sequence, sharing every block (CoW on first append);
        the hybrid side state is copied — it is mutated every step.
        Forces a flush barrier first: the fork's CoW bookkeeping (and
        its copied SSM/conv state) must see committed KV, not a step
        still in flight."""
        self._check_released()
        self.flush()
        src = self._seqs[sid]
        nsid = self._next_sid
        self._next_sid += 1
        self._seqs[nsid] = _PagedSeq(
            nsid, src.table.fork(self.pool), list(src.tokens),
            ssm=None if src.ssm is None else src.ssm.copy(),
            conv=None if src.conv is None else src.conv.copy())
        return nsid

    # -- decode preemption (pause -> demote -> resume) -----------------------

    def pause_seq(self, sid: int) -> dict:
        """Preempt a live decode: flush the pipeline FIRST (the paused
        lane may still be owed a deferred write-back token — pausing
        mid-step would capture half a state), capture the sequence's
        full decode state host-side (cached tokens, every block's KV
        payload + content tag, hybrid ssm/conv), then release its
        blocks.  Registered prefix blocks stay resident as evictable
        cache — demotable to the spill tiers under pressure via the
        existing ``TierManager`` eviction hook — so a prompt resume
        usually re-matches them for free; private blocks free outright.

        Returns the opaque pause record ``resume_seq`` restores from.
        The captured payloads are verbatim pool bytes, which is what
        makes resumption bitwise: nothing is ever recomputed."""
        self._check_released()
        self.flush()
        if self.obs is not None:
            self.obs.trace.event("backend.pause", shard=self.obs_shard,
                                 sid=sid)
        seq = self._seqs.pop(sid)
        pool = self.pool
        blocks = []
        for bid in seq.table.blocks:
            blocks.append({
                "content": pool.content[bid],
                "k": np.array(pool.k_pages[:, bid]),
                "v": None if pool.v_pages is None
                else np.array(pool.v_pages[:, bid]),
            })
        rec = {
            "tokens": list(seq.tokens),
            "num_tokens": seq.table.num_tokens,
            "blocks": blocks,
            "ssm": None if seq.ssm is None else seq.ssm.copy(),
            "conv": None if seq.conv is None else seq.conv.copy(),
        }
        self.prefix.release(seq.table, pool)
        return rec

    def resume_seq(self, rec: dict,
                   on_alloc: Optional[Callable[[int, int], None]] = None
                   ) -> int:
        """Re-admit a paused sequence bitwise-identically under a new
        sid.  No prefill recompute anywhere: the leading blocks re-enter
        through the prefix cache and tiers (``match`` returns the SAME
        bytes — registration is exact-prefix keyed and tier demotion
        captured payloads verbatim), and whatever the caches no longer
        hold is restored from the pause record's captured pages with
        plain ``alloc`` + ``write_kv``.  Atomic under pool exhaustion:
        on RuntimeError every matched reference is released and nothing
        stays live."""
        self._check_released()
        self.flush()
        if self.obs is not None:
            self.obs.trace.event("backend.resume", shard=self.obs_shard,
                                 tokens=len(rec["tokens"]))
        pool = self.pool
        bs = pool.cfg.block_size
        tokens = list(rec["tokens"])
        num = rec["num_tokens"]
        if not self.share_prefixes:
            bids, n = [], 0
        elif self.tiers is not None:
            bids, n = self.tiers.match(tokens)
        else:
            bids, n = self.prefix.match(tokens, pool)
        # as in ``_store_prompts``: the on_alloc claim counts only the
        # restore's own allocations (tier promotion destinations are the
        # tier manager's business, not the caller's reservation)
        allocs0 = pool.stats.allocs
        start = n // bs
        need = len(rec["blocks"]) - start
        try:
            if not pool.can_alloc(need):
                raise RuntimeError(
                    f"pool exhausted: resume needs {need} blocks, "
                    f"free {pool.num_free}, cached {pool.num_cached}")
            fresh = pool.alloc(need, hint_blocks=bids) if need else []
        except RuntimeError:
            if self.tiers is not None:
                self.tiers.cancel_promotions()
            self.prefix.release(BlockTable(list(bids), n), pool)
            raise
        for j, bid in enumerate(fresh):
            src = rec["blocks"][start + j]
            pool.content[bid] = src["content"]
            pool.write_kv(bid, 0, src["k"], src["v"])
            pool.touch(bid)
            end = (start + j + 1) * bs
            if self.share_prefixes and end <= num:
                self.prefix.register(tuple(tokens[:end]), bid, pool)
        if self.tiers is not None:
            self.tiers.flush_promotions()
        sid = self._next_sid
        self._next_sid += 1
        seq = _PagedSeq(sid, BlockTable(list(bids) + list(fresh), num),
                        tokens,
                        ssm=None if rec["ssm"] is None
                        else rec["ssm"].copy(),
                        conv=None if rec["conv"] is None
                        else rec["conv"].copy())
        self._seqs[sid] = seq
        if on_alloc is not None:
            on_alloc(sid, pool.stats.allocs - allocs0)
        return sid

    def decode(self, params, sids: Sequence[int], tokens: Sequence[int],
               on_alloc: Optional[Callable[[int, int], None]] = None):
        """One ragged decode step over live sequences — the synchronous
        compatibility wrapper: ``dispatch_decode`` + ``sync`` +
        ``commit`` in one call (KV is committed before it returns).

        Args:
          sids: sequences to advance (any subset of the live set, each at
            its own length).
          tokens: ``tokens[i]`` is fed to ``sids[i]``.
          on_alloc: per-sequence callback ``(sid, n_fresh_blocks)`` — a
            lane allocates at most one block per step (new tail or CoW).
        Returns:
          next-token logits, float32 (len(sids), V), row-aligned to sids.
        Invariants: the new K/V is written back host-side *after* the
        step (the kernel never reads a half-written page); a capacity
        precheck makes the step all-or-nothing — on "pool exhausted"
        every sequence is exactly as it was.
        """
        step = self.dispatch_decode(params, tokens, sids=sids,
                                    on_alloc=on_alloc)
        out = self.sync(step)
        self.commit(step)
        return out

    # -- split-phase decode lifecycle ----------------------------------------

    def dispatch_decode(self, params, tokens, *, sids=None,
                        on_alloc: Optional[Callable[[int, int], None]]
                        = None) -> DecodeStep:
        """Launch one ragged decode step without blocking.

        Commits the pending prior step first (decode step N lands step
        N-1's dirty blocks), prechecks capacity for *this* step, stages
        the next mirror slot, and dispatches the jitted step — jax
        queues the kernel and returns immediately, so the scatter and
        kernel execution overlap whatever the host does until ``sync``.

        The dispatch-time capacity precheck is sufficient for the
        deferred commit because every allocating path (``_add_seqs``,
        ``fork_seq``) and every refcount-changing path (``free_seq``)
        flushes first — between a dispatch and its commit the pool can
        only have gained capacity.

        ``sids=None`` dispatches the batch-API lanes (``tokens`` is the
        (B, 1) int32 batch); otherwise ``tokens[i]`` feeds ``sids[i]``.
        Raising ("pool exhausted", or a second dispatch while one step
        is in flight) leaves every sequence exactly as it was.
        """
        self._check_released()
        batch_api = sids is None
        if batch_api:
            sids = list(self._batch)
            tokens = [int(t) for t in np.asarray(tokens).reshape(-1)]
        assert sids, "no active sequences to decode (prefill first)"
        if self._inflight is not None:
            raise RuntimeError(
                "a decode step is already in flight; sync() it before "
                "dispatching the next")
        with span("backend.dispatch", step=self._steps, lanes=len(sids)):
            return self._dispatch(params, sids, tokens, batch_api,
                                  on_alloc)

    def _dispatch(self, params, sids, tokens, batch_api: bool,
                  on_alloc) -> DecodeStep:
        """The body of ``dispatch_decode`` once its arguments check out:
        commit, precheck, stage, launch."""
        from repro.kernels.paged_attention import ops
        self._commit_pending()
        # tier contract: every queued promotion flushed (copy-in complete,
        # block dirtied for staging) before a promoted page can enter a
        # decode batch — prefill flushes per batch, so the queue must be
        # empty here
        assert self.tiers is None or self.tiers.pending == 0, \
            "unflushed tier promotions entering a decode batch"
        seqs = [self._seqs[s] for s in sids]
        B = len(seqs)
        page = self.pool.cfg.block_size
        # capacity precheck so the deferred write-back cannot die halfway
        # (rolling back a committed lane would mean undoing CoW/eviction
        # side effects): each lane needs at most one fresh block — a new
        # tail, or a CoW copy of a shared tail.  Raising here leaves
        # every sequence exactly as it was before the step.
        need = 0
        for s in seqs:
            fill = s.table.num_tokens % page
            if fill == 0 or \
                    self.pool.refcount[s.table.blocks[-1]] > 1:
                need += 1
        if not self.pool.can_alloc(need):
            raise RuntimeError(
                f"pool exhausted: decode step needs {need} blocks, "
                f"free {self.pool.num_free}, cached {self.pool.num_cached}")
        # padded operand pack: every lane needs room for its new slot on
        # the gather path (the kernel path attends the in-flight token
        # out of registers, but shares the padding so both compile alike)
        pt, lengths, toks = ops.decode_step_operands(
            [s.table for s in seqs], tokens, page)
        with span("backend.stage", step=self._steps, lanes=B,
                  kv_kind=self.cfg.kv_kind):
            kp, vp = self._staged_pages()
        if self.obs is not None:
            # live row-locality: this step's page walk in kernel issue
            # order (sequence-major, page-contiguous — the MARS-reordered
            # stream; defined the same way on the gather path so the
            # gauge is mode-independent), fed to this shard's open-row
            # model
            self.obs.observe_kv_walk(
                self.obs_shard,
                ops.kv_read_trace_kernel([s.table for s in seqs],
                                         block_size=page))
        ssm = conv = None
        if self.cfg.has_ssm:
            with span("backend.state_pack", step=self._steps, lanes=B):
                ssm, conv = self._pack_state(seqs, toks.shape[0])
        with span("backend.launch", step=self._steps, lanes=B):
            params = self._device_params(params)
            if self.decode_mode == "kernel":
                logits, k_new, v_new, ssm_new, conv_new, routed = \
                    _paged_decode_kernel(
                        params, self.cfg, self._put(toks), kp, vp,
                        self._put(pt), self._put(lengths), ssm, conv,
                        interpret=self.kernel_interpret)
            else:
                logits, k_new, v_new, ssm_new, conv_new, routed = \
                    _paged_decode(
                        params, self.cfg, self._put(toks), kp, vp,
                        self._put(pt), self._put(lengths), ssm, conv)
        step = DecodeStep(index=self._steps, sids=list(sids),
                          tokens=[int(t) for t in tokens],
                          staged=self.staged_blocks_last_step,
                          batch_api=batch_api, seqs=seqs,
                          on_alloc=on_alloc)
        step.dev.update(logits=logits, k=k_new, v=v_new,
                        ssm=ssm_new, conv=conv_new, routed=routed)
        self._steps += 1
        self.stats.decode_steps += 1
        self._inflight = step
        if self.obs is not None:
            self.obs.trace.event("backend.dispatch", shard=self.obs_shard,
                                 step=step.index, lanes=B,
                                 staged=step.staged)
        return step

    def _pack_state(self, seqs, Bp: int):
        """Batch the per-sequence hybrid side state for one step and
        upload it (padded lanes get zeros; their outputs are discarded
        at sync)."""
        L = self.cfg.n_layers
        ssm_np = np.zeros((L, Bp) + seqs[0].ssm.shape[1:],
                          seqs[0].ssm.dtype)
        conv_np = np.zeros((L, Bp) + seqs[0].conv.shape[1:],
                           seqs[0].conv.dtype)
        for i, s in enumerate(seqs):
            ssm_np[:, i] = s.ssm
            conv_np[:, i] = s.conv
        return self._put(ssm_np), self._put(conv_np)

    def sync(self, step: DecodeStep):
        """Block on a dispatched step's logits.  The new K/V stays on
        device (its non-blocking device→host copy starts here); the
        write-back commits one step later.  Idempotent on a synced
        step.  Returns float32 (len(sids), V) row-aligned to the
        dispatched sids — or (B, 1, V) for a batch-API step."""
        self._check_released()
        if step.synced:
            return step.logits
        if step is not self._inflight:
            raise RuntimeError(
                "sync() of a step that is not in flight on this backend")
        B = len(step.sids)
        # the span measures the blocking wait (the dispatch-to-sync gap)
        # and the logits coming to the host
        with span("backend.decode", self._log, shard=self.obs_shard,
                  step=step.index, lanes=B, staged=step.staged):
            logits = step.dev.pop("logits")
            with span("backend.decode.wait", step=step.index, lanes=B):
                logits.block_until_ready()
            with span("backend.decode.fetch", step=step.index, lanes=B):
                step.logits = np.asarray(self._fetch(logits)[:B, 0],
                                         np.float32)
                routed = step.dev.pop("routed", None)
                if routed is not None:   # computed with the logits
                    self.stats.moe_routed_here += int(self._fetch(routed))
        # logits landing means the step finished; start the KV transfer
        # for the deferred commit without blocking on it
        for name in ("k", "v", "ssm", "conv"):
            arr = step.dev.get(name)
            if arr is not None and hasattr(arr, "copy_to_host_async"):
                arr.copy_to_host_async()
        if step.batch_api:
            step.logits = self._put(step.logits)[:, None, :]
        step.synced = True
        self._inflight = None
        self._pending = step
        return step.logits

    def commit(self, step: Optional[DecodeStep] = None) -> None:
        """Land the pending synced step's KV write-back (see
        ``_commit_pending``).  ``step=None`` commits whatever is
        pending; committing an already-committed step is a no-op;
        committing an un-synced step is an error."""
        self._check_released()
        if step is not None:
            if step.committed:
                return
            if step is not self._pending:
                raise RuntimeError(
                    "commit() of a step that is not pending on this "
                    "backend (sync() it first)")
        self._commit_pending()

    def _commit_pending(self) -> None:
        """The deferred write-back: append the pending step's new K/V to
        each lane's block table (CoW on shared tails), update hybrid
        side state, fire ``on_alloc``.  Cannot fail: capacity was
        prechecked at dispatch and every alloc/refcount path since has
        flushed first."""
        step = self._pending
        if step is None:
            return
        self._pending = None
        B = len(step.sids)
        with span("backend.commit", step=step.index, lanes=B,
                  kv_kind=self.cfg.kv_kind):
            with span("backend.commit.fetch", step=step.index, lanes=B):
                k_new = self._fetch(step.dev.pop("k"))   # (L, Bp, 1, K, dh)
                v_new = step.dev.pop("v")
                if v_new is not None:
                    v_new = self._fetch(v_new)
                ssm_new = step.dev.pop("ssm")
                conv_new = step.dev.pop("conv")
                if ssm_new is not None:
                    ssm_new = self._fetch(ssm_new)       # (L, Bp, H, P, N)
                    conv_new = self._fetch(conv_new)
            with span("backend.commit.store", step=step.index, lanes=B):
                self._store_step(step, k_new, v_new, ssm_new, conv_new)
        step.committed = True
        step.seqs = None
        if self.obs is not None:
            self.obs.trace.event("backend.commit", shard=self.obs_shard,
                                 step=step.index, lanes=len(step.sids))

    def _store_step(self, step: DecodeStep, k_new, v_new, ssm_new,
                    conv_new) -> None:
        """Append each lane's new token and K/V to its block table (CoW
        on shared tails), carry the hybrid side state, fire
        ``on_alloc``."""
        for i, (s, tok) in enumerate(zip(step.seqs, step.tokens)):
            allocs0 = self.pool.stats.allocs
            new_tokens = s.tokens + [int(tok)]
            s.table.extend(
                self.pool, [int(tok)], seq_tokens=new_tokens,
                cache=self.prefix if self.share_prefixes else None,
                kv=(k_new[:, i], None if v_new is None else v_new[:, i]))
            s.tokens = new_tokens     # commit only after the extend
            if ssm_new is not None:
                s.ssm = np.ascontiguousarray(ssm_new[:, i])
                s.conv = np.ascontiguousarray(conv_new[:, i])
            if step.on_alloc is not None:
                step.on_alloc(s.sid, self.pool.stats.allocs - allocs0)

    def flush(self) -> None:
        """Barrier: sync any in-flight step and commit any pending
        write-back.  Idempotent — flushing twice (or with nothing
        outstanding) is a no-op.  ``release()`` drains through here, so
        a released backend never holds pending work; flushing after
        release raises like every other entry point."""
        self._check_released()
        with span("backend.flush"):
            if self._inflight is not None:
                self.sync(self._inflight)
            self._commit_pending()

    @property
    def inflight_steps(self) -> int:
        """Steps between dispatch and commit: 0 (drained), 1 (one step
        dispatched or pending), or 2 (one in flight + one pending)."""
        return int(self._inflight is not None) + \
            int(self._pending is not None)

    def free_seq(self, sid: int) -> None:
        """Finished sequence: registered prefix blocks stay evictable;
        the hybrid side state dies with the sequence.  Flushes first —
        the deferred step may still owe this sequence (and others) a
        committed token, and freeing mid-step would strand it."""
        self._check_released()
        self.flush()
        seq = self._seqs.pop(sid)
        self.prefix.release(seq.table, self.pool)

    def table(self, sid: int) -> BlockTable:
        self._check_released()
        return self._seqs[sid].table

    def block_of(self, sid: int, layer: int, token_index: int) -> int:
        """Pool block holding a token's KV for one layer — the layer axis
        shares the block id, so one placement covers all layers."""
        assert 0 <= layer < self.cfg.n_layers
        seq = self._seqs[sid]
        assert token_index < seq.table.num_tokens
        return seq.table.blocks[token_index // self.pool.cfg.block_size]

    # -- batch-level KVBackend API ------------------------------------------

    def prefill(self, params, tokens, frontend_emb=None):
        """Protocol ``prefill``: one new sequence per row of the (B, S)
        batch, freeing any lanes a prior call created.  Returns
        last-position logits (B, 1, V)."""
        self._check_released()
        self.flush()    # barrier: lagged write-back lands before re-batch
        assert frontend_emb is None, "paged backend has no frontend state"
        old, self._batch = self._batch, []
        for sid in old:              # re-prefill replaces the batch lanes
            self.free_seq(sid)
        logits, self._batch, _ = self._add_seqs(params, np.asarray(tokens))
        return self._put(logits)[:, None, :]

    def decode_step(self, params, tokens):
        """Protocol ``decode_step``: advance the prefill lanes one token
        (tokens (B, 1) int32, row order = prefill row order).  Returns
        next-token logits (B, 1, V)."""
        self._check_released()
        toks = [int(t) for t in np.asarray(tokens).reshape(-1)]
        logits = self.decode(params, self._batch, toks)
        return self._put(logits)[:, None, :]

    @property
    def lengths(self) -> np.ndarray:
        """(B,) int32 cached token count per prefill lane — genuinely
        ragged (unlike the dense backend's broadcast scalar)."""
        self._check_released()
        return np.asarray(
            [self._seqs[s].table.num_tokens for s in self._batch], np.int32)

    def release(self) -> None:
        """Drain the decode pipeline (implicit flush — a pending step's
        dirty blocks land in the pool, never silently dropped), free
        every live sequence (registered prefix blocks stay as evictable
        cache), drop the mirror slots, and poison the backend: all later
        entry points raise "backend released"."""
        if not self._released:
            if self._inflight is not None:
                self.sync(self._inflight)
            self._commit_pending()
        for sid in list(self._seqs):
            self.free_seq(sid)
        self._batch = []
        self._mirrors = [None, None]
        self._slot_dirty = [set(), set()]
        self._slot, self._staged_slot = 0, None
        self._params_src = self._params_dev = None
        self._released = True


# ---------------------------------------------------------------------------
# Mesh-sharded paged backend
# ---------------------------------------------------------------------------

class ShardedPagedBackend:
    """One ``PagedBackend`` per shard of a ``ShardedBlockPool``.

    Each shard owns a complete serving stack: its own block pool, prefix
    cache, staged-dirty device mirror, and — when ``devices`` are given —
    its own mesh device, so ``lm.paged_decode_step`` runs the kernel
    **per shard over shard-local pools** (per-shard page tables; no
    global block-id space exists).  A sequence lives entirely on one
    shard: ``fork_seq`` forks within the parent's shard (CoW stays
    shard-local) and prefix sharing only ever matches blocks the same
    shard stored — which is why the scheduler routes shared prefixes to
    one shard in the first place.

    Sequence ids handed out here are backend-global; the mapping to
    (shard, inner sid) is internal.  ``decode`` accepts any mix of
    sequences, groups them by shard, runs one ragged kernel step per
    shard, and reassembles logits in call order — so the engine's lane
    loop is shard-agnostic.  The batch-level ``KVBackend`` API routes
    prefill rows to the least-loaded shard, giving drop-in parity with
    ``DenseBackend``/``PagedBackend``.
    """

    def __init__(self, cfg: ModelConfig, *_legacy_pool, pool=None,
                 n_shards: Optional[int] = None, mesh=None,
                 devices: Optional[Sequence] = None,
                 num_blocks: int = 256, block_size: int = 16,
                 placement: str = "mars", eviction: str = "fifo", **kw):
        """Prefer ``make_backend(cfg, "paged", shards=N, ...)`` — the one
        documented construction surface.

        Args:
          pool: a ``ShardedBlockPool`` to drive, or None to build one
            (``num_blocks`` total across shards).  Passing it
            positionally is deprecated; pass ``pool=``.
          n_shards/mesh: shard-count discovery when building the pool —
            forwarded to ``ShardedBlockPool`` (mesh model axis; 1
            without a mesh).
          devices: per-shard jax devices for the staged mirrors, decode
            operands and each shard's copy of the parameters (length
            ``n_shards``).  None keeps everything on the default device —
            pool sharding still partitions placement.
          num_blocks: total capacity request when building a pool; it is
            rounded *up* to a multiple of the shard count, so any
            capacity request is honored.
          Remaining kwargs (decode_mode, share_prefixes, ...) configure
          every per-shard backend alike.
        """
        from repro.kvcache.sharded_pool import ShardedBlockPool, \
            discover_shards
        if cfg.is_mla:
            raise NotImplementedError(
                "ShardedPagedBackend pages K/V only; the latent KV kind "
                "(MLA) is served by one PagedBackend")
        if _legacy_pool:
            if len(_legacy_pool) > 1 or pool is not None:
                raise TypeError(
                    "ShardedPagedBackend takes at most one pool")
            warnings.warn(
                "passing the pool positionally to ShardedPagedBackend is "
                "deprecated; pass pool= by keyword (or use make_backend)",
                DeprecationWarning, stacklevel=2)
            pool = _legacy_pool[0]
        if pool is None:
            n_shards = discover_shards(n_shards, mesh)
            num_blocks = -(-num_blocks // n_shards) * n_shards
            pool = ShardedBlockPool(
                pool_config(cfg, num_blocks=num_blocks,
                            block_size=block_size, placement=placement,
                            eviction=eviction),
                n_shards=n_shards, mesh=mesh)
        assert isinstance(pool, ShardedBlockPool), \
            "ShardedPagedBackend needs a ShardedBlockPool"
        if devices is not None:
            assert len(devices) == pool.n_shards, \
                (len(devices), pool.n_shards)
        self.cfg = cfg
        self.pool = pool
        self.backends = [
            PagedBackend(cfg, pool=shard_pool,
                         device=None if devices is None else devices[i],
                         **kw)
            for i, shard_pool in enumerate(pool.shards)]
        self._seqs: dict[int, tuple[int, int]] = {}   # gsid -> (shard, isid)
        self._rev: dict[tuple[int, int], int] = {}    # (shard, isid) -> gsid
        self._next_sid = 0
        self._batch: list[int] = []
        self._released = False
        # split-phase pipeline state (mirrors PagedBackend's; the inner
        # per-shard steps live in the outer step's ``parts``)
        self._inflight: Optional[DecodeStep] = None
        self._pending: Optional[DecodeStep] = None
        self._steps = 0

    def _check_released(self) -> None:
        if self._released:
            raise RuntimeError(
                "ShardedPagedBackend released: release() returned every "
                "block to its shard pool; build a new backend to serve "
                "again")

    # decode_mode / kernel_interpret / kernel staging reads mirror
    # PagedBackend's so the engine's use_kernel override and the staging
    # tests stay backend-agnostic (setter fans out to every shard)

    @property
    def decode_mode(self) -> str:
        return self.backends[0].decode_mode

    @decode_mode.setter
    def decode_mode(self, mode: str) -> None:
        if mode not in ("kernel", "gather"):
            raise ValueError(f"unknown decode_mode {mode!r}")
        for b in self.backends:
            b.decode_mode = mode

    @property
    def kernel_interpret(self) -> bool:
        return self.backends[0].kernel_interpret

    @property
    def staged_blocks_last_step(self) -> int:
        return sum(b.staged_blocks_last_step for b in self.backends)

    @property
    def stats(self) -> BackendStats:
        """The shards' ``BackendStats`` summed (a snapshot; each shard's
        own counters are adopted as ``backend.shardN.<field>``)."""
        return BackendStats(**{f: sum(getattr(b.stats, f)
                                      for b in self.backends)
                               for f in BackendStats.FIELDS})

    # -- sequence-level API (what the serve engine drives) ------------------

    def new_seq(self, params, prompt: Sequence[int],
                on_alloc: Optional[Callable[[int, int], None]] = None,
                shard: Optional[int] = None) -> tuple[int, Any, int]:
        """Prefill one sequence on one shard.

        Args:
          shard: the routed shard (what ``MarsScheduler`` stamped on the
            request via ``ShardedBlockPool.route``); None picks the
            least-loaded shard (direct API use, no scheduler in front).
        Returns/invariants: as ``PagedBackend.new_seq`` — additionally,
        every block of the sequence lives in ``pool.shards[shard]``.
        """
        self._check_released()
        # barrier across *all* shards (the inner new_seq only flushes its
        # own) so admission reads post-commit pool state everywhere
        self.flush()
        if shard is None:
            shard = self.pool.least_loaded()
        assert 0 <= shard < self.pool.n_shards, shard
        gsid = self._next_sid
        self._next_sid += 1
        cb = None if on_alloc is None else \
            (lambda _isid, n: on_alloc(gsid, n))
        isid, logits, shared = self.backends[shard].new_seq(
            params, prompt, on_alloc=cb)
        self._seqs[gsid] = (shard, isid)
        self._rev[(shard, isid)] = gsid
        return gsid, logits, shared

    def fork_seq(self, sid: int) -> int:
        """Fork within the parent's shard — CoW forks are shard-local by
        construction (blocks of one pool cannot be referenced from
        another).  Forces a flush barrier first (every shard — the
        outer step is all-or-nothing across shards)."""
        self._check_released()
        self.flush()
        shard, isid = self._seqs[sid]
        nisid = self.backends[shard].fork_seq(isid)
        gsid = self._next_sid
        self._next_sid += 1
        self._seqs[gsid] = (shard, nisid)
        self._rev[(shard, nisid)] = gsid
        return gsid

    # -- decode preemption (pause -> demote -> resume) -----------------------

    def pause_seq(self, sid: int) -> dict:
        """Preempt a live decode on its shard: barrier across every
        shard first (the outer round is all-or-nothing), then capture
        and release on the owning shard (``PagedBackend.pause_seq``).
        The record remembers the shard so an un-routed resume defaults
        back to where the cached/demoted blocks still live."""
        self._check_released()
        self.flush()
        shard, isid = self._seqs.pop(sid)
        del self._rev[(shard, isid)]
        rec = self.backends[shard].pause_seq(isid)
        rec["shard"] = shard
        return rec

    def resume_seq(self, rec: dict,
                   on_alloc: Optional[Callable[[int, int], None]] = None,
                   shard: Optional[int] = None) -> int:
        """Re-admit a paused sequence under a new global sid.

        ``shard=None`` resumes on the pause shard (prefix/tier matches
        only ever hit there); an explicit shard restores the captured
        payload onto that shard instead — the bytes are shard-agnostic,
        only the cache reuse is not.  Bitwise either way."""
        self._check_released()
        self.flush()
        if shard is None:
            shard = rec.get("shard", self.pool.least_loaded())
        assert 0 <= shard < self.pool.n_shards, shard
        gsid = self._next_sid
        self._next_sid += 1
        cb = None if on_alloc is None else \
            (lambda _isid, n: on_alloc(gsid, n))
        isid = self.backends[shard].resume_seq(rec, on_alloc=cb)
        self._seqs[gsid] = (shard, isid)
        self._rev[(shard, isid)] = gsid
        return gsid

    def decode(self, params, sids: Sequence[int], tokens: Sequence[int],
               on_alloc: Optional[Callable[[int, int], None]] = None):
        """One ragged decode round across shards — the synchronous
        compatibility wrapper: ``dispatch_decode`` + ``sync`` +
        ``commit``.  Even this wrapper is issue-then-gather: every
        shard's kernel is dispatched before any shard's logits are
        awaited.  Returns float32 (len(sids), V) row-aligned to sids.

        All-or-nothing across shards, like ``PagedBackend.decode`` is
        within one: every shard's worst-case block need is prechecked
        before ANY shard dispatches, so a "pool exhausted" raise leaves
        every sequence — on every shard — exactly as it was (no lane
        double-appends KV on a retry)."""
        step = self.dispatch_decode(params, tokens, sids=sids,
                                    on_alloc=on_alloc)
        out = self.sync(step)
        self.commit(step)
        return out

    # -- split-phase decode lifecycle (issue-then-gather) --------------------

    def dispatch_decode(self, params, tokens, *, sids=None,
                        on_alloc: Optional[Callable[[int, int], None]]
                        = None) -> DecodeStep:
        """Dispatch one decode round on every involved shard before any
        is synced: flush (committing the prior round everywhere), run
        the cross-shard capacity precheck, then launch each shard's
        kernel back-to-back — jax queues them asynchronously, so the
        per-shard kernels and mirror scatters overlap instead of running
        host-blocking round trips shard by shard."""
        self._check_released()
        batch_api = sids is None
        if batch_api:
            sids = list(self._batch)
            tokens = [int(t) for t in np.asarray(tokens).reshape(-1)]
        assert sids, "no active sequences to decode (prefill first)"
        if self._inflight is not None:
            raise RuntimeError(
                "a decode step is already in flight; sync() it before "
                "dispatching the next")
        self._commit_pending()
        by_shard: dict[int, list[int]] = {}
        for i, s in enumerate(sids):
            by_shard.setdefault(self._seqs[s][0], []).append(i)
        # cross-shard capacity precheck (mirrors the per-shard one):
        # each lane needs at most one fresh block — a new tail, or a CoW
        # copy of a shared tail.  Prechecking every shard before ANY
        # dispatches keeps the round all-or-nothing.
        page = self.pool.cfg.block_size
        for shard, idxs in by_shard.items():
            inner = self.backends[shard]
            need = 0
            for i in idxs:
                t = inner._seqs[self._seqs[sids[i]][1]].table
                fill = t.num_tokens % page
                if fill == 0 or inner.pool.refcount[t.blocks[-1]] > 1:
                    need += 1
            if not inner.pool.can_alloc(need):
                raise RuntimeError(
                    f"pool exhausted on shard {shard}: decode step needs "
                    f"{need} blocks, free {inner.pool.num_free}, "
                    f"cached {inner.pool.num_cached}")
        parts = []
        for shard, idxs in sorted(by_shard.items()):
            cb = None if on_alloc is None else \
                (lambda isid, n, _s=shard:
                 on_alloc(self._rev[(_s, isid)], n))
            inner_step = self.backends[shard].dispatch_decode(
                params, [tokens[i] for i in idxs],
                sids=[self._seqs[sids[i]][1] for i in idxs], on_alloc=cb)
            parts.append((shard, inner_step, idxs))
        step = DecodeStep(index=self._steps, sids=list(sids),
                          tokens=[int(t) for t in tokens],
                          staged=self.staged_blocks_last_step,
                          batch_api=batch_api, parts=parts)
        self._steps += 1
        self._inflight = step
        return step

    def sync(self, step: DecodeStep):
        """Gather every shard's logits (all kernels were already issued
        by ``dispatch_decode``) and reassemble rows in call order.
        Idempotent on a synced step."""
        self._check_released()
        if step.synced:
            return step.logits
        if step is not self._inflight:
            raise RuntimeError(
                "sync() of a step that is not in flight on this backend")
        rows: dict[int, np.ndarray] = {}
        for shard, inner_step, idxs in step.parts:
            lg = self.backends[shard].sync(inner_step)
            for j, i in enumerate(idxs):
                rows[i] = lg[j]
        step.logits = np.stack([rows[i] for i in range(len(step.sids))])
        if step.batch_api:
            step.logits = jnp.asarray(step.logits)[:, None, :]
        step.synced = True
        self._inflight = None
        self._pending = step
        return step.logits

    def commit(self, step: Optional[DecodeStep] = None) -> None:
        """Commit every shard's part of the pending round."""
        self._check_released()
        if step is not None:
            if step.committed:
                return
            if step is not self._pending:
                raise RuntimeError(
                    "commit() of a step that is not pending on this "
                    "backend (sync() it first)")
        self._commit_pending()

    def _commit_pending(self) -> None:
        step = self._pending
        if step is None:
            return
        self._pending = None
        for shard, inner_step, _ in step.parts:
            self.backends[shard].commit(inner_step)
        step.committed = True

    def flush(self) -> None:
        """Barrier across every shard: sync the in-flight round, commit
        the pending one, and drain each shard backend (covers direct
        inner-backend use too).  Idempotent; raises once released."""
        self._check_released()
        if self._inflight is not None:
            self.sync(self._inflight)
        self._commit_pending()
        for b in self.backends:
            b.flush()

    @property
    def inflight_steps(self) -> int:
        """Cross-shard rounds between dispatch and commit (0, 1, or 2 —
        a round counts once however many shards it spans)."""
        return int(self._inflight is not None) + \
            int(self._pending is not None)

    def free_seq(self, sid: int) -> None:
        """Release a finished sequence back to its shard's pool (after
        the flush barrier — the deferred round may still owe it a
        committed token)."""
        self._check_released()
        self.flush()
        shard, isid = self._seqs.pop(sid)
        del self._rev[(shard, isid)]
        self.backends[shard].free_seq(isid)

    def table(self, sid: int) -> BlockTable:
        self._check_released()
        shard, isid = self._seqs[sid]
        return self.backends[shard].table(isid)

    def shard_of(self, sid: int) -> int:
        """Shard a live sequence's blocks occupy — the leading coordinate
        of its placement key (``placement.placement_key``)."""
        self._check_released()
        return self._seqs[sid][0]

    # -- tiered KV memory (per-shard tiers, demotion/promotion shard-local) --

    @property
    def tiered(self) -> bool:
        """True iff the per-shard backends carry spill tiers (the
        ``tiered=`` kwarg fans out to every shard: one ``TierManager``
        per shard pool, so demoted payloads never cross shards)."""
        return self.backends[0].tiers is not None

    def tier_shard_for(self, prompt: Sequence[int]) -> Optional[int]:
        """Shard whose spill tiers hold the prompt's first full prefix
        block, or ``None`` — the promotable lower-tier prefix hit the
        scheduler may count toward affinity routing
        (``MarsScheduler.tier_probe``).  Routing a request here turns a
        would-be recompute into a shard-local promotion."""
        self._check_released()
        for i, b in enumerate(self.backends):
            if b.tiers is not None and b.tiers.holds_prefix(prompt):
                return i
        return None

    # -- batch-level KVBackend API ------------------------------------------

    def prefill(self, params, tokens, frontend_emb=None):
        """Protocol ``prefill``: rows route greedily to the least-loaded
        shard (load measured in blocks, each row charged its block need —
        the batch API has no prefix pages to be affine to), then each
        shard prefills its rows in one batched call.  Atomic across
        shards like ``PagedBackend._add_seqs`` is within one: if a later
        shard exhausts its pool, rows already prefilled on earlier shards
        are freed before the error re-raises — nothing stays live.
        Returns last-position logits (B, 1, V) in row order."""
        self._check_released()
        assert frontend_emb is None, "paged backend has no frontend state"
        self.flush()
        old, self._batch = self._batch, []
        for sid in old:
            self.free_seq(sid)
        tokens = np.asarray(tokens)
        B = tokens.shape[0]
        # same unit as pool.load (blocks): a row stores S prompt tokens.
        # Shard ranking comes from the shared load snapshot (the same
        # numbers ShardedBlockPool.route and the obs gauges use).
        from repro.obs.observer import shard_load_snapshot
        row_blocks = -(-tokens.shape[1] // self.pool.cfg.block_size)
        load = [r["load"] for r in shard_load_snapshot(self.pool)]
        plan: dict[int, list[int]] = {}
        for i in range(B):
            s = min(range(self.pool.n_shards),
                    key=lambda x: (load[x], x))
            plan.setdefault(s, []).append(i)
            load[s] += row_blocks
        out = np.zeros((B, self.cfg.vocab), np.float32)
        gsids: dict[int, int] = {}
        for shard, idxs in sorted(plan.items()):
            try:
                lg, isids, _ = self.backends[shard]._add_seqs(
                    params, tokens[idxs])
            except RuntimeError:
                # the failing shard rolled itself back; free the rows
                # earlier shards already created, then surface the error
                for gsid in gsids.values():
                    self.free_seq(gsid)
                raise
            for j, i in enumerate(idxs):
                out[i] = lg[j]
                gsid = self._next_sid
                self._next_sid += 1
                self._seqs[gsid] = (shard, isids[j])
                self._rev[(shard, isids[j])] = gsid
                gsids[i] = gsid
        self._batch = [gsids[i] for i in range(B)]
        return jnp.asarray(out)[:, None, :]

    def decode_step(self, params, tokens):
        """Protocol ``decode_step`` over the prefill lanes (see
        ``PagedBackend.decode_step``); lanes decode on their own shards.
        Returns next-token logits (B, 1, V)."""
        self._check_released()
        toks = [int(t) for t in np.asarray(tokens).reshape(-1)]
        logits = self.decode(params, self._batch, toks)
        return jnp.asarray(logits)[:, None, :]

    @property
    def lengths(self) -> np.ndarray:
        """(B,) int32 cached token count per prefill lane."""
        self._check_released()
        return np.asarray([self.table(s).num_tokens for s in self._batch],
                          np.int32)

    def release(self) -> None:
        """Drain the pipeline (implicit flush), then release every shard
        backend; later entry points raise."""
        if not self._released:
            if self._inflight is not None:
                self.sync(self._inflight)
            self._commit_pending()
        for b in self.backends:
            b.release()
        self._seqs.clear()
        self._rev.clear()
        self._batch = []
        self._released = True


def make_backend(cfg: ModelConfig, kind: str = "dense", *,
                 batch: int = 1, max_seq: int = 0, enc_len: int = 0,
                 pool: Optional[BlockPool] = None,
                 shards: Optional[int] = None, device=None,
                 **kw) -> KVBackend:
    """Backend registry — the single documented construction surface:
    "dense" | "paged" | "sharded-paged".

    One keyword surface configures every kind alike: ``decode_mode``
    ("kernel"/"gather"), ``tiered`` (spill tiers behind the pool),
    ``shards`` (shard count — ``shards > 1`` turns "paged" into the
    mesh-sharded backend), and
    ``device`` (the jax device for the staged mirror; per-shard
    ``devices=[...]`` for sharded kinds).

    Args:
      batch/max_seq: capacity request — dense allocates (B, max_seq)
        directly; paged kinds size the pool to hold ``batch`` lanes of
        ``max_seq`` tokens (+1 decode slot each) unless ``num_blocks`` or
        an explicit ``pool`` overrides it.
      pool: concrete storage to share (``BlockPool`` for "paged",
        ``ShardedBlockPool`` for "sharded-paged").
      shards: partition the pool across this many shards (kind "paged"
        with ``shards > 1`` routes to "sharded-paged"; aliases
        ``n_shards`` there).
      device: jax device for a paged backend's mirror + operands.
      Remaining kwargs forward to the backend constructor.
    Returns: an object satisfying the ``KVBackend`` protocol.

    >>> make_backend(None, "holographic")
    Traceback (most recent call last):
        ...
    ValueError: unknown KV backend kind 'holographic'

    The split-phase decode lifecycle (dispatch → sync → commit, with
    ``flush()`` as the barrier — decode step N commits step N-1):

    >>> import jax
    >>> from repro import configs
    >>> from repro.models import lm
    >>> cfg = configs.get_smoke("qwen1_5_0_5b")
    >>> params = lm.init(cfg, jax.random.key(0)).params
    >>> b = make_backend(cfg, "paged", num_blocks=16, block_size=4,
    ...                  decode_mode="gather")
    >>> sid, _, _ = b.new_seq(params, [1, 2, 3, 4, 5])
    >>> step = b.dispatch_decode(params, [7], sids=[sid])  # no block
    >>> step.synced, b.inflight_steps
    (False, 1)
    >>> logits = b.sync(step)              # block on logits only
    >>> logits.shape[0], step.synced, step.committed
    (1, True, False)
    >>> b.table(sid).num_tokens            # write-back still deferred
    5
    >>> b.flush()                          # barrier: commit the KV
    >>> b.table(sid).num_tokens, b.inflight_steps
    (6, 0)
    >>> b.release()
    """
    if kind == "dense":
        return DenseBackend(cfg, batch, max_seq, enc_len)
    if kind in ("paged", "sharded-paged"):
        if shards is not None and kind == "paged" and shards > 1:
            kind = "sharded-paged"
        if kind == "sharded-paged" and shards is not None:
            kw.setdefault("n_shards", shards)
        size_request = pool is None and "num_blocks" not in kw and max_seq
        # honor the caller's capacity request: room for `batch` lanes of
        # max_seq tokens (+1 decode slot each)
        bs = kw.get("block_size", 16)
        lane_blocks = -(-(max_seq + 1) // bs)
        if kind == "paged":
            if size_request:
                kw["num_blocks"] = batch * lane_blocks
            return PagedBackend(cfg, pool=pool, device=device, **kw)
        if device is not None:
            raise ValueError(
                "sharded-paged takes per-shard devices=[...], not device=")
        if size_request:
            from repro.kvcache.sharded_pool import discover_shards
            n = kw["n_shards"] = discover_shards(kw.get("n_shards"),
                                                 kw.get("mesh"))
            # a lane never spans shards, so splitting batch*lane_blocks
            # evenly would under-size shards whenever n does not divide
            # batch: every shard must hold its share of WHOLE lanes
            kw["num_blocks"] = n * (-(-batch // n)) * lane_blocks
        return ShardedPagedBackend(cfg, pool=pool, **kw)
    raise ValueError(f"unknown KV backend kind {kind!r}")
