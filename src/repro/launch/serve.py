"""Serving driver: continuous batching behind the MARS request scheduler.

``python -m repro.launch.serve --arch qwen1_5_0_5b --smoke --requests 64``

Demonstrates the online MARS path end-to-end: requests (some sharing
prompt prefixes = "pages") flow through the bounded scheduler; batches are
formed page-major oldest-page-first; prefix-sharing batches reuse a
prefill cache.  Reports the serving CAS/ACT analogue: unique prefix blocks
per scheduled batch, with and without MARS.
"""
from __future__ import annotations

import argparse
import os
import pathlib
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.models import lm
from repro.serve.step import greedy_generate
from repro.serving.scheduler import MarsScheduler, Request, \
    default_classes, unique_prefix_blocks

# the checkout root: src/repro/launch/serve.py -> <repo>
_REPO_ROOT = pathlib.Path(__file__).resolve().parents[3]

# --classes N: per-class decode-length profile for the synthetic stream —
# interactive stays short (chat turns), batch decodes long (summarize),
# stream sits between; the multipliers scale --new-tokens
_CLASS_NEW_TOKENS = {"interactive": 1, "batch": 4, "stream": 2}


def place_compile_cache() -> str:
    """Directory JAX keeps its persistent compilation cache in; returns it.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is read by JAX itself and
    nothing is set here.  Otherwise the cache goes to ``<repo>/.jax_cache``
    — a fixed path, so a later run from the same checkout finds what this
    one compiled.  Entry points call it first thing in ``main``; importing
    a module never does.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(_REPO_ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def synth_requests(n: int, vocab: int, n_prefixes: int = 8,
                   prefix_len: int = 16, seed: int = 0):
    """Interleaved request streams: n_prefixes hot prompt prefixes."""
    rng = np.random.default_rng(seed)
    prefixes = [tuple(rng.integers(1, vocab, prefix_len).tolist())
                for _ in range(n_prefixes)]
    out = []
    for i in range(n):
        p = prefixes[i % n_prefixes]       # round-robin = interleaved
        tail = tuple(rng.integers(1, vocab, 8).tolist())
        out.append(Request(rid=i, prompt=p + tail, arrival=i * 1e-3,
                           prefix_len=prefix_len))
    return out


def _attach_metrics(args, eng):
    """--metrics: wire an ``obs.Observer`` through the engine (spans,
    counters, live row-hit model; ``--paranoid`` adds the periodic
    incremental invariant sweep).  None when telemetry is off."""
    if not getattr(args, "metrics", False):
        return None
    from repro.obs import Observer
    return Observer(paranoid=args.paranoid).attach(eng)


def _dump_metrics(obs, args):
    """Write ``<metrics-path>/metrics.json`` (registry snapshot) and
    ``<metrics-path>/trace.jsonl`` (span/event log), then print the
    one-screen summary table."""
    if obs is None:
        return
    import json
    import os
    os.makedirs(args.metrics_path, exist_ok=True)
    snap_path = os.path.join(args.metrics_path, "metrics.json")
    trace_path = os.path.join(args.metrics_path, "trace.jsonl")
    with open(snap_path, "w", encoding="utf-8") as fh:
        json.dump(obs.snapshot(), fh, indent=1, sort_keys=True)
        fh.write("\n")
    open(trace_path, "w").close()       # fresh file; flush() appends
    n = obs.trace.flush(trace_path)
    print("[metrics] " + "-" * 50)
    for line in obs.summary_lines():
        print(f"[metrics]   {line}")
    print("[metrics] " + "-" * 50)
    print(f"[metrics] snapshot -> {snap_path}")
    print(f"[metrics] trace    -> {trace_path} ({n} events)")


def main_paged_toy(args):
    """Continuous batching over the paged KV pool (``serve.engine``) with
    the deterministic single-layer ToyModel: admission bounded by pool
    capacity, prefix-shared blocks, MARS-aware placement, CoW forks."""
    from repro.kvcache import BlockPool, PoolConfig
    from repro.serve.engine import ServeEngine

    pool = BlockPool(PoolConfig(num_blocks=args.pool_blocks, block_size=16,
                                n_kv_heads=2, head_dim=64))
    sched = MarsScheduler(pool=pool)
    eng = ServeEngine(pool, sched, max_lanes=args.batch,
                      use_kernel=args.kernel_decode)
    obs = _attach_metrics(args, eng)
    reqs = [Request(rid=r.rid, prompt=r.prompt, arrival=r.arrival,
                    prefix_len=r.prefix_len, max_new=args.new_tokens)
            for r in synth_requests(args.requests, vocab=128)]
    t0 = time.time()
    finished = eng.run(reqs)
    dt = time.time() - t0
    _dump_metrics(obs, args)
    print(f"[serve --paged] served={len(finished)} steps={eng.stats.steps} "
          f"prefill_tokens={eng.stats.prefill_tokens} "
          f"decode_tokens={eng.stats.decode_tokens} "
          f"prefix_hits={pool.stats.prefix_hits} "
          f"shared_prompt_tokens={eng.stats.shared_prompt_tokens} "
          f"evictions={pool.stats.evictions} "
          f"pool_rejects={sched.stats.pool_rejects} wall={dt:.1f}s")
    pool.check_invariants()
    return dict(served=len(finished), steps=eng.stats.steps,
                prefix_hits=pool.stats.prefix_hits,
                pool_rejects=sched.stats.pool_rejects)


def _dense_forced_logits(params, cfg, prompt, forced):
    """Teacher-force the dense backend along ``forced`` tokens; returns the
    dense logits (n, V) seen before each forced token."""
    logits, backend = lm.prefill(params, cfg,
                                 jnp.asarray([prompt], jnp.int32),
                                 max_seq=len(prompt) + len(forced) + 1)
    out = [np.asarray(logits[0, -1], np.float32)]
    for tok in forced[:-1]:
        logits = backend.decode_step(
            params, jnp.asarray([[tok]], jnp.int32))
        out.append(np.asarray(logits[0, -1], np.float32))
    return np.stack(out)


def main_paged(args):
    """Full-LM paged serving: a real ``ModelConfig`` model decoded through
    ``PagedBackend`` by the continuous-batching engine — every layer's KV
    in the layered block pool, ragged lanes, prefix sharing, CoW forks.
    Decode runs through the per-layer Pallas ``paged_attention`` kernel
    (``--kernel-decode``, default) or the gathered dense view
    (``--no-kernel-decode``).  Sliding-window configs decode on the
    kernel path natively (per-layer window mask), and hybrid families
    (``--config hymba_1_5b``) carry their per-sequence SSM/conv state
    through the backend.  ``--shards N`` partitions the pool across a
    host-device mesh (one pool + backend + staged mirror per shard,
    admissions shard-routed by the scheduler).  Cross-checks a sample of
    served sequences against the dense backend for end-to-end token
    parity."""
    if args.toy:
        return main_paged_toy(args)
    from repro.kvcache.backend import make_backend
    from repro.serve.engine import PagedLM, ServeEngine

    t_setup = time.perf_counter()
    cfg = configs.get_smoke(args.arch) if args.smoke else configs.get(args.arch)
    assert cfg.n_layers > 1, "full-LM paged serving needs a multi-layer cfg"
    params = lm.init(cfg, jax.random.key(0)).params
    decode_mode = "kernel" if args.kernel_decode else "gather"
    devices = []
    if args.shards > 1:
        # mesh-sharded serving: one block pool + paged backend per shard
        # of the serving mesh's model axis, each shard's staged mirror,
        # operands and parameter copy on its own device
        from repro.launch import mesh as mesh_mod
        from repro.sharding import context as shctx
        mesh = mesh_mod.make_serve_mesh(args.shards)
        devices = list(mesh.devices.flat)
        with shctx.use_mesh(mesh):
            pool_blocks = -(-args.pool_blocks // args.shards) * args.shards
            backend = make_backend(
                cfg, "paged", shards=args.shards, devices=devices,
                num_blocks=pool_blocks, block_size=16,
                decode_mode=decode_mode, tiered=args.tiered_kv)
        print(f"[serve --paged {cfg.name}] shards={args.shards} "
              f"devices={[d.id for d in devices]} "
              f"blocks/shard={backend.pool.shard_blocks}")
    else:
        backend = make_backend(
            cfg, "paged", num_blocks=args.pool_blocks, block_size=16,
            decode_mode=decode_mode, tiered=args.tiered_kv)
    pool = backend.pool
    classes = default_classes(args.classes) if args.classes > 1 else None
    sched = MarsScheduler(pool=pool, classes=classes)
    if args.tiered_kv and args.shards > 1:
        # admission counts a promotable lower-tier prefix hit toward
        # shard routing: land the request where its demoted blocks are
        sched.tier_probe = backend.tier_shard_for
    eng = ServeEngine(pool, sched, PagedLM(params, cfg, backend),
                      max_lanes=args.batch, pipeline=args.pipeline)
    obs = _attach_metrics(args, eng)
    cnames = [c.name for c in classes] if classes else None
    reqs = []
    for r in synth_requests(args.requests, vocab=cfg.vocab,
                            n_prefixes=args.prefixes):
        cname = cnames[r.rid % len(cnames)] if cnames else "default"
        mult = _CLASS_NEW_TOKENS.get(cname, 1) if cnames else 1
        reqs.append(Request(rid=r.rid, prompt=r.prompt, arrival=r.arrival,
                            prefix_len=r.prefix_len,
                            max_new=args.new_tokens * mult,
                            traffic_class=cname))
    t0 = time.perf_counter()
    setup_s = t0 - t_setup
    finished = eng.run(reqs)
    dt = time.perf_counter() - t0
    pool.check_invariants()
    _dump_metrics(obs, args)
    shard_note = "" if args.shards <= 1 else \
        f"shards={args.shards} shard_defers={sched.stats.shard_defers} "
    print(f"[serve --paged {cfg.name}] layers={cfg.n_layers} "
          f"decode={backend.decode_mode} "
          f"pipeline={'on' if args.pipeline else 'off'} {shard_note}"
          f"served={len(finished)} steps={eng.stats.steps} "
          f"prefill_tokens={eng.stats.prefill_tokens} "
          f"decode_tokens={eng.stats.decode_tokens} "
          f"prefix_hits={pool.stats.prefix_hits} "
          f"evictions={pool.stats.evictions} "
          f"pool_rejects={sched.stats.pool_rejects} "
          f"setup={setup_s:.1f}s wall={dt:.1f}s")
    if classes:
        for cname, cs in sched.class_stats.items():
            h = sched.wait_hist[cname]
            print(f"[serve --paged {cfg.name}] class {cname}: "
                  f"admit={cs.admit} reject={cs.reject} defer={cs.defer} "
                  f"preempt={cs.preempt} scheduled={cs.scheduled} "
                  f"wait p50={h.quantile(0.5):.1f}ms "
                  f"p99={h.quantile(0.99):.1f}ms")
    if args.tiered_kv:
        inner = getattr(backend, "backends", None) or [backend]
        tm = [b.tiers for b in inner if b.tiers is not None]
        print(f"[serve --paged {cfg.name}] tiers: "
              f"demotes={sum(t.stats.demotes for t in tm)} "
              f"promotes={sum(t.stats.promotes for t in tm)} "
              f"promoted_tokens={sum(t.stats.promoted_tokens for t in tm)} "
              f"clean_drops={sum(t.stats.clean_drops for t in tm)} "
              f"drops={sum(t.stats.drops for t in tm)} "
              f"stall_us={sum(t.stats.stall_us for t in tm):.1f}")
        for t in tm:
            t.check()

    # dense-vs-paged parity on a sample of served requests (salt-0 lane of
    # each request is plain greedy).  Gather-path decode runs the identical
    # dense math, so tokens must match the dense backend exactly.  The
    # kernel path accumulates attention in f32 (the dense path rounds
    # through the compute dtype), so its logits differ by ~1 ulp of the
    # compute dtype; the check teacher-forces the dense backend along the
    # *served* tokens and requires every served token's dense logit to be
    # within a near-tie margin of the dense argmax — exact parity up to
    # compute-dtype ties (same scheme as the fp8 near-tie tests).
    n_check = min(args.parity_checks, len(reqs))
    margin = 0.0 if backend.decode_mode == "gather" else \
        (0.0 if jnp.dtype(cfg.compute_dtype) == jnp.float32 else 5e-2)
    mismatches = exact = 0
    for req in reqs[:n_check]:
        got = finished[req.rid][0]
        dense = _dense_forced_logits(params, cfg, list(req.prompt), got)
        greedy = dense.argmax(-1)
        if list(greedy) == got:
            exact += 1
        elif any(dense[i, t] < dense[i].max() - margin
                 for i, t in enumerate(got)):
            mismatches += 1
    print(f"[serve --paged {cfg.name}] dense-vs-{backend.decode_mode} "
          f"parity: {n_check - mismatches}/{n_check} sequences match "
          f"({exact} argmax-exact, margin={margin})")
    assert mismatches == 0, \
        f"{backend.decode_mode} paged serving diverged from the dense backend"
    return dict(served=len(finished), steps=eng.stats.steps,
                prefix_hits=pool.stats.prefix_hits,
                parity_checked=n_check, parity_ok=n_check - mismatches,
                decode=backend.decode_mode,
                kernel_interpret=backend.kernel_interpret,
                devices=[d.id for d in devices],
                setup_s=setup_s, serve_s=dt)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", "--config", dest="arch", default="qwen1_5_0_5b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=64)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--new-tokens", type=int, default=8)
    ap.add_argument("--prefixes", type=int, default=8,
                    help="distinct hot prompt prefixes in the synthetic "
                         "stream; raise past the pool's cached capacity "
                         "(with --tiered-kv) to force spill traffic")
    ap.add_argument("--paged", action="store_true",
                    help="serve a real config through the paged KV backend")
    ap.add_argument("--kernel-decode", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="with --paged: decode through the per-layer Pallas "
                         "paged_attention kernel (default on; sliding-"
                         "window and hybrid configs included); "
                         "--no-kernel-decode uses the gathered dense view")
    ap.add_argument("--toy", action="store_true",
                    help="with --paged: single-layer ToyModel engine demo")
    ap.add_argument("--pipeline", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="with --paged: drive the split-phase decode "
                         "pipeline (flush -> dispatch -> sync; KV write-"
                         "back one step deferred; default on); "
                         "--no-pipeline serves through the synchronous "
                         "decode() wrapper — tokens are identical")
    ap.add_argument("--shards", type=int, default=1,
                    help="with --paged: partition the KV pool across this "
                         "many mesh shards (per-shard pools, prefix-"
                         "affinity admission routing, per-shard kernel "
                         "decode); CPU runs force a host-device mesh")
    ap.add_argument("--pool-blocks", type=int, default=256)
    ap.add_argument("--classes", type=int, default=0,
                    help="with --paged (full-LM): SMS traffic classes — "
                         "install the first N default_classes() streams "
                         "(interactive/batch/stream), stamp the synthetic "
                         "requests round-robin with per-class decode "
                         "lengths, and let overload preempt batch decodes "
                         "for interactive arrivals (0/1 = class-blind)")
    ap.add_argument("--tiered-kv", action="store_true",
                    help="with --paged: spill tiers behind the block "
                         "pool(s) — eviction demotes registered prefix "
                         "blocks to host/remote tiers, prefix misses "
                         "promote them back (MARS-reordered batched "
                         "copy-in); size --pool-blocks small to force "
                         "spill traffic")
    ap.add_argument("--parity-checks", type=int, default=4,
                    help="with --paged: served sequences re-checked densely")
    ap.add_argument("--metrics", action="store_true",
                    help="with --paged: serve instrumented (obs.Observer) "
                         "and dump a JSON metrics snapshot + JSONL span "
                         "trace, plus a one-screen summary")
    ap.add_argument("--metrics-path", default="metrics_out",
                    help="directory for metrics.json / trace.jsonl")
    ap.add_argument("--paranoid", action="store_true",
                    help="with --metrics: run the pool's incremental "
                         "invariant sweep every few engine steps")
    args = ap.parse_args(argv)

    cache_dir = place_compile_cache()
    if args.shards > 1:
        # must precede the first jax device use so the host can present a
        # multi-device CPU mesh (no-op if the backend already initialized;
        # make_serve_mesh then raises for want of devices)
        from repro.launch.mesh import request_cpu_devices
        request_cpu_devices(args.shards)

    if args.paged:
        from repro.kernels import pallas_interpret
        mode = "interpreted" if pallas_interpret() else "compiled"
        print(f"[serve] platform={jax.default_backend()} "
              f"devices={jax.device_count()} pallas={mode} "
              f"compile_cache={cache_dir}")
        return main_paged(args)

    cfg = configs.get_smoke(args.arch) if args.smoke \
        else configs.get(args.arch)
    params = lm.init(cfg, jax.random.key(0)).params

    reqs = synth_requests(args.requests, cfg.vocab)
    results = {}
    for mars in (False, True):
        sched = MarsScheduler(mars=mars)
        pending = list(reqs)
        served = 0
        blocks = 0
        batches = 0
        t0 = time.time()
        while pending or len(sched):
            while pending and sched.offer(pending[0]):
                pending.pop(0)
            batch = sched.schedule_batch(args.batch)
            if not batch:
                break
            blocks += unique_prefix_blocks(batch)
            batches += 1
            # run the batch through the dense KV backend: prefill the
            # (page-shared) prompts + greedy decode
            prompts = jnp.asarray([r.prompt for r in batch], jnp.int32)
            greedy_generate(params, cfg, prompts, args.new_tokens + 1,
                            max_seq=prompts.shape[1] + args.new_tokens + 1)
            served += len(batch)
        dt = time.time() - t0
        results[mars] = dict(served=served, batches=batches,
                             blocks_per_batch=blocks / max(batches, 1),
                             mean_wait=sched.stats.mean_wait, wall_s=dt)
        print(f"[serve] mars={mars} served={served} batches={batches} "
              f"unique-prefix-blocks/batch={blocks/max(batches,1):.2f} "
              f"wall={dt:.1f}s")
    base, mars_r = results[False], results[True]
    gain = base["blocks_per_batch"] / max(mars_r["blocks_per_batch"], 1e-9)
    print(f"[serve] MARS page-coherence gain: {gain:.2f}x fewer unique "
          f"prefix blocks per batch")
    return results


if __name__ == "__main__":
    main()
