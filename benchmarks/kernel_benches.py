"""TPU-adaptation benchmarks (beyond-paper): MARS dispatch/gather vs the
locality-oblivious baselines.

CPU wall-time is NOT the roofline metric (that's the dry-run's job); what
these benches report as ``derived`` is the access-pattern statistic the
reorder exists to improve — destination-run length (the CAS/ACT analogue)
— plus compute-cost ratios of baseline vs MARS paths.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np


def _run_len(a: np.ndarray) -> float:
    return float(np.diff(np.flatnonzero(np.concatenate(
        [[True], a[1:] != a[:-1], [True]]))).mean())


def _timeit(fn, *args, n=5):
    fn(*args)[0].block_until_ready() if isinstance(fn(*args), tuple) \
        else jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(n):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / n * 1e6


def bench_moe_dispatch(emit):
    from repro.models import moe as moe_mod
    from repro.models.config import ModelConfig
    from repro.kernels.moe_dispatch import ops

    cfg = ModelConfig(name="bench", family="moe", n_layers=1, d_model=256,
                      n_heads=4, n_kv_heads=4, d_ff=512, vocab=128,
                      n_experts=32, top_k=2, d_expert=512)
    params = moe_mod.moe_init(jax.random.key(0), cfg).params
    T = 2048
    x = jax.random.normal(jax.random.key(1), (T, cfg.d_model))
    idx, gates, _ = moe_mod.router_topk(params, x, cfg)

    w_in, w_gate, w_out = moe_mod.expert_stacks(params)
    us_mars = _timeit(jax.jit(lambda x, i, g: ops.mars_moe_ffn(
        x, i, g, w_in, w_gate, w_out, n_experts=32)), x, idx, gates)
    us_base = _timeit(jax.jit(lambda x: moe_mod.moe_apply_einsum(
        params, x, cfg)[0]), x)
    flat = np.asarray(idx).reshape(-1)
    emit("moe_dispatch/mars_sorted", us_mars,
         f"run_len={_run_len(np.sort(flat)):.1f}")
    emit("moe_dispatch/einsum_baseline", us_base,
         f"run_len={_run_len(flat):.2f}")
    emit("moe_dispatch/speedup", 0.0, f"{us_base/us_mars:.2f}x")


def bench_mars_gather(emit):
    from repro.kernels.mars_gather import ops
    table = jax.random.normal(jax.random.key(0), (1 << 15, 512))
    rng = np.random.default_rng(0)
    ids = jnp.asarray((rng.zipf(1.3, 1 << 14) % (1 << 15)).astype(np.int32))
    us_plain = _timeit(jax.jit(lambda t, i: ops.embedding_gather(
        t, i, mode="plain")), table, ids)
    us_sorted = _timeit(jax.jit(lambda t, i: ops.embedding_gather(
        t, i, mode="sorted")), table, ids)
    pages = np.asarray(ids) >> 2
    emit("mars_gather/plain", us_plain,
         f"page_run={_run_len(pages):.2f}")
    emit("mars_gather/sorted", us_sorted,
         f"page_run={_run_len(np.sort(pages)):.1f}")


def bench_scheduler(emit):
    from repro.serving.scheduler import MarsScheduler, Request, \
        unique_prefix_blocks
    rng = np.random.default_rng(0)
    prefixes = [tuple(rng.integers(1, 100, 16).tolist()) for _ in range(16)]
    reqs = [Request(rid=i, prompt=prefixes[i % 16]
                    + tuple(rng.integers(1, 100, 4).tolist()),
                    arrival=i * 1e-3, prefix_len=16) for i in range(256)]
    for mars in (False, True):
        sched = MarsScheduler(mars=mars)
        pend = list(reqs)
        blocks, batches = 0, 0
        t0 = time.perf_counter()
        while pend or len(sched):
            while pend and sched.offer(pend[0]):
                pend.pop(0)
            b = sched.schedule_batch(16, now=1.0)
            if not b:
                break
            blocks += unique_prefix_blocks(b)
            batches += 1
        us = (time.perf_counter() - t0) * 1e6
        emit(f"scheduler/{'mars' if mars else 'fifo'}", us,
             f"prefix_blocks_per_batch={blocks/max(batches,1):.2f}")


def bench_mars_engine(emit):
    from repro.core import mars, streams
    wl = streams.make_workload("WL1", reqs_per_core=128)
    ports = np.asarray(wl.source) // 8
    t0 = time.perf_counter()
    perm, stats = mars.mars_reorder(wl.addr, ports,
                                    src=np.asarray(wl.source))
    us = (time.perf_counter() - t0) * 1e6
    emit("mars_engine/reorder_8192req", us,
         f"cycles={stats['total_cycles']}")


def run(emit):
    bench_moe_dispatch(emit)
    bench_mars_gather(emit)
    bench_scheduler(emit)
    bench_mars_engine(emit)
