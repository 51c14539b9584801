"""Batched serving through the paged KV-cache pool + MARS scheduler.

    PYTHONPATH=src python examples/serve_paged.py

All three MARS layers of the serving stack:
  1. the ONLINE scheduler (software RequestQ) grouping requests by KV
     prefix block, vs FIFO batching, driving a real smoke model;
  2. the MEMORY subsystem: continuous batching over the block pool —
     prefix-shared blocks, MARS-aware placement, copy-on-write forks,
     pool-capacity admission;
  3. the BULK kernel: paged_attention reading the pool's block tables
     (interpreted on the CPU, compiled on a TPU), validated against the
     dense jnp oracle;
  4. the FULL LM: a real multi-layer config served through the unified
     KV-backend API (``PagedBackend``), token-exact against the dense
     backend.
"""
import numpy as np

from repro.kvcache import BlockPool, PoolConfig
from repro.launch import serve
from repro.serve.engine import ServeEngine
from repro.serving.scheduler import MarsScheduler, Request

# 1. scheduler comparison (runs a real smoke model underneath)
results = serve.main(["--arch", "qwen1_5_0_5b", "--smoke",
                      "--requests", "48", "--batch", "8"])

# 2 + 3. continuous batching over the pool, decode via the Pallas kernel
rng = np.random.default_rng(0)
prefixes = [tuple(rng.integers(1, 100, 20).tolist()) for _ in range(4)]
reqs = []
for i in range(24):
    reqs.append(Request(rid=i, prompt=prefixes[i % 4]
                        + tuple(rng.integers(1, 100, 4).tolist()),
                        arrival=i * 1e-3, max_new=6,
                        n_samples=3 if i == 0 else 1))  # forks exercise CoW

outs = {}
for use_kernel in (False, True):
    pool = BlockPool(PoolConfig(num_blocks=96, block_size=16,
                                n_kv_heads=2, head_dim=64))
    eng = ServeEngine(pool, MarsScheduler(pool=pool), max_lanes=6,
                      use_kernel=use_kernel)
    outs[use_kernel] = eng.run(reqs)
    pool.check_invariants()
    if use_kernel:
        print(f"[example] paged pool: served={len(outs[use_kernel])} "
              f"prefix_hits={pool.stats.prefix_hits} "
              f"cow_copies={pool.stats.cow_copies} "
              f"evictions={pool.stats.evictions} "
              f"pool_rejects={eng.scheduler.stats.pool_rejects}")

assert outs[False] == outs[True], "kernel vs oracle serving paths diverged"
print("[example] paged_attention kernel serving matches dense oracle "
      f"on {sum(len(v) for v in outs[True].values())} sequences")

# 4. full-LM paged serving: qwen smoke config, every layer's KV in the
# layered pool, parity against the dense backend asserted inside
serve.main(["--paged", "--config", "qwen1_5_0_5b", "--smoke",
            "--requests", "12", "--batch", "4", "--new-tokens", "5"])
