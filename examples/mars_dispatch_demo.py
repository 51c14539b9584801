"""The paper's technique on TPU: MARS-sorted MoE dispatch.

    PYTHONPATH=src python examples/mars_dispatch_demo.py

Routes a token batch to 16 experts, then runs the expert FFN three ways:
  a. dense per-token oracle (what the math says),
  b. locality-oblivious einsum dispatch (the "no MARS" baseline),
  c. MARS-sorted grouped matmul (ragged_dot and the Pallas kernel).
All must agree; the point is the ACCESS PATTERN, quantified by the
page-run statistics printed at the end (the CAS/ACT analogue).
"""
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.moe_dispatch import ops
from repro.models import moe as moe_mod
from repro.models.config import ModelConfig

cfg = ModelConfig(name="demo", family="moe", n_layers=1, d_model=64,
                  n_heads=4, n_kv_heads=4, d_ff=96, vocab=128,
                  n_experts=16, top_k=2, d_expert=96,
                  param_dtype="float32", compute_dtype="float32")
params = moe_mod.moe_init(jax.random.key(0), cfg).params
T = 256
x = jax.random.normal(jax.random.key(1), (T, cfg.d_model))
idx, gates, _ = moe_mod.router_topk(params, x, cfg)

w_in, w_gate, w_out = moe_mod.expert_stacks(params)
y_mars = ops.mars_moe_ffn(x, idx, gates, w_in, w_gate, w_out, n_experts=16)
y_pallas = ops.mars_moe_ffn(x, idx, gates, w_in, w_gate, w_out,
                            n_experts=16, use_pallas=True, bm=32)
y_base, _ = moe_mod.moe_apply_einsum(params, x, cfg)
np.testing.assert_allclose(np.asarray(y_mars), np.asarray(y_pallas),
                           rtol=2e-4, atol=2e-4)
np.testing.assert_allclose(np.asarray(y_mars), np.asarray(y_base),
                           rtol=2e-4, atol=2e-4)
print("[example] three dispatch paths agree")

# access-pattern statistics: expert-id run lengths before/after MARS sort
flat = np.asarray(idx).reshape(-1)
runs = lambda a: np.diff(np.flatnonzero(np.concatenate(
    [[True], a[1:] != a[:-1], [True]])))
print(f"[example] expert-visit run length: interleaved {runs(flat).mean():.2f}"
      f" -> MARS-sorted {runs(np.sort(flat)).mean():.2f} "
      f"(x{runs(np.sort(flat)).mean()/runs(flat).mean():.1f} page locality)")
