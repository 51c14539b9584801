"""All MoE dispatch paths compute the same function (in f32):
dense oracle == einsum baseline == MARS local (also for one chip's share
of the experts) == kernels op."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.moe_dispatch import ops
from repro.models import moe as moe_mod
from repro.models.config import ModelConfig


@pytest.fixture(scope="module")
def setup():
    cfg = ModelConfig(name="eq", family="moe", n_layers=1, d_model=48,
                      n_heads=4, n_kv_heads=4, d_ff=64, vocab=64,
                      n_experts=8, top_k=2, d_expert=64,
                      param_dtype="float32", compute_dtype="float32")
    params = moe_mod.moe_init(jax.random.key(0), cfg).params
    T = 96
    x = jax.random.normal(jax.random.key(1), (T, cfg.d_model))
    idx, gates, _ = moe_mod.router_topk(params, x, cfg)
    return cfg, params, x, idx, gates


def _dense_oracle(params, x, idx, gates, first=0):
    """Every held expert on every token, then each token's routed ones
    picked (router ids ``first ..`` are the held ones; others add 0)."""
    T = x.shape[0]
    w_in, w_gate, w_out = moe_mod.expert_stacks(params)
    h = jnp.einsum("td,edf->tef", x, w_in)
    g = jnp.einsum("td,edf->tef", x, w_gate)
    o = jnp.einsum("tef,efd->ted", jax.nn.silu(g) * h, w_out)
    local = idx - first
    held = (local >= 0) & (local < w_in.shape[0])
    per = o[jnp.arange(T)[:, None], jnp.where(held, local, 0)]
    return (per * (gates * held)[..., None]).sum(1)


def test_einsum_matches_dense(setup):
    cfg, params, x, idx, gates = setup
    want = _dense_oracle(params, x, idx, gates)
    got, _ = moe_mod.moe_apply_einsum(params, x, cfg)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_mars_local_matches_dense(setup):
    cfg, params, x, idx, gates = setup
    want = _dense_oracle(params, x, idx, gates)
    got, _ = moe_mod._mars_dispatch_local(params, x, cfg)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_mars_local_share_matches_dense(setup):
    """One chip's share: experts 4-7 of a router over 16.  Assignments
    to the experts held elsewhere add nothing and are not counted."""
    cfg, _, x, _, _ = setup
    share = dataclasses.replace(cfg, n_experts=4, n_routed_experts=16,
                                first_expert=4)
    params = moe_mod.moe_init(jax.random.key(2), share).params
    idx, gates, _ = moe_mod.router_topk(params, x, share)
    want = _dense_oracle(params, x, idx, gates, first=4)
    got, aux = moe_mod._mars_dispatch_local(params, x, share)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    here = (np.asarray(idx) >= 4) & (np.asarray(idx) < 8)
    np.testing.assert_array_equal(np.asarray(aux["held"]), here.reshape(-1))
    assert 0 < here.sum() < here.size


def test_kernel_op_matches_dense(setup):
    cfg, params, x, idx, gates = setup
    want = _dense_oracle(params, x, idx, gates)
    got = ops.mars_moe_ffn(x, idx, gates, *moe_mod.expert_stacks(params),
                           n_experts=cfg.n_experts)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_moe_apply_adds_shared_expert(setup):
    cfg, params, x, idx, gates = setup
    cfg_sh = dataclasses.replace(cfg, n_shared_experts=1)
    params_sh = moe_mod.moe_init(jax.random.key(0), cfg_sh).params
    y, _ = moe_mod.moe_apply(params_sh, x[None], cfg_sh)
    y_no, _ = moe_mod.moe_apply(
        {k: v for k, v in params_sh.items() if k != "shared"},
        x[None], cfg)
    assert not np.allclose(np.asarray(y), np.asarray(y_no))
