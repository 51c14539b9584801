"""Model weights from the seed, made on the device in one jitted call.

The program's parameter tree gives the leaves' names, shapes and dtypes
(``lm.init`` under ``jax.eval_shape``, so nothing is allocated); their
values are drawn here, so the plain reference and the program compute
with the same weights and neither made them.  Every leaf is random,
biases and norm scales included, so each of them shows in the logits.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


def key_from_seed(seed: int):
    """A threefry key from any whole number ``seed`` (64 bits and more
    are fine): two 32-bit words drawn from ``numpy.random.SeedSequence``.
    """
    words = np.random.SeedSequence(int(seed)).generate_state(2)
    return jax.random.wrap_key_data(np.asarray(words, np.uint32),
                                    impl="threefry2x32")


def _leaf(key, path: tuple, shape: tuple, dtype):
    """One leaf's values.  ``path`` is the tuple of dict keys; a leaf
    under ``blocks`` carries a leading layer axis."""
    name = path[-1]
    stacked = any(p.startswith("blocks") for p in path)
    inner = shape[1:] if stacked else shape
    normal = jax.random.normal(key, shape, jnp.float32)
    if name == "tok":
        val = 0.02 * normal
    elif name in ("scale", "norm", "d_skip"):
        val = 1.0 + 0.1 * normal
    elif name in ("bias", "bq", "bk", "bv", "conv_b", "conv_b_bc",
                  "dt_bias"):
        val = 0.1 * normal
    elif name == "a_log":
        val = jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))
    elif name in ("conv_w", "conv_w_bc"):
        val = normal / math.sqrt(inner[0])
    elif path[-2:] == ("attn", "wo"):
        val = normal / math.sqrt(inner[0] * inner[1])
    else:
        val = normal / math.sqrt(inner[0])
    return val.astype(dtype)


def make_params(cfg, seed: int):
    """The parameter tree of ``repro.models.lm`` for ``cfg``, filled from
    ``seed`` on the default device, in the dtypes the program serves."""
    from repro.models import lm

    shapes = jax.eval_shape(lambda: lm.init(cfg, jax.random.key(0)).params)
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    paths = [tuple(getattr(k, "key", str(k)) for k in p) for p, _ in flat]
    avals = [(s.shape, s.dtype) for _, s in flat]

    @jax.jit
    def gen(key):
        keys = jax.random.split(key, len(avals))
        return treedef.unflatten([
            _leaf(keys[i], paths[i], shape, dtype)
            for i, (shape, dtype) in enumerate(avals)])

    params = gen(key_from_seed(seed))
    jax.block_until_ready(params)
    return params
