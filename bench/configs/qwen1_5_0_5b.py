"""Plain float32 reference of qwen1.5-0.5B (dense decoder).

Per layer: ``x += attn(rmsnorm(x))``, then ``x += mlp(rmsnorm(x))``;
attention is causal multi-head attention (16 query heads, 16 KV heads)
with biases on q, k and v and rotate-half RoPE (theta 1e6); the MLP is
gated SiLU; the LM head is the transposed token embedding.

Departures from the published model: none in the mathematics.  The
weights are random from the seed (``benchkit.weights``), not the trained
checkpoint.
"""
from __future__ import annotations

import functools

import jax

from benchkit.refmodel import decoder_logits


@functools.partial(jax.jit, static_argnames=("m", "prec"))
def logits(params, m, tokens, at, prec="f32"):
    """float32 logits (len(at), vocab) of the teacher-forced ``tokens``
    at positions ``at``."""
    with jax.default_matmul_precision("highest"):
        return decoder_logits(params, m, tokens, at, prec)
