"""Plain float32 reference of hymba-1.5B as the repository builds it
(hybrid decoder, arXiv:2411.13676).

Per layer: the attention heads and the Mamba2 heads read the same input
in parallel, each through its own RMSNorm, and the residual gains the
mean of their outputs: ``x += (attn(n1(x)) + ssm(n2(x))) / 2``, then
``x += mlp(rmsnorm(x))``.  Attention is GQA (25 query heads over 5 KV
heads) with rotate-half RoPE; layers ``0`` and ``16`` attend globally,
the rest over a sliding window of 1024 positions.  The SSM is Mamba2 with
50 heads of 64 channels, state 16, one B/C group, a width-4 causal
convolution over x and over B/C, and a gated RMSNorm before its output
projection; it is evaluated here by its recurrence, not by the chunked
SSD form the program uses.

Departures from the paper, all of them the repository's model and
listed under ``assumed`` in ``hymba_1_5b.json``: no meta tokens, no
cross-layer KV sharing, global layers at every 16th layer instead of the
first, middle and last.  Weights are random from the seed.
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
