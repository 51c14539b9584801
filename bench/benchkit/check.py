"""The comparison that decides ``correct`` for a served model.

After the window has closed and the program's state is freed, a sample
of the requests the program finished, drawn from the seed and always
holding the longest, is run once through the configuration's plain
float32 reference, teacher-forced along each prompt and the tokens the
program served.  The number compared is the widest gap, over every
served token of the sample, by which the reference's logit of the served
token lies below the reference's best logit at that position.  Greedy
decoding serves the argmax, so a sound program only loses to the
reference where rounding flips a near tie.

The control reads the same gap for the token the reference computed in
float8 (``prec="fp8"``) puts first, at the same positions.
"""
from __future__ import annotations

import numpy as np


def sample(done: list, seed: int, min_tokens: int, min_requests: int,
           max_requests: int) -> list:
    """Finished requests to check: the longest (prompt plus output), then
    others in an order drawn from ``seed`` until the sample holds
    ``min_tokens`` served tokens and ``min_requests`` requests, at most
    ``max_requests``.  ``done``: records with ``prompt`` and ``out``."""
    if not done:
        return []
    longest = max(range(len(done)),
                  key=lambda i: (len(done[i].prompt) + len(done[i].out), -i))
    rng = np.random.default_rng([seed, 2])
    order = [longest] + [int(i) for i in rng.permutation(len(done))
                         if i != longest]
    out, n = [], 0
    for i in order:
        if len(out) >= max_requests or (n >= min_tokens
                                        and len(out) >= min_requests):
            break
        out.append(done[i])
        n += len(done[i].out)
    return out


def _pad(xs, n):
    a = np.zeros(n, np.int32)
    a[:len(xs)] = xs
    return a


def gaps(logits_fn, params, m, prompt, served, s_pad: int, n_pad: int,
         prec: str = "f32") -> np.ndarray:
    """Per served token: reference best logit minus the reference logit
    of the token ``prec`` picks (``f32``: the token the program served;
    ``fp8``: the control's own argmax)."""
    seq = list(prompt) + list(served[:-1])
    at = np.arange(len(prompt) - 1, len(prompt) - 1 + len(served))
    ref = logits_fn(params, m, _pad(seq, s_pad), _pad(at, n_pad), "f32")
    ref = np.asarray(ref)[:len(served)]
    if prec == "f32":
        pick = np.asarray(served)
    else:
        lo = logits_fn(params, m, _pad(seq, s_pad), _pad(at, n_pad), prec)
        pick = np.asarray(lo)[:len(served)].argmax(-1)
    return ref.max(-1) - ref[np.arange(len(served)), pick]


def widest_gap(logits_fn, params, m, reqs: list, s_pad: int, n_pad: int,
               prec: str = "f32") -> tuple:
    """(widest gap, served tokens compared) over ``reqs``."""
    widest, n = 0.0, 0
    for r in reqs:
        g = gaps(logits_fn, params, m, r.prompt, r.out, s_pad, n_pad, prec)
        widest = max(widest, float(g.max()))
        n += len(g)
    return widest, n
