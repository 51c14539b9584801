"""One generator for every traffic mix; a mix is a JSON file of
parameters under ``bench/traffic/``.

Keys of a mix file:

  loop         "open" (arrivals on a schedule, Poisson) or "closed"
               (``clients`` callers, each sending its next request when
               its last one completes)
  rate_per_s   open loop: mean arrivals per second
  burst        open loop, optional: {"size": k}: requests arrive in
               clumps of k due at the same instant, the clumps Poisson at
               rate_per_s / k (the mean rate is unchanged)
  clients      closed loop: number of callers
  prompt       {"median", "sigma", "ladder"}: lognormal prompt lengths,
               rounded up to the next rung of ``ladder`` (clipped to its
               top rung)
  output       {"median", "sigma", "min", "max"}: lognormal output
               lengths, clipped; or {"uniform": [lo, hi]}
  prefix       optional: {"count", "length", "zipf_s"}: every prompt
               starts with one of ``count`` shared prefixes of ``length``
               tokens, prefix i drawn with weight 1 / (i + 1) ** zipf_s;
               the rest of the prompt is the request's own.  Every rung
               of the prompt ladder must be at least ``length``.
  ramp_s       seconds of this traffic served before the window opens
  drain_gap_s  open loop: seconds per output token that a request due in
               the window may take to finish after it closes; the drain
               limit is the mix's longest output times this

Every seed gets the same multiset of sizes and inter-arrival gaps, read
off the distributions' quantiles (a closed loop: the same multiset in
every round of ``clients`` requests); the seed only shuffles their order
and draws the token ids.  So two seeds do the same amount of work, in a
different order, and differ no more than two runs of one seed do.
Prompt tokens are uniform over the vocabulary, so without ``prefix`` no
prefix is shared.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import Optional

import numpy as np

_NORMAL = NormalDist()


@dataclasses.dataclass
class Spec:
    """One request before it has tokens: ``due`` in seconds after the
    traffic starts (open loop; None for a closed loop)."""
    rid: int
    prompt_len: int
    max_new: int
    due: Optional[float] = None
    prefix: Optional[int] = None    # shared prefix index (``prefix`` key)


def _quantile_points(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def _lognormal(n: int, median: float, sigma: float) -> np.ndarray:
    z = np.asarray([_NORMAL.inv_cdf(u) for u in _quantile_points(n)])
    return median * np.exp(sigma * z)


def prompt_lengths(spec: dict, n: int) -> np.ndarray:
    """``n`` prompt lengths on the mix's ladder, quantile-spaced."""
    ladder = np.asarray(sorted(spec["ladder"]), np.int64)
    raw = _lognormal(n, spec["median"], spec["sigma"])
    idx = np.searchsorted(ladder, raw, side="left")
    return ladder[np.minimum(idx, len(ladder) - 1)]


def output_lengths(spec: dict, n: int) -> np.ndarray:
    """``n`` exact output lengths (``max_new``), quantile-spaced."""
    if "uniform" in spec:
        lo, hi = spec["uniform"]
        out = lo + (hi - lo) * _quantile_points(n)
    else:
        out = np.clip(_lognormal(n, spec["median"], spec["sigma"]),
                      spec["min"], spec["max"])
    return np.rint(out).astype(np.int64)


def max_output(mix: dict) -> int:
    o = mix["output"]
    return int(o["uniform"][1]) if "uniform" in o else int(o["max"])


def drain_s(mix: dict) -> float:
    """How long after the window closes the requests due in it may take
    to finish: the longest output at ``drain_gap_s`` a token."""
    return max_output(mix) * float(mix["drain_gap_s"])


def _shuffle(rng: np.random.Generator, a: np.ndarray) -> np.ndarray:
    return a[rng.permutation(len(a))]


def _prefixes(mix: dict, n: int) -> np.ndarray:
    """``n`` shared-prefix indices in Zipf proportion (largest
    remainder), unshuffled; -1 for every request without ``prefix``."""
    spec = mix.get("prefix")
    if spec is None:
        return np.full(n, -1, np.int64)
    if min(mix["prompt"]["ladder"]) < spec["length"]:
        raise ValueError("every prompt rung must hold the shared prefix")
    w = 1.0 / (np.arange(spec["count"]) + 1.0) ** spec["zipf_s"]
    want = n * w / w.sum()
    counts = np.floor(want).astype(np.int64)
    extra = np.argsort(-(want - counts), kind="stable")[:n - counts.sum()]
    counts[extra] += 1
    return np.repeat(np.arange(spec["count"]), counts)


def _specs(mix: dict, rng: np.random.Generator, n: int, rid0: int,
           due=None) -> list:
    """``n`` requests: the quantile-spaced sizes, paired by a permutation
    that depends on ``n`` alone, then shuffled by ``rng`` as pairs."""
    pair = np.random.default_rng([n, 7]).permutation(n)
    order = rng.permutation(n)
    prompts = prompt_lengths(mix["prompt"], n)[order]
    outs = output_lengths(mix["output"], n)[pair][order]
    pre = _prefixes(mix, n)[np.random.default_rng([n, 8]).permutation(n)]
    pre = pre[order]
    return [Spec(rid0 + i, int(prompts[i]), int(outs[i]),
                 None if due is None else float(due[i]),
                 None if pre[i] < 0 else int(pre[i])) for i in range(n)]


def _segment(mix: dict, rng: np.random.Generator, start: float,
             seconds: float, rid0: int) -> list:
    """The requests due in ``[start, start + seconds)``: ``rate_per_s *
    seconds`` of them (clumps, with ``burst``), the inter-arrival gaps
    read off the exponential's quantiles, shuffled, and scaled to fill
    the stretch exactly; sizes quantile-spaced and shuffled."""
    rate = float(mix["rate_per_s"])
    size = int(mix.get("burst", {}).get("size", 1))
    groups = max(int(round(rate * seconds / size)), 1)
    gaps = _shuffle(rng, -np.log1p(-_quantile_points(groups)))
    at = np.concatenate([[0.0], np.cumsum(gaps)[:-1]]) / gaps.sum()
    due = start + seconds * np.repeat(at, size)
    return _specs(mix, rng, len(due), rid0, due)


def open_loop(mix: dict, seed: int, seconds: float) -> list:
    """Requests due over the ramp (``ramp_s``) and then a window of
    ``seconds``, in seconds from the start: Poisson arrivals at
    ``rate_per_s``.  Each stretch gets its own fixed set of gaps and
    sizes, so the window holds the same work whatever the seed."""
    rng = np.random.default_rng([seed, 0])
    ramp = float(mix.get("ramp_s", 0))
    specs = _segment(mix, rng, 0.0, ramp, 0) if ramp > 0 else []
    return specs + _segment(mix, rng, ramp, float(seconds), len(specs))


def closed_loop(mix: dict, seed: int, n: int = 4096) -> list:
    """The sequence the ``clients`` callers draw their requests from, in
    order: rounds of ``clients`` requests, each round the same
    quantile-spaced sizes shuffled by ``seed``, so that any stretch of
    the sequence holds the same work whatever the seed."""
    rng = np.random.default_rng([seed, 0])
    k = int(mix["clients"])
    specs = []
    while len(specs) < n:
        specs += _specs(mix, rng, k, len(specs))
    return specs[:n]


def prompt(mix: dict, seed: int, spec: Spec, vocab: int) -> tuple:
    """The prompt's token ids: its shared prefix's, if it has one, then
    its own."""
    if spec.prefix is None:
        return tokens(seed, spec.rid, spec.prompt_len, vocab)
    n = int(mix["prefix"]["length"])
    head = np.random.default_rng([seed, 2, spec.prefix]).integers(
        0, vocab, n)
    return tuple(int(t) for t in head) + \
        tokens(seed, spec.rid, spec.prompt_len - n, vocab)


def tokens(seed: int, rid: int, length: int, vocab: int) -> tuple:
    """Prompt token ids of request ``rid``: uniform over the vocabulary,
    from ``(seed, rid)`` alone, so they do not depend on the order in
    which requests are made."""
    rng = np.random.default_rng([seed, 1, rid])
    return tuple(int(t) for t in rng.integers(0, vocab, length))
