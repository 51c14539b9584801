"""The traffic generator: deterministic per seed, the same work for
every seed, and faithful to the mix's parameters."""
import collections
import json

import numpy as np
import pytest

from benchkit import harness, traffic
from conftest import BENCH

MIXES = {p.stem: json.loads(p.read_text())
         for p in sorted((BENCH / "traffic").glob("*.json"))}
BIG_SEED = 2**31 + 12345


def _open(mix, seed, seconds=60.0):
    return traffic.open_loop(mix, seed, seconds)


def _specs(mix, seed):
    if mix["loop"] == "open":
        return _open(mix, seed)
    return traffic.closed_loop(mix, seed, n=512)


@pytest.mark.parametrize("name", sorted(MIXES))
def test_same_seed_same_requests(name):
    mix = MIXES[name]
    a, b = _specs(mix, BIG_SEED), _specs(mix, BIG_SEED)
    assert a == b
    assert traffic.tokens(BIG_SEED, 3, 64, 1000) == \
        traffic.tokens(BIG_SEED, 3, 64, 1000)
    assert _specs(mix, BIG_SEED + 1) != a


@pytest.mark.parametrize("name", sorted(MIXES))
def test_every_seed_does_the_same_work(name):
    mix = MIXES[name]
    a, b = _specs(mix, 1), _specs(mix, BIG_SEED)
    key = lambda s: (s.prompt_len, s.max_new)
    assert sorted(map(key, a)) != [key(s) for s in a] or len(a) < 3
    assert collections.Counter(s.prompt_len for s in a) == \
        collections.Counter(s.prompt_len for s in b)
    assert collections.Counter(s.max_new for s in a) == \
        collections.Counter(s.max_new for s in b)


@pytest.mark.parametrize("name", sorted(MIXES))
def test_lengths_keep_to_the_ladder_and_bounds(name):
    mix = MIXES[name]
    specs = _specs(mix, 7)
    ladder = set(mix["prompt"]["ladder"])
    assert {s.prompt_len for s in specs} <= ladder
    out = mix["output"]
    lo, hi = out["uniform"] if "uniform" in out else (out["min"], out["max"])
    assert all(lo <= s.max_new <= hi for s in specs)
    assert traffic.max_output(mix) == hi
    lens = np.array([s.prompt_len for s in specs])
    assert np.median(lens) >= min(ladder)


def test_open_loop_keeps_its_rate():
    mix = MIXES["chat"]
    seconds = 200.0
    specs = _open(mix, 99, seconds)
    due = [s.due for s in specs]
    ramp = mix["ramp_s"]
    end = ramp + seconds
    assert due == sorted(due) and due[0] == 0.0 and due[-1] < end
    assert len(specs) == pytest.approx(mix["rate_per_s"] * end, abs=2)


@pytest.mark.parametrize("seeds", [(1, 2), (3, BIG_SEED)])
def test_the_window_holds_the_same_work_for_every_seed(seeds):
    mix = MIXES["chat"]
    ramp = mix["ramp_s"]
    win = [[(s.prompt_len, s.max_new) for s in _open(mix, seed, 51.0)
            if s.due >= ramp] for seed in seeds]
    assert len(win[0]) == len(win[1]) == round(mix["rate_per_s"] * 51)
    assert sorted(win[0]) == sorted(win[1]) and win[0] != win[1]


def test_prompt_median_follows_the_mix():
    mix = MIXES["chat"]
    lens = traffic.prompt_lengths(mix["prompt"], 4001)
    ladder = sorted(mix["prompt"]["ladder"])
    # the median draw rounds up to the rung at or above the mix's median
    want = min(r for r in ladder if r >= mix["prompt"]["median"])
    assert float(np.median(lens)) == want


class _FakeEngine:
    """Runs up to ``lanes`` requests at a time, one token a step each,
    and finishes each at its ``max_new``."""

    class _Sched:
        def __init__(self):
            self.q = []

        def __len__(self):
            return len(self.q)

    def __init__(self, lanes=3):
        self.scheduler = self._Sched()
        self.running, self.paused, self.finished = [], [], {}
        self.lanes = lanes

    def submit(self, req):
        self.scheduler.q.append(req)
        return True

    def step(self, now=0.0):
        while self.scheduler.q and len(self.running) < self.lanes:
            r = self.scheduler.q.pop(0)
            self.running.append(_Seq(r.rid, r.max_new))
        for s in self.running:
            s.n_generated += 1
        for s in [s for s in self.running if s.n_generated >= s.max_new]:
            self.running.remove(s)
            self.finished[s.rid] = [[1] * s.max_new]
        return len(self.running)


class _Seq:
    def __init__(self, rid, max_new):
        self.rid, self.max_new, self.n_generated = rid, max_new, 0


class _Request:
    def __init__(self, rid, prompt, arrival, max_new):
        self.rid, self.prompt, self.max_new = rid, prompt, max_new


class _NoTrace:
    def poll(self, now):
        pass

    def stop(self):
        pass


def test_closed_loop_keeps_its_client_count(monkeypatch):
    mix = {"loop": "closed", "clients": 5,
           "prompt": {"median": 8, "sigma": 0.5, "ladder": [8, 16]},
           "output": {"uniform": [20, 40]}, "ramp_s": 0}
    eng = _FakeEngine(lanes=3)
    driver = harness.Driver(eng, _Request, harness.Recorder(), 3, 50)
    outstanding = []
    step = driver.step

    def counted():
        outstanding.append(len(driver.active) + len(driver.queue))
        return step()

    monkeypatch.setattr(driver, "step", counted)
    import time

    import jax
    with jax.profiler.TraceAnnotation("warm"):   # before the window opens
        pass
    t = time.perf_counter()
    reqs = harness._closed_loop(driver, mix, 3, t, t + 0.3, _NoTrace())
    assert len(reqs) > 5 * 2
    # every finished request is replaced at once: five callers, always
    assert all(n == 5 for n in outstanding)


def test_bursts_keep_the_mean_rate_and_arrive_together():
    mix = dict(MIXES["chat"], burst={"size": 4})
    specs = _open(mix, 5, 400.0)
    due = [s.due for s in specs]
    assert len(specs) == pytest.approx(
        mix["rate_per_s"] * (400 + mix["ramp_s"]), abs=8)
    clumps = collections.Counter(due)
    assert set(clumps.values()) == {4}
    assert _open(mix, 5, 400.0) == specs


def test_shared_prefixes_follow_zipf_and_share_tokens():
    mix = dict(MIXES["chat"], prompt={"median": 1024, "sigma": 0.3,
                                      "ladder": [1024, 2048]},
               prefix={"count": 4, "length": 512, "zipf_s": 1.0})
    specs = _open(mix, 3, 300.0)
    counts = collections.Counter(s.prefix for s in specs)
    w = np.array([1, 1 / 2, 1 / 3, 1 / 4])
    want = len(specs) * w / w.sum()
    assert [counts[i] for i in range(4)] == pytest.approx(want, abs=2)
    a, b = [s for s in specs if s.prefix == 0][:2]
    pa, pb = (traffic.prompt(mix, 3, s, 1000) for s in (a, b))
    assert len(pa) == a.prompt_len and pa[:512] == pb[:512]
    assert pa[512:] != pb[512:]
    with pytest.raises(ValueError):
        traffic.open_loop(dict(mix, prompt=dict(mix["prompt"],
                                                ladder=[256, 2048])), 3, 10)


def test_drain_limit_follows_the_longest_output():
    mix = {"output": {"median": 192, "sigma": 0.6, "min": 16, "max": 512},
           "drain_gap_s": 0.25}
    assert traffic.drain_s(mix) == 128.0
    mix["output"] = {"uniform": [128, 300]}
    assert traffic.drain_s(mix) == 75.0
