"""A run whose timed path is broken underneath reads ``correct`` false,
once for each fault a serving cell can have (CPU, smoke size)."""
import json

import numpy as np
import pytest

from conftest import run_smoke


def _break_commit(monkeypatch, change):
    """Run ``change(step)`` on each decode step's device results just
    before the backend writes them back."""
    from repro.kvcache import backend as be
    orig = be.PagedBackend._commit_pending

    def broken(self):
        if self._pending is not None:
            change(self._pending)
        return orig(self)

    monkeypatch.setattr(be.PagedBackend, "_commit_pending", broken)


def _zero_new_kv(step):
    for name in ("k", "v"):
        a = np.asarray(step.dev[name])
        step.dev[name] = np.zeros_like(a)


def _keep_old_state(step):
    step.dev["ssm"] = step.dev["conv"] = None


def _alter_token(monkeypatch):
    from repro.serve import engine
    orig = engine.PagedLM.next_token

    def altered(self, logits, salt):
        return (orig(self, logits, salt) + 1) % self.cfg.vocab

    monkeypatch.setattr(engine.PagedLM, "next_token", altered)


FAULTS = {
    # a token altered where it is produced (greedy sampling)
    "token_altered": lambda mp: _alter_token(mp),
    # the decoded token's K/V written back wrong (zeros in the pool)
    "kv_write_back": lambda mp: _break_commit(mp, _zero_new_kv),
    # a step that returns its state unchanged: SSM and conv state not
    # carried from one decode step to the next
    "state_unchanged": lambda mp: _break_commit(mp, _keep_old_state),
}
CASES = [("qwen05b.chat", "token_altered"), ("qwen05b.chat", "kv_write_back"),
         ("hymba15b.batch", "token_altered"),
         ("hymba15b.batch", "kv_write_back"),
         ("hymba15b.batch", "state_unchanged")]


@pytest.mark.parametrize("cell,fault", CASES)
def test_broken_timed_path_reads_not_correct(checkout, monkeypatch, cell,
                                             fault):
    FAULTS[fault](monkeypatch)
    res = run_smoke(checkout, cell)
    assert res["correct"] is False, json.dumps(res["check"])
