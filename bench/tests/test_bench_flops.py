"""The operation and byte counts, checked against brute force."""
import json

import pytest

from benchkit import flops, refmodel
from conftest import BENCH


def _model(name):
    conf = json.loads((BENCH / "configs" / f"{name}.json").read_text())
    return refmodel.Model.from_config(conf["model"])


def _pages_brute(ln, window, bs):
    """Pages holding the cached positions the in-flight token at ``ln``
    attends: ``[ln - window + 1, ln)`` under a window, else ``[0, ln)``."""
    lo = max(ln - window + 1, 0) if window else 0
    pos = range(lo, ln)
    return len({p // bs for p in pos}), len(pos)


@pytest.mark.parametrize("window", [0, 16, 1024])
@pytest.mark.parametrize("ln", [0, 1, 15, 16, 17, 1023, 1024, 1025, 2047,
                                2600])
def test_kernel_pages_match_brute_force(ln, window):
    assert flops.kernel_pages(ln, window, 16) == _pages_brute(ln, window, 16)


def test_hymba_window_gate_and_global_layers():
    m = _model("hymba_1_5b")
    glob = [li for li in range(m.n_layers) if not flops.layer_window(m, li)]
    assert glob == [0, 16]
    long_ctx = [4000]
    f, b = flops.attention_kernel_cost(m, long_ctx, 16)
    page = 2 * 16 * m.n_kv_heads * m.d_head * 2
    pages = 2 * (-(-4000 // 16)) + 30 * _pages_brute(4000, 1024, 16)[0]
    per_lane = 2 * m.n_heads * m.d_head * 2 + 2 * m.n_heads * 4
    assert b == pages * page + m.n_layers * per_lane
    seen = 2 * 4000 + 30 * 1023
    assert f == 4 * m.n_heads * m.d_head * seen


def test_qwen_decode_flops_by_hand():
    m = _model("qwen1_5_0_5b")
    d, f, V = 1024, 2816, 151936
    per_layer = 2 * d * 3 * d + 2 * d * d + 2 * 3 * d * f
    ctx = 100
    want = 24 * per_layer + 2 * d * V + 24 * 4 * d * (ctx + 1)
    assert flops.decode_token_flops(m, ctx) == want


def test_hostdev_bytes_by_hand():
    m = _model("qwen1_5_0_5b")
    ctx = [100, 20, 300]            # 3 lanes pad to 4; 301 -> 19 -> 32 pages
    block = 2 * 24 * 16 * 16 * 64 * 2
    want = 5 * block + 4 * 4 * 2 + 4 * 32 * 4 \
        + 2 * 24 * 4 * 16 * 64 * 2 + 4 * 151936 * 2
    assert flops.decode_hostdev_bytes(m, ctx, 5, 16) == want
    h = _model("hymba_1_5b")
    base = flops.decode_hostdev_bytes(h, [10], 0, 16)
    ssm = 2 * 32 * 1 * 50 * 64 * 16 * 4 + 2 * 32 * 1 * 3 * (3200 + 32) * 2
    assert base - ssm == 4 * 2 + 1 * 1 * 4 + 2 * 32 * 5 * 64 * 2 \
        + 32001 * 2
