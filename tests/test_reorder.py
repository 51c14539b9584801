"""The host rendering of the MARS emission order against the jax one."""
import numpy as np
import pytest

from repro.core.reorder import mars_order, mars_order_host
from repro.kernels.paged_attention import ops
from repro.kvcache.prefix import BlockTable


def _random_keys(rng, n):
    """Keys in [-1, n // 3]: repeats at every length above 2, and ``-1``."""
    return rng.integers(-1, n // 3 + 1, n).astype(np.int32)


def _sharded_lane_keys(rng, n):
    """Lane keys as ``batch_lane_order`` builds them for a sharded pool:
    tail blocks' row groups (some tables empty, keyed ``-1``) led by the
    lane's shard."""
    tables = [BlockTable([int(b)], 1) if b >= 0 else BlockTable()
              for b in rng.integers(-1, 40, n)]
    return ops.lane_keys(tables, blocks_per_group=8,
                         shard_ids=rng.integers(0, 4, n).tolist())


@pytest.mark.parametrize("make_keys", [_random_keys, _sharded_lane_keys])
@pytest.mark.parametrize("n", [0, 1, 2, 3, 17, 32, 64])
def test_host_order_is_mars_order(n, make_keys):
    keys = make_keys(np.random.default_rng(n), n)
    want = np.asarray(mars_order(keys))
    got = mars_order_host(keys)
    assert got.dtype == want.dtype
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(mars_order_host(keys.tolist()), want)
