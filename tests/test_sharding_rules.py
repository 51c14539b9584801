"""Sharding rules: divisibility fallbacks, memory accounting, cache specs."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.sharding import rules


@pytest.fixture(scope="module")
def mesh():
    n = len(jax.devices())
    return jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)


def _spec(axes, shape, mesh, fsdp=True):
    return rules.spec_for(axes, shape, rules.logical_rules(mesh, fsdp), mesh)


def _norm(spec):
    # older jax does not canonicalize PartitionSpec('x') == P(('x',))
    return tuple(e if isinstance(e, tuple) or e is None else (e,)
                 for e in spec)


def test_divisible_dims_get_primary_mapping(mesh):
    # 16-way mesh axes of size 1 always divide: primary mappings hold
    s = _spec(("embed", "heads", "head"), (1024, 16, 64), mesh)
    assert _norm(s) == _norm(P(("data",), "model", None))


def test_nondivisible_heads_fall_back_to_head_dim():
    mesh16 = jax.sharding.Mesh(
        np.array(jax.devices() * 16)[:16].reshape(1, 16), ("data", "model"))
    s = rules.spec_for(("embed", "heads", "head"), (7168, 56, 128),
                       rules.logical_rules(mesh16), mesh16)
    assert s[1] is None and s[2] == "model"   # heads 56 % 16 != 0 -> head dim


def test_nondivisible_vocab_replicates():
    mesh16 = jax.sharding.Mesh(
        np.array(jax.devices() * 16)[:16].reshape(1, 16), ("data", "model"))
    s = rules.spec_for(("vocab", "embed"), (50280, 1024),
                       rules.logical_rules(mesh16, fsdp=False), mesh16)
    assert s[0] is None  # vocab replicated; model falls back to embed dim
    assert s[1] == "model"


def test_no_axis_used_twice():
    mesh16 = jax.sharding.Mesh(
        np.array(jax.devices() * 32)[:32].reshape(2, 16), ("data", "model"))
    s = rules.spec_for(("expert", "embed", "mlp"), (128, 7168, 4864),
                       rules.logical_rules(mesh16), mesh16)
    used = [a for a in s if a is not None]
    flat = []
    for a in used:
        flat.extend(a if isinstance(a, tuple) else (a,))
    assert len(flat) == len(set(flat))


def test_sharded_bytes_accounting():
    mesh16 = jax.sharding.Mesh(
        np.array(jax.devices() * 16)[:16].reshape(1, 16), ("data", "model"))
    tree = [jax.ShapeDtypeStruct((64, 128), jnp.bfloat16)]
    sh = [jax.NamedSharding(mesh16, P(None, "model"))]
    b = rules.sharded_bytes_per_device(tree, sh, mesh16)
    assert b == 64 * 8 * 2
    # padding: 56 over 16 -> ceil = 4 rows/device
    tree = [jax.ShapeDtypeStruct((56, 10), jnp.float32)]
    sh = [jax.NamedSharding(mesh16, P("model", None))]
    assert rules.sharded_bytes_per_device(tree, sh, mesh16) == 4 * 10 * 4


def test_batch_sharding_divisibility(mesh):
    assert rules.batch_sharding(mesh, 4).spec == P(("data",))
    assert rules.batch_sharding(mesh, 1).spec == P(("data",))  # 1 % 1 == 0


def test_cache_shardings_kv_vs_seq():
    from repro import configs
    mesh16 = jax.sharding.Mesh(
        np.array(jax.devices() * 256)[:256].reshape(16, 16),
        ("data", "model"))
    qwen = configs.get("qwen1_5_0_5b")       # kv=16 divides
    cs = rules.cache_shardings(mesh16, qwen, batch=128)
    assert cs.k.spec == P(None, ("data",), None, "model", None)
    dsk = configs.get("deepseek_coder_33b")  # kv=8 doesn't -> seq sharding
    cs = rules.cache_shardings(mesh16, dsk, batch=128)
    assert cs.k.spec == P(None, ("data",), "model", None, None)


def test_cache_shardings_rejects_non_dense_backends():
    """Regression: the specs assume the dense (L,B,S,K,dh) lm.Cache
    layout — a paged pool's (L,P,page,K,dh) buffer would silently
    mis-shard its page axis as if it were the sequence axis, so any
    non-dense backend must raise, pointing at ShardedBlockPool."""
    from repro import configs
    mesh16 = jax.sharding.Mesh(
        np.array(jax.devices() * 256)[:256].reshape(16, 16),
        ("data", "model"))
    qwen = configs.get("qwen1_5_0_5b")
    with pytest.raises(NotImplementedError, match="ShardedBlockPool"):
        rules.cache_shardings(mesh16, qwen, batch=128, backend="paged")
    # the dense default is untouched (dryrun.py call site)
    assert rules.cache_shardings(mesh16, qwen, batch=128,
                                 backend="dense").k is not None


def test_pool_shard_count_uses_model_axis():
    mesh16 = jax.sharding.Mesh(
        np.array(jax.devices() * 16)[:16].reshape(1, 16),
        ("data", "model"))
    assert rules.pool_shard_count(mesh16) == 16
    assert rules.pool_shard_count(None) == 1
    no_model = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("data",))
    assert rules.pool_shard_count(no_model) == 1
