"""Fault-tolerance: checkpoint save/restore roundtrip + atomicity,
heartbeat/straggler detection, elastic re-mesh planning."""
import json
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.ft import checkpoint as ckpt
from repro.ft.manager import (ElasticPlan, HeartbeatMonitor,
                              StragglerDetector, optimal_ckpt_interval_steps,
                              plan_elastic_mesh)


def _tree(seed=0):
    k = jax.random.key(seed)
    return {"w": jax.random.normal(k, (16, 8)),
            "b": {"x": jnp.arange(10, dtype=jnp.float32),
                  "step": jnp.asarray(7)}}


def test_checkpoint_roundtrip(tmp_path):
    t = _tree()
    ckpt.save(t, 5, tmp_path)
    assert ckpt.latest_step(tmp_path) == 5
    r = ckpt.restore(jax.eval_shape(lambda: t), 5, tmp_path)
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), t, r)


def test_checkpoint_gc_keeps_last_three(tmp_path):
    for s in (1, 2, 3, 4, 5):
        ckpt.save(_tree(s), s, tmp_path)
    steps = sorted(p.name for p in Path(tmp_path).glob("step_*"))
    assert len(steps) == 3
    assert ckpt.latest_step(tmp_path) == 5


def test_checkpoint_corruption_detected(tmp_path):
    t = _tree()
    d = ckpt.save(t, 1, tmp_path)
    shard = next((d / "shards").glob("*.npy"))
    arr = np.load(shard)
    arr.flat[0] += 1.0
    np.save(shard, arr)
    with pytest.raises(IOError, match="checksum"):
        ckpt.restore(jax.eval_shape(lambda: t), 1, tmp_path)


def test_checkpoint_resharding_on_restore(tmp_path):
    """Restore onto a different mesh (elastic restart)."""
    t = {"w": jnp.arange(64, dtype=jnp.float32).reshape(16, 4)}
    ckpt.save(t, 1, tmp_path)
    mesh = jax.make_mesh((1,), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    sh = {"w": jax.NamedSharding(mesh, jax.sharding.PartitionSpec("data"))}
    r = ckpt.restore(jax.eval_shape(lambda: t), 1, tmp_path, shardings=sh)
    np.testing.assert_array_equal(np.asarray(r["w"]), np.asarray(t["w"]))
    assert r["w"].sharding.spec == jax.sharding.PartitionSpec("data")


def test_heartbeat_dead_host_detection(tmp_path):
    a = HeartbeatMonitor(tmp_path, host_id=0, timeout_s=0.2)
    b = HeartbeatMonitor(tmp_path, host_id=1, timeout_s=0.2)
    a.beat(1)
    b.beat(1)
    assert a.dead_hosts() == []
    time.sleep(0.3)
    a.beat(2)                      # host 0 alive, host 1 silent
    assert a.dead_hosts() == [1]


def test_straggler_detection():
    d = StragglerDetector(n_hosts=4, factor=1.5, patience=3)
    for step in range(10):
        for h in range(4):
            d.observe(h, 1.0 if h != 2 else 3.0)
        s = d.stragglers()
    assert s == [2]


def test_elastic_plan_preserves_model_axis():
    p = plan_elastic_mesh((2, 16, 16), ("pod", "data", "model"), 256)
    assert dict(zip(p.axis_names, p.mesh_shape))["model"] == 16
    assert np.prod(p.mesh_shape) <= 256
    # losing one pod keeps a full single-pod mesh
    assert p.mesh_shape == (1, 16, 16)


def test_elastic_plan_partial_loss():
    p = plan_elastic_mesh((2, 16, 16), ("pod", "data", "model"), 480)
    used = int(np.prod(p.mesh_shape))
    assert used <= 480 and used >= 448
    assert dict(zip(p.axis_names, p.mesh_shape))["model"] == 16


def test_elastic_plan_rejects_too_few():
    with pytest.raises(ValueError):
        plan_elastic_mesh((2, 16, 16), ("pod", "data", "model"), 8)


def test_young_daly_interval():
    # 1s steps, 30s checkpoints, 24h MTBF/host, 512 hosts
    n = optimal_ckpt_interval_steps(1.0, 30.0, 24.0, 512)
    assert 50 <= n <= 200, n
    # more hosts -> checkpoint more often
    n2 = optimal_ckpt_interval_steps(1.0, 30.0, 24.0, 2048)
    assert n2 < n


def test_train_resume_exact(tmp_path):
    """Kill-and-resume reproduces the uninterrupted run exactly."""
    from repro.launch import train as train_mod
    argv = ["--arch", "qwen1_5_0_5b", "--smoke", "--steps", "6",
            "--batch", "2", "--seq", "32", "--ckpt-interval", "3",
            "--workdir", str(tmp_path / "a"), "--log-every", "100"]
    full = train_mod.main(argv)
    argv2 = [a if a != str(tmp_path / "a") else str(tmp_path / "b")
             for a in argv]
    part = train_mod.main(argv2[:-2] + ["--steps", "3"][0:0] + argv2[-2:]
                          if False else
                          ["--arch", "qwen1_5_0_5b", "--smoke", "--steps",
                           "3", "--batch", "2", "--seq", "32",
                           "--ckpt-interval", "3",
                           "--workdir", str(tmp_path / "b"),
                           "--log-every", "100"])
    resumed = train_mod.main(
        ["--arch", "qwen1_5_0_5b", "--smoke", "--steps", "6", "--batch",
         "2", "--seq", "32", "--ckpt-interval", "3", "--workdir",
         str(tmp_path / "b"), "--resume", "--log-every", "100"])
    np.testing.assert_allclose(resumed[-1], full[-1], rtol=1e-4)
