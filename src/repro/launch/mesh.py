"""Production mesh construction.

Single pod : (16, 16)    axes ("data", "model")   = 256 chips (v5e pod)
Multi-pod  : (2, 16, 16) axes ("pod", "data", "model") = 512 chips

Functions, not module-level constants, so importing never touches jax
device state (the dry-run must set XLA_FLAGS before first device init).
"""
from __future__ import annotations

import os

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_local_mesh() -> jax.sharding.Mesh:
    """1-device mesh with the production axis names (CPU tests)."""
    return jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


def request_cpu_devices(n: int) -> None:
    """Ask XLA for ``n`` host CPU devices (the host-device CPU mesh the
    sharded serve smoke runs on).  Must be called before the first jax
    device use — backends already initialized ignore the flag, and
    ``make_serve_mesh`` then raises for want of devices.  A TPU host
    ignores it too: there the mesh takes the chips that exist."""
    if n <= 1:
        return
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = \
            f"{flags} --xla_force_host_platform_device_count={n}".strip()


def make_serve_mesh(n_shards: int) -> jax.sharding.Mesh:
    """(1, n_shards) serving mesh, axes ("data", "model"): the model axis
    is what ``ShardedBlockPool`` partitions the KV pool over, one
    distinct device per shard.  Raises ``ValueError`` when fewer local
    devices exist than shards — two shards never share a device."""
    have = jax.local_device_count()
    if have < n_shards:
        raise ValueError(
            f"{n_shards} shards need {n_shards} devices, but only {have} "
            f"{jax.default_backend()} device(s) exist")
    return jax.make_mesh((1, n_shards), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2,
                         devices=jax.local_devices()[:n_shards])
