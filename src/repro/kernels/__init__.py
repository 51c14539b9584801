"""Pallas TPU kernels, each with a pure-jnp oracle (``ref.py``) and a
pool/model bridge (``ops.py``) where it has one."""
from __future__ import annotations

import functools

import jax


@functools.cache
def pallas_interpret() -> bool:
    """Whether Pallas kernels run in interpret mode on this platform.

    True on the CPU, where the interpreter is the only way to run a TPU
    kernel (tests, CI); False on a TPU, where the kernel is compiled by
    Mosaic.  Any other platform raises: these kernels target the TPU and
    have no fallback there.  The platform is fixed once JAX has picked
    its backend, so the answer is resolved once per process.
    """
    platform = jax.default_backend()
    if platform == "cpu":
        return True
    if platform == "tpu":
        return False
    raise RuntimeError(
        f"Pallas TPU kernels run compiled on a TPU or interpreted on the "
        f"CPU; JAX's default backend is {platform!r}")
