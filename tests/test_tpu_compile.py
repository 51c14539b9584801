"""Compile-only checks that the served Pallas kernels lower for TPU v5e.

The TPU compiler installed with JAX compiles for a described (not
attached) ``v5e:2x2`` topology from a CPU host, so these tests catch what
interpret-mode tests never see — a kernel Mosaic refuses — at real
widths and no chip time.  Nothing runs: each test compiles for one chip
of the topology and asserts the program holds the kernel
(``tpu_custom_call``, named ``paged_attention``, or ``mla_attention``
for the latent mode).  ``interpret=False``
is passed explicitly, since ``jax.default_backend()`` is still the CPU
here.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker
imports this file.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.configs.deepseek_v2_lite import CONFIG as DSV2_LITE, \
    expert_share
from repro.kernels.paged_attention.paged_attention import decode_attend, \
    mla_decode_attend
from repro.kvcache.backend import _paged_decode_kernel
from repro.models import lm


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")    # no compiler logs on disk
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:     # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """A compile for a described chip can be written to the persistent
    cache but not read back without the chip; keep it out."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("name,window", [("qwen1_5_0_5b", 0),
                                         ("hymba_1_5b", 1024)])
def test_paged_attention_compiles_for_v5e(one_chip, no_compile_cache,
                                          name, window):
    """Decode attention (kernel over the cached pages + in-flight merge)
    at the published head layout, over the folded pool the backend
    serves: qwen's 16/16 heads (folded width 1024, read as it is), and
    hymba's GQA 25/5 heads under its 1024-token sliding window.  Hymba's
    folded width, 5 x 64 = 320, is off the 128-lane tile: its default
    layout puts the block axis minor (``{1,3,2,0}``), and the wrapper
    pads the width to 384, so the compiled program relays the pool out —
    a copy that no benchmark cell runs."""
    cfg = configs.get(name)
    B, page, P, n_pages = 8, 16, 256, 8
    H, K, D = cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    cd, kvd = cfg.cdtype, cfg.kvdtype

    def step(q, k_new, v_new, kp, vp, pt, lengths, layer):
        return decode_attend(q, k_new, v_new, kp, vp, pt, lengths,
                             layer=layer, window=window, folded=True,
                             interpret=False)

    pool = _spec((cfg.n_layers, P, page, K * D), kvd, one_chip)
    lowered = jax.jit(step).lower(
        _spec((B, H, D), cd, one_chip), _spec((B, K, D), cd, one_chip),
        _spec((B, K, D), cd, one_chip), pool, pool,
        _spec((B, n_pages), jnp.int32, one_chip),
        _spec((B,), jnp.int32, one_chip),
        _spec((), jnp.int32, one_chip))
    # the name the device trace's kernel op carries
    assert 'kernel_name = "paged_attention"' in lowered.as_text()
    assert "tpu_custom_call" in lowered.compile().as_text()


def test_mla_attention_compiles_for_v5e(one_chip, no_compile_cache):
    """The latent mode at DeepSeek-V2-Lite's widths: 16 heads' absorbed
    queries (576 wide) over the latent mirror, whose rows the backend
    pads to 640 lanes, and the in-flight row's merge."""
    cfg = DSV2_LITE
    B, page, P, n_pages = 8, 16, 256, 8
    H, W = cfg.n_heads, cfg.latent_width

    def step(q, row, pages, pt, lengths, layer):
        return mla_decode_attend(q, row, pages, pt, lengths, layer=layer,
                                 scale=cfg.softmax_scale,
                                 dv=cfg.kv_lora_rank, interpret=False)

    lowered = jax.jit(step).lower(
        _spec((B, H, W), cfg.cdtype, one_chip),
        _spec((B, W), cfg.cdtype, one_chip),
        _spec((cfg.n_layers, P, page, 640), cfg.kvdtype, one_chip),
        _spec((B, n_pages), jnp.int32, one_chip),
        _spec((B,), jnp.int32, one_chip), _spec((), jnp.int32, one_chip))
    assert 'kernel_name = "mla_attention"' in lowered.as_text()
    assert "tpu_custom_call" in lowered.compile().as_text()


def _compiled_decode_step(one_chip, B, n_pages, P, cfg=None):
    """HLO of the whole jitted kernel-path decode step at full width
    (qwen1.5-0.5B's 24 layers and 151,936-word vocabulary unless ``cfg``
    says otherwise), parameters as shapes only and the pool as the
    backend's folded device mirror (for the latent kind its one buffer
    of rows padded to 640 lanes, and no V)."""
    cfg = cfg or configs.get("qwen1_5_0_5b")
    params = jax.tree.map(
        lambda a: _spec(a.shape, a.dtype, one_chip),
        jax.eval_shape(lambda: lm.init(cfg, jax.random.key(0)).params))
    width = 640 if cfg.is_mla else cfg.n_kv_heads * cfg.d_head
    pool = _spec((cfg.n_layers, P, 16, width), cfg.kvdtype, one_chip)
    return _paged_decode_kernel.lower(
        params, cfg, _spec((B, 1), jnp.int32, one_chip), pool,
        None if cfg.is_mla else pool,
        _spec((B, n_pages), jnp.int32, one_chip),
        _spec((B,), jnp.int32, one_chip), None, None,
        interpret=False).compile().as_text()


def test_decode_step_compiles_for_v5e(one_chip, no_compile_cache):
    """The whole jitted kernel-path decode step at full width."""
    assert "tpu_custom_call" in _compiled_decode_step(one_chip, 8, 4, 256)


def test_decode_step_reads_the_pool_without_a_copy(one_chip,
                                                    no_compile_cache):
    """At the chat cell's shape (32 lanes, 128 pages, a pool of 1800
    blocks) the compiled decode step hands the folded mirror to the
    kernel as it is: no ``copy`` of a pool-shaped array.  The unfolded
    (L, P, page, Hkv, dh) pool got a block-minor default layout and was
    relaid out whole, K and V, at every step."""
    hlo = _compiled_decode_step(one_chip, 32, 128, 1800)
    assert "tpu_custom_call" in hlo
    pool = "bf16[24,1800,16,1024]"
    assert pool in hlo                       # the mirror is an operand
    copies = [ln for ln in hlo.splitlines()
              if " copy(" in ln and pool in ln.split("copy(")[0]]
    assert not copies, copies[0][:200]


def test_latent_decode_step_reads_the_pool_without_a_copy(one_chip,
                                                           no_compile_cache):
    """DeepSeek-V2-Lite's decode step as the cell serves it (one chip's
    share of 8 experts, 32 lanes, 512 pages, a pool of 5000 blocks): the
    latent mirror goes to the ``mla_attention`` kernel as it is, with no
    ``copy`` of a pool-shaped array; and the grouped matmul reads the
    flat expert-major expert matrices as stored, with no copy or
    transpose of one (a (d, E, e) layout is relaid out at every step)."""
    hlo = _compiled_decode_step(one_chip, 32, 512, 5000,
                                expert_share(DSV2_LITE, 8, 0))
    assert "tpu_custom_call" in hlo
    pool = "bf16[27,5000,16,640]"
    assert pool in hlo
    copies = [ln for ln in hlo.splitlines()
              if " copy(" in ln and pool in ln.split("copy(")[0]]
    assert not copies, copies[0][:200]
    moved = []
    for ln in hlo.splitlines():
        m = re.search(r"= bf16\[([\d,]+)\]\S* (copy|transpose)\(", ln)
        if m and "1408" in m.group(1).split(",") and np.prod(
                [int(n) for n in m.group(1).split(",")]) >= 2048 * 1408:
            moved.append(ln)
    assert not moved, moved[0][:200]

