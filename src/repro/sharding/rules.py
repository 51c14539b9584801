"""Logical-axis -> mesh-axis rules.

Model code annotates every parameter with logical axes (see
``models/layers.py``); these rules translate them into ``PartitionSpec``s
for a concrete mesh.  The production mesh axes are ("pod",) "data", "model":

  TP  : heads / kv_heads / mlp / vocab / ssm_in  -> "model"
  EP  : expert                                   -> "model"
  FSDP: embed (weight rows)                      -> "data"  (ZeRO-3 style)
  EP+FSDP: expert_embed (a flat expert matrix's
        expert-major rows, E·d)                  -> ("model", "data")
  DP  : batch                                    -> ("pod", "data")

Explicit input shardings must divide dimensions exactly, so every mapping
is divisibility-checked with fallbacks: a head count that doesn't divide
the model axis (56 heads on 16-way TP) moves the sharding to the head_dim
("head") instead; dimensions with no valid mapping replicate.  All
fallbacks are honest — the roofline table shows their cost.
"""
from __future__ import annotations

from typing import Any, Optional

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

# when the primary mapping doesn't divide, move the mesh axis to the dim
# with this logical name instead (if present and divisible)
_FALLBACK_DIM = {
    "heads": "head",
    "kv_heads": "head",
    "vocab": "embed",
    "ssm_heads": None,
}


def logical_rules(mesh: jax.sharding.Mesh, fsdp: bool = True) -> dict:
    has_pod = "pod" in mesh.axis_names
    data_axes = ("pod", "data") if has_pod else ("data",)
    return {
        "vocab": "model",
        "heads": "model",
        "kv_heads": "model",
        "mlp": "model",
        "expert": "model",
        "expert_embed": ("model",) + data_axes if fsdp else "model",
        "ssm_in": "model",
        "ssm_small": None,
        "ssm_heads": "model",
        "embed": data_axes if fsdp else None,
        "head": None,
        "conv": None,
        "seq": None,
        "layers": None,
        "batch": data_axes,
    }


def _axis_size(mesh, name) -> int:
    if isinstance(name, tuple):
        out = 1
        for n in name:
            out *= mesh.shape[n]
        return out
    return mesh.shape[name]


def spec_for(axes, shape, rules, mesh) -> P:
    """Divisibility-checked PartitionSpec for one parameter."""
    n = len(axes)
    out = [None] * n
    used = set()

    def mark(m):
        used.update(m if isinstance(m, tuple) else (m,))

    # first pass: primary mappings that divide
    pending = []
    for i, a in enumerate(axes):
        m = rules.get(a)
        if m is None:
            continue
        ms = tuple(x for x in (m if isinstance(m, tuple) else (m,))
                   if x not in used)
        if not ms:
            continue
        m2 = ms if len(ms) > 1 else ms[0]
        if shape[i] % _axis_size(mesh, m2) == 0:
            out[i] = m2
            mark(m2)
        else:
            pending.append((i, a, m2))
    # second pass: fallback dims for failed mappings
    for i, a, m in pending:
        fb = _FALLBACK_DIM.get(a)
        if fb is None:
            continue
        if isinstance(m, tuple) or m in used:
            continue
        for j, b in enumerate(axes):
            if b == fb and out[j] is None \
                    and shape[j] % _axis_size(mesh, m) == 0:
                out[j] = m
                mark(m)
                break
    return P(*out)


def param_shardings(specs_tree, params_abs, mesh, fsdp: bool = True):
    """Map the logical-spec tree + abstract params to NamedShardings."""
    rules = logical_rules(mesh, fsdp)

    def one(axes, aval):
        return NamedSharding(mesh, spec_for(axes, aval.shape, rules, mesh))
    return jax.tree.map(one, specs_tree, params_abs,
                        is_leaf=lambda x: isinstance(x, tuple))


def batch_sharding(mesh, batch: int | None = None) -> NamedSharding:
    has_pod = "pod" in mesh.axis_names
    cand = [("pod", "data"), ("data",), ("pod",)] if has_pod else [("data",)]
    if batch is not None:
        for axes in cand:
            if batch % _axis_size(mesh, axes) == 0:
                return NamedSharding(mesh, P(axes))
        return NamedSharding(mesh, P())
    return NamedSharding(mesh, P(cand[0]))


def sharded_bytes_per_device(tree, shardings, mesh) -> int:
    """Analytic per-device bytes of a (possibly abstract) array tree under
    the given shardings (ceil per sharded dim, matching GSPMD padding)."""
    total = 0
    for leaf, sh in zip(jax.tree.leaves(tree),
                        jax.tree.leaves(shardings,
                                        is_leaf=lambda x: x is None)):
        if leaf is None:
            continue
        n = 1
        spec = list(sh.spec) + [None] * (leaf.ndim - len(sh.spec)) \
            if sh is not None else [None] * leaf.ndim
        for dim, ax in zip(leaf.shape, spec):
            k = _axis_size(mesh, ax) if ax is not None else 1
            n *= -(-dim // k)
        total += n * leaf.dtype.itemsize
    return total


def replicated(mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def pool_shard_count(mesh) -> int:
    """How many shards a mesh gives the KV block pool: the size of the
    model axis (one per-device pool per model shard —
    ``kvcache.sharded_pool.ShardedBlockPool``); 1 without a mesh or when
    the mesh has no model axis."""
    if mesh is None or "model" not in mesh.axis_names:
        return 1
    return int(mesh.shape["model"])


def cache_shardings(mesh, cfg, batch: int, backend: str = "dense") -> Any:
    """KV cache (L,B,S,K,dh): batch on data axes; kv heads on model when
    divisible, otherwise the *sequence* dim shards on model (flash-decoding
    style partial attention, resolved by GSPMD collectives).  SSM states
    shard heads on model when divisible.

    Only the dense ``lm.Cache`` layout is covered (``backend="dense"``).
    A paged backend's KV lives in a host-side ``BlockPool`` with layout
    ``(L, num_blocks, page, K, dh)`` — handing these specs to it would
    silently shard the *page* axis as if it were the sequence axis, so
    any other ``backend`` raises: paged caches shard across the mesh via
    ``kvcache.sharded_pool.ShardedBlockPool`` (per-shard pools driving
    per-shard kernel calls), not via GSPMD cache specs.
    """
    if backend != "dense":
        raise NotImplementedError(
            f"cache_shardings covers the dense lm.Cache layout only; "
            f"backend {backend!r} caches do not shard via GSPMD specs — "
            f"use kvcache.sharded_pool.ShardedBlockPool (mesh-partitioned "
            f"block pools) for paged serving")
    has_pod = "pod" in mesh.axis_names
    d = ("pod", "data") if has_pod else ("data",)
    nm = mesh.shape["model"]
    nd = _axis_size(mesh, d)
    bspec = d if batch % nd == 0 else None
    kv_on_heads = cfg.n_kv_heads % nm == 0
    if kv_on_heads:
        kv = P(None, bspec, None, "model", None)
    else:
        kv = P(None, bspec, "model", None, None)
    from repro.models import ssm as ssm_mod
    if cfg.has_ssm:
        _, H, _, _ = ssm_mod.ssm_dims(cfg)
        ssm = P(None, bspec, "model" if H % nm == 0 else None, None, None)
        # conv state is tiny; its (x|bc) channel split is shard-misaligned,
        # so replicate the channel dim rather than permute on every decode
        conv = P(None, bspec, None, None)
    else:
        ssm = conv = P()
    enc_kv = P(None, bspec, None, "model" if cfg.n_kv_heads % nm == 0
               else None, None)

    def ns(p):
        return NamedSharding(mesh, p)
    from repro.models.lm import Cache
    return Cache(
        k=ns(kv) if cfg.has_attention else None,
        v=ns(kv) if cfg.has_attention else None,
        ssm=ns(ssm) if cfg.has_ssm else None,
        conv=ns(conv) if cfg.has_ssm else None,
        xk=ns(enc_kv) if cfg.family == "encdec" else None,
        xv=ns(enc_kv) if cfg.family == "encdec" else None,
        length=ns(P()),
    )
