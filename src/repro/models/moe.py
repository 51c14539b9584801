"""Mixture-of-Experts layer with MARS-sorted dispatch.

This is the paper's technique mapped onto TPU: token->expert assignments
arrive interleaved (tokens in sequence order = the merged GPU streams); the
locality-oblivious baseline streams every token through capacity buffers for
every expert (GShard one-hot einsum — the "no MARS" path).  The MARS path
buffers a step's token window, *sorts assignments by destination expert*
("page"), moves them with a single all-to-all, and runs a contiguous
grouped matmul per expert — sequential HBM reads of each expert's weights,
full MXU tiles, then inverse-permute.  ``core/reorder.py`` provides the
sort; ``kernels/moe_dispatch`` provides the TPU Pallas grouped matmul (the
jnp path below uses ``lax.ragged_dot`` so everything compiles on any
backend).

Expert weights are sharded on the ``model`` mesh axis (expert parallelism);
tokens are sharded on ``data`` (and ``pod``).  The dispatch all-to-all runs
inside ``shard_map`` along ``model`` only, so it never crosses pods for
token movement — only gradient all-reduce does.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.reorder import mars_sort_by_page, inverse_permutation
from repro.models import layers
from repro.models.config import ModelConfig
from repro.sharding import context as shctx


def moe_init(key, cfg: ModelConfig) -> layers.ParamBundle:
    """Each expert matrix is stored flat and expert-major: ``w_in`` and
    ``w_gate`` (E·d, e), ``w_out`` (E·e, d), one expert's rows after
    another, so the grouped matmul reads them as (E, d, e) / (E, e, d)
    by a reshape (``expert_stacks``), with no copy on a TPU.  Each is
    drawn at its contraction width's scale."""
    d = cfg.d_model
    e = cfg.d_expert or cfg.d_ff
    E = cfg.n_experts
    ks = jax.random.split(key, 5)
    items = [
        ("router", layers._dense_init(ks[0], (d, cfg.router_width),
                                      ("embed", "expert"), jnp.float32)),
        ("w_in", layers._dense_init(ks[1], (E * d, e),
                                    ("expert_embed", "mlp"), cfg.pdtype,
                                    1.0 / np.sqrt(d))),
        ("w_gate", layers._dense_init(ks[2], (E * d, e),
                                      ("expert_embed", "mlp"), cfg.pdtype,
                                      1.0 / np.sqrt(d))),
        ("w_out", layers._dense_init(ks[3], (E * e, d), ("expert", "embed"),
                                     cfg.pdtype, 1.0 / np.sqrt(e))),
    ]
    if cfg.n_shared_experts:
        shared = layers.mlp_init(ks[4], cfg,
                                 d_ff=e * cfg.n_shared_experts)
        items.append(("shared", shared))
    return layers._merge(*items)


def expert_stacks(p) -> tuple:
    """``(w_in, w_gate, w_out)`` of one layer as (E, d, e), (E, d, e) and
    (E, e, d), as the grouped matmul takes them: reshapes of the flat
    stored matrices, no copy.  E is what ``p`` holds (a shard's share
    inside ``shard_map``)."""
    w_in, w_gate, w_out = p["w_in"], p["w_gate"], p["w_out"]
    e, d = w_in.shape[1], w_out.shape[1]
    E = w_in.shape[0] // d
    return (w_in.reshape(E, d, e), w_gate.reshape(E, d, e),
            w_out.reshape(E, e, d))


def router_topk(p, x, cfg: ModelConfig):
    """Returns (expert_idx (T,k), gates (T,k), aux losses) for flat tokens.

    The router scores all ``cfg.router_width`` experts in float32 (at
    HIGHEST, so a TPU does not round its operands to bfloat16), takes
    the softmax over all of them and the top ``top_k``; the gates are
    the softmax values, renormalised to sum 1 when ``router_renorm``,
    times ``router_scale``.  ``expert_idx`` indexes the router's width,
    not the experts held here (see ``held_ids``)."""
    logits = jnp.einsum("td,de->te", x.astype(jnp.float32), p["router"],
                        precision=jax.lax.Precision.HIGHEST)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, cfg.top_k)
    if cfg.router_renorm:
        gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    if cfg.router_scale != 1.0:
        gates = gates * cfg.router_scale
    # load-balance aux (Switch-style) + router z-loss
    T = x.shape[0]
    me = probs.mean(0)
    ce = jnp.zeros(cfg.router_width).at[idx.reshape(-1)].add(1.0) / (
        T * cfg.top_k)
    aux_lb = cfg.router_width * jnp.sum(me * ce)
    aux_z = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)
    return idx, gates.astype(x.dtype), {"moe_lb": aux_lb, "moe_z": aux_z}


def held_ids(idx, cfg: ModelConfig):
    """Router expert ids -> ids among the experts held here (0 ..
    n_experts - 1), and ``n_experts`` for an expert held elsewhere."""
    local = idx - cfg.first_expert
    return jnp.where((local >= 0) & (local < cfg.n_experts), local,
                     cfg.n_experts)


# ---------------------------------------------------------------------------
# MARS-sorted grouped dispatch
# ---------------------------------------------------------------------------

def _grouped_ffn(tokens, local_ids, w_in, w_gate, w_out, n_local: int,
                 act: str):
    """Sorted grouped matmul over contiguous per-expert segments.

    tokens: (M, d) already MARS-sorted by ``local_ids`` (invalid rows zeroed
    and assigned to the last group).  Uses lax.ragged_dot (the Pallas
    moe_dispatch kernel implements the same contract on TPU).
    """
    group_sizes = jnp.bincount(local_ids, length=n_local)
    with jax.named_scope("moe_experts"):
        h = jax.lax.ragged_dot(tokens, w_in, group_sizes)
        g = jax.lax.ragged_dot(tokens, w_gate, group_sizes)
        h = layers._act(g, act) * h
        return jax.lax.ragged_dot(h, w_out, group_sizes)


def _mars_dispatch_local(p, xf, cfg: ModelConfig):
    """Single-shard MARS dispatch: sort assignments by expert, grouped
    matmul, unsort.  (T, d) -> (T, d).

    Assignments to experts held elsewhere (an expert-parallel share,
    ``cfg.first_expert``) sort last, outside every group: the grouped
    matmul multiplies only the rows of the experts held here, and the
    others add nothing."""
    E, k = cfg.n_experts, cfg.top_k
    idx, gates, aux = router_topk(p, xf, cfg)
    T = xf.shape[0]
    flat_e = held_ids(idx, cfg).reshape(-1)       # (T*k,), E = elsewhere
    perm, inv, sorted_e, _ = mars_sort_by_page(flat_e, E + 1)
    tok_of = perm // k                            # source token per slot
    cd = cfg.cdtype
    gathered = xf[tok_of].astype(cd)              # (T*k, d) page-ordered
    w_in, w_gate, w_out = (w.astype(cd) for w in expert_stacks(p))
    out_sorted = _grouped_ffn(gathered, sorted_e, w_in, w_gate, w_out, E,
                              cfg.act)
    out_sorted = jnp.where((sorted_e < E)[:, None], out_sorted, 0)
    out_flat = out_sorted[inv]                    # back to assignment order
    w = gates.reshape(-1, 1).astype(cd)
    y = jnp.zeros_like(xf).at[jnp.arange(T * k) // k].add(out_flat * w)
    aux["held"] = flat_e < E
    return y, aux


def _mars_dispatch_sharded(p, xf, cfg: ModelConfig, mesh):
    """shard_map dispatch: tokens sharded on data axes (replicated over
    model), experts sharded on model.

    Every model column holds the full token window for its data row; it
    MARS-sorts assignments by expert, keeps the contiguous slice destined
    to *its* expert shard, runs the sorted grouped matmul, and the partial
    outputs are psum-combined over the model axis.  Token traffic is zero;
    the psum is the per-layer collective (see EXPERIMENTS §Perf for the
    all-to-all variant trade-off).
    """
    from jax.sharding import PartitionSpec as P
    E, k = cfg.n_experts, cfg.top_k
    n_model = mesh.shape["model"]
    E_loc = E // n_model
    daxes = shctx.data_axes(mesh)
    cd = cfg.cdtype

    # per-column capacity: the RequestQ-slot bound of the paper.  Each
    # column computes ONLY its contiguous MARS-sorted slice (expected
    # A/n_model rows, 2x headroom); overflow beyond capacity is dropped,
    # exactly the capacity-factor semantics of production MoE (§Perf C1:
    # without this every column multiplies all A rows -> 16x wasted flops).
    def body(pr, w_in, w_gate, w_out, x):
        w_in, w_gate, w_out = expert_stacks(
            {"w_in": w_in, "w_gate": w_gate, "w_out": w_out})
        T = x.shape[0]
        d = x.shape[1]
        col = jax.lax.axis_index("model")
        idx, gates, aux = router_topk({"router": pr}, x, cfg)
        A = T * k
        import os
        C = A if os.environ.get("REPRO_MOE_FULL") else \
            int(np.ceil(A / n_model * 2.0))
        flat_e = idx.reshape(-1)
        # ---- MARS reorder: group assignments by destination expert
        perm, inv, sorted_e, _ = mars_sort_by_page(flat_e, E)
        tok_of = perm // k
        gathered = x[tok_of].astype(cd)                    # (A, d)
        # ---- slice this column's contiguous block [lo, lo+C)
        lo = jnp.searchsorted(sorted_e, col * E_loc).astype(jnp.int32)
        xpad = jnp.concatenate([gathered, jnp.zeros((C, d), cd)])
        epad = jnp.concatenate([sorted_e,
                                jnp.full((C,), E, sorted_e.dtype)])
        xin = jax.lax.dynamic_slice(xpad, (lo, jnp.int32(0)), (C, d))
        e_c = jax.lax.dynamic_slice_in_dim(epad, lo, C)
        mine = (e_c // E_loc) == col
        eloc = jnp.where(mine, e_c % E_loc, E_loc)         # E_loc = dump grp
        xin = jnp.where(mine[:, None], xin, 0)
        # already sorted (contiguous slice of a sorted array)
        gsz = jnp.bincount(eloc, length=E_loc + 1)
        pad = jnp.zeros((1,) + w_in.shape[1:], w_in.dtype)
        h = jax.lax.ragged_dot(xin, jnp.concatenate([w_in, pad]), gsz)
        g = jax.lax.ragged_dot(xin, jnp.concatenate([w_gate, pad]), gsz)
        h = layers._act(g, cfg.act) * h
        padT = jnp.zeros((1,) + w_out.shape[1:], w_out.dtype)
        out_c = jax.lax.ragged_dot(h, jnp.concatenate([w_out, padT]), gsz)
        out_c = jnp.where(mine[:, None], out_c, 0)
        # ---- scatter the block back to assignment order
        outpad = jnp.zeros((A + C, d), cd)
        outpad = jax.lax.dynamic_update_slice(outpad, out_c,
                                              (lo, jnp.int32(0)))
        out = outpad[:A][inv]
        w = gates.reshape(-1, 1).astype(cd)
        y = jnp.zeros_like(x).at[jnp.arange(A) // k].add(out * w)
        y = jax.lax.psum(y, "model")
        return y, aux["moe_lb"][None], aux["moe_z"][None]

    spec_tok = P(daxes if daxes else None)
    aux_spec = P(daxes if daxes else None)
    y, lb, zz = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(), P("model"), P("model"), P("model"), spec_tok),
        out_specs=(spec_tok, aux_spec, aux_spec),
        check_vma=False,
    )(p["router"], p["w_in"], p["w_gate"], p["w_out"], xf)
    return y, {"moe_lb": lb.mean(), "moe_z": zz.mean()}


def moe_apply_einsum(p, xf, cfg: ModelConfig):
    """Locality-oblivious baseline (GShard one-hot capacity dispatch): every
    token window is streamed through per-expert capacity buffers — the
    "interleaved streams" path MARS removes."""
    E, k = cfg.n_experts, cfg.top_k
    T = xf.shape[0]
    idx, gates, aux = router_topk(p, xf, cfg)
    local = held_ids(idx, cfg)                  # E: held elsewhere
    aux["held"] = (local < E).reshape(-1)
    cap = max(1, int(np.ceil(T * k / E * 2.0)))
    # position of each assignment within its expert's capacity buffer
    # (an expert held elsewhere has none: its one-hot row is zero)
    onehot = jax.nn.one_hot(local, E, dtype=jnp.int32)     # (T,k,E)
    pos = jnp.cumsum(onehot.reshape(T * k, E), axis=0) - 1
    pos = pos.reshape(T, k, E)
    keep = (pos < cap) & (onehot > 0)
    disp = jax.nn.one_hot(pos, cap, dtype=xf.dtype) * keep[..., None]
    disp = (disp * gates[..., None, None]).sum(1)          # (T,E,cap) combine
    sel = jax.nn.one_hot(pos, cap, dtype=xf.dtype) * keep[..., None]
    sel = sel.sum(1)                                       # (T,E,cap) 0/1
    cd = cfg.cdtype
    w_in, w_gate, w_out = (w.astype(cd) for w in expert_stacks(p))
    ex_in = jnp.einsum("td,tec->ecd", xf.astype(cd), sel.astype(cd))
    h = jnp.einsum("ecd,edf->ecf", ex_in, w_in)
    g = jnp.einsum("ecd,edf->ecf", ex_in, w_gate)
    h = layers._act(g, cfg.act) * h
    out = jnp.einsum("ecf,efd->ecd", h, w_out)
    y = jnp.einsum("ecd,tec->td", out, disp.astype(cd))
    return y, aux


@dataclasses.dataclass(frozen=True)
class MoeRuntime:
    dispatch: str = "mars"         # mars | einsum


_RUNTIME = MoeRuntime()


def set_dispatch(mode: str):
    global _RUNTIME
    _RUNTIME = MoeRuntime(dispatch=mode)


def moe_apply(p, x, cfg: ModelConfig, valid=None):
    """x: (B, S, d) -> (B, S, d); adds shared-expert path if configured.

    aux also carries ``moe_routed_here``: the assignments of the tokens
    of the lanes ``valid`` (B,) marks (all when None) to experts held
    here (the sharded path, which holds all of them across the mesh,
    counts none)."""
    B, S, d = x.shape
    xf = x.reshape(B * S, d)
    mesh = shctx.current_mesh()
    if _RUNTIME.dispatch == "einsum":
        y, aux = moe_apply_einsum(p, xf, cfg)
    elif mesh is not None and mesh.shape.get("model", 1) > 1 \
            and cfg.n_experts % mesh.shape["model"] == 0 \
            and cfg.router_width == cfg.n_experts:
        y, aux = _mars_dispatch_sharded(p, xf, cfg, mesh)
    else:
        y, aux = _mars_dispatch_local(p, xf, cfg)
    held = aux.pop("held", jnp.zeros((B * S * cfg.top_k,), bool))
    if valid is not None:
        held = held & jnp.repeat(
            jnp.broadcast_to(valid[:, None], (B, S)).reshape(-1), cfg.top_k)
    aux["moe_routed_here"] = held.sum().astype(jnp.float32)
    y = y.reshape(B, S, d)
    if cfg.n_shared_experts:
        y = y + layers.mlp_apply(p["shared"], x, cfg)
    return y, aux
