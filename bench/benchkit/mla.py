"""Operations and bytes of the ``mla_attention`` kernel, the latent mode
of the paged page walk (DeepSeek-V2's absorbed MLA decode), and its
share of the chip's roofline in a traced run.

``model`` is the ``model`` entry of a configuration file that has latent
attention (``kv_lora_rank`` > 0).  Each head's absorbed query, ``r +
rope`` wide, scores every cached row ``[c, k_pe]`` of its lane, and the
value is the row's first ``r`` lanes (the latent ``c``): one row per
token and layer, shared by every head, so a page is read once for both.
The mirror pads a row to whole 128-lane tiles (576 to 640); the
kernel's counts hold only the ``r + rope`` values the work needs, while
the bytes between host and device count the padded rows the program
stages, since those cross.

Also the model FLOPs of one decode token of such a model (absorbed MLA,
a dense first ``n_dense_layers``, then expert layers holding
``n_experts`` of the router's ``n_routed_experts``) and the bytes one
decode step and one prefill move between host and device.
"""
from __future__ import annotations

from benchkit import flops, readers, trace

# the Pallas kernel's name in the device trace (``mla_attention.<n>``)
KERNEL = r"mla_attention"


def kernel_cost(model: dict, contexts, block_size: int) -> tuple:
    """(FLOPs, bytes) the kernel needs over every layer of one decode
    step whose lanes hold ``contexts`` cached tokens.  Per lane and
    layer, with ``seen`` cached positions on ``pages`` pages: scores
    ``2 H (r + rope) seen`` and values ``2 H r seen`` FLOPs; the pages'
    rows, the bf16 query read, and the float32 output and softmax state
    written."""
    H, r = model["n_heads"], model["kv_lora_rank"]
    width = r + model["qk_rope_head_dim"]
    page_bytes = block_size * width * flops.BF16
    fl = by = 0
    for ln in contexts:
        pages, seen = flops.kernel_pages(ln, 0, block_size)
        fl += 2 * H * width * seen + 2 * H * r * seen
        by += pages * page_bytes + H * width * flops.BF16 \
            + H * r * flops.F32 + 2 * H * flops.F32
    L = model["n_layers"]
    return float(L * fl), float(L * by)


def mirror_width(model: dict) -> int:
    """A latent row as the device mirror holds it: ``r + rope`` padded to
    whole 128-lane tiles."""
    w = model["kv_lora_rank"] + model["qk_rope_head_dim"]
    return -(-w // 128) * 128


def decode_token_flops(model: dict, ctx: int) -> float:
    """Model FLOPs of one decode token with ``ctx`` cached tokens, in the
    absorbed form the cached decode computes.  Per layer: the query
    projection, the latent row ``[c, k_pe]``, the query absorbed through
    ``W_uk``, scores and values over the ``ctx + 1`` positions it sees,
    ``W_uv`` and the output projection; then the dense MLP, or the
    router, the shared experts and the held experts at the assignments
    a token sends them on average (``top_k`` of the router's width, of
    which ``n_experts`` are held); and the LM head."""
    d, H = model["d_model"], model["n_heads"]
    r, rope = model["kv_lora_rank"], model["qk_rope_head_dim"]
    nope, dv = model["qk_nope_head_dim"], model["v_head_dim"]
    seen = ctx + 1
    attn = 2 * d * H * (nope + rope) + 2 * d * (r + rope) \
        + 2 * H * nope * r + 2 * H * (r + rope) * seen + 2 * H * r * seen \
        + 2 * H * r * dv + 2 * H * dv * d
    e = model["d_expert"]
    width = model["n_routed_experts"]
    routed = model["top_k"] * model["n_experts"] / width
    moe = 2 * d * width + 2 * 3 * d * e * model["n_shared_experts"] \
        + routed * 2 * 3 * d * e
    dense = 2 * 3 * d * model["d_ff"]
    L, Ld = model["n_layers"], model["n_dense_layers"]
    return float(L * attn + Ld * dense + (L - Ld) * moe
                 + 2 * d * model["vocab"])


def decode_hostdev_bytes(model: dict, contexts, staged_blocks: int,
                         block_size: int) -> float:
    """Bytes between host and device for one decode step of
    ``len(contexts)`` lanes, padded as the program pads them (lanes and
    pages to powers of two): the dirty pool blocks staged into the
    device mirror (rows at the mirror's padded width), the operands, the
    new latent row of every layer and the logits coming back, and the
    count of assignments routed here."""
    L = model["n_layers"]
    row = model["kv_lora_rank"] + model["qk_rope_head_dim"]
    B = len(contexts)
    Bp = flops._pow2(B)
    n_pages = flops._pow2(max(-(-(c + 1) // block_size) for c in contexts))
    total = staged_blocks * L * block_size * mirror_width(model) * flops.BF16
    total += Bp * flops.I32 * 2 + Bp * n_pages * flops.I32
    total += L * Bp * row * flops.BF16 + Bp * model["vocab"] * flops.BF16
    return float(total + flops.F32)


def prefill_hostdev_bytes(model: dict, prompt_len: int) -> float:
    """Bytes between host and device for one prompt's prefill: the tokens
    up, the prompt's latent rows of every layer and the last logits
    down."""
    row = model["kv_lora_rank"] + model["qk_rope_head_dim"]
    return float(prompt_len * flops.I32
                 + model["n_layers"] * prompt_len * row * flops.BF16
                 + model["vocab"] * flops.BF16)


def hostdev_mb_per_step(run, model: dict):
    """Megabytes between host and device per decode step in the window:
    every decode step's bytes plus the window's prefills', over the
    window's decode steps (``readers.hostdev_mb_per_step`` for the
    latent pool)."""
    dec = [d for d in run.decodes if run.in_window(d[0]) and d[1]]
    if not dec or any(d[2] is None for d in dec):
        return None
    bs = run.serve["block_size"]
    total = sum(decode_hostdev_bytes(model, ctx, staged, bs)
                for _, ctx, staged in dec)
    total += sum(prefill_hostdev_bytes(model, n)
                 for t0, _, n, _ in run.prefills if run.in_window(t0))
    return total / len(dec) / 1e6


def decode_mfu(run, model: dict):
    """Model FLOPs of the decode tokens dispatched in the traced window,
    over the window's length and the chip's bf16 peak, in %."""
    dec = readers._traced_decodes(run)
    if not dec or run.peaks is None:
        return None
    work = sum(decode_token_flops(model, c) for _, ctx, _ in dec
               for c in ctx)
    secs = run.traced[1] - run.traced[0]
    return 100.0 * work / secs / run.peaks["bf16_flops_per_s"]


def kernel_roofline(run, model: dict):
    """The least time the chip could take for the kernel's work in the
    traced window (the larger of FLOPs over the bf16 peak and bytes over
    HBM bandwidth), over the kernel's summed device time, in %."""
    dec = readers._traced_decodes(run)
    if not dec or run.peaks is None or run.trace is None:
        return None
    kernel_s = trace.op_seconds(run.trace, KERNEL)
    if not kernel_s:
        return None
    fl = by = 0.0
    for _, ctx, _ in dec:
        f, b = kernel_cost(model, ctx, run.serve["block_size"])
        fl, by = fl + f, by + b
    bound = max(fl / run.peaks["bf16_flops_per_s"],
                by / run.peaks["hbm_bytes_per_s"])
    return 100.0 * bound / kernel_s
