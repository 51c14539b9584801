"""Operations and bytes from shapes: what a decode token needs, what the
``paged_attention`` kernel must read and compute, and what crosses
between host and device.  These are the benchmark's own counts; the
program is not asked for them.

``m`` is a ``refmodel.Model``.  A lane with ``ctx`` cached tokens
decodes its in-flight token against ``ctx + 1`` positions: the ``ctx``
cached ones, which the kernel reads from the pool's pages, and itself.
"""
from __future__ import annotations

BF16 = 2
F32 = 4
I32 = 4


def _pow2(n: int) -> int:
    return 1 << max(n - 1, 0).bit_length()


def layer_window(m, layer: int) -> int:
    """Sliding window of ``layer`` (0 = global)."""
    if not m.sliding_window:
        return 0
    if m.global_every and layer % m.global_every == 0:
        return 0
    return m.sliding_window


def ssm_dims(m):
    d_in = m.ssm_expand * m.d_model
    return d_in, d_in // m.d_ssm_head, m.d_ssm_head, m.ssm_state


def decode_token_flops(m, ctx: int) -> float:
    """Model FLOPs of one decode token with ``ctx`` cached tokens: the
    matrix products of every layer, attention over the positions it
    sees, the SSM where there is one, and the LM head."""
    d, H, K, dh, f = m.d_model, m.n_heads, m.n_kv_heads, m.d_head, m.d_ff
    per_layer = 2 * d * (H + 2 * K) * dh + 2 * H * dh * d + 2 * 3 * d * f
    if m.family == "hybrid":
        d_in, Hs, P, N = ssm_dims(m)
        per_layer += 2 * d * 2 * d_in + 2 * d * (2 * N + Hs) \
            + 2 * d_in * d + 2 * m.ssm_conv * (d_in + 2 * N) + 6 * Hs * P * N
    total = m.n_layers * per_layer + 2 * d * m.vocab
    for layer in range(m.n_layers):
        w = layer_window(m, layer)
        seen = ctx + 1 if not w else min(ctx + 1, w)
        total += 4 * H * dh * seen
    return float(total)


def kernel_pages(ln: int, window: int, block_size: int) -> tuple:
    """(pages read, cached positions attended) of one lane of the paged
    kernel at ``ln`` cached tokens: the pages holding positions
    ``[lo, ln)``, ``lo = ln - window + 1`` under a window, else 0."""
    if ln <= 0:
        return 0, 0
    lo = max(ln - window + 1, 0) if window else 0
    if lo >= ln:
        return 0, 0
    return (ln - 1) // block_size - lo // block_size + 1, ln - lo


def attention_kernel_cost(m, contexts, block_size: int,
                          kv_itemsize: int = BF16) -> tuple:
    """(FLOPs, bytes) the ``paged_attention`` kernel needs over all
    layers of one decode step whose lanes hold ``contexts`` cached
    tokens: QK and PV over the attended cached positions, and the K and
    V pages that hold them (after the window's page gate), plus the
    query read and the output written."""
    H, K, dh = m.n_heads, m.n_kv_heads, m.d_head
    page_bytes = 2 * block_size * K * dh * kv_itemsize
    flops = nbytes = 0
    for layer in range(m.n_layers):
        w = layer_window(m, layer)
        for ln in contexts:
            pages, seen = kernel_pages(ln, w, block_size)
            flops += 4 * H * dh * seen
            nbytes += pages * page_bytes + 2 * H * dh * BF16 + 2 * H * F32
    return float(flops), float(nbytes)


def decode_hostdev_bytes(m, contexts, staged_blocks: int,
                         block_size: int) -> float:
    """Bytes between host and device for one decode step of
    ``len(contexts)`` lanes, padded as the program pads them (lanes and
    pages to powers of two): the dirty pool blocks staged into the
    device mirror, the operands, the new token's K and V and the logits
    coming back, and a hybrid's SSM and conv state both ways."""
    L, K, dh = m.n_layers, m.n_kv_heads, m.d_head
    B = len(contexts)
    Bp = _pow2(B)
    n_pages = _pow2(max(-(-(c + 1) // block_size) for c in contexts))
    total = staged_blocks * 2 * L * block_size * K * dh * BF16
    total += Bp * I32 * 2 + Bp * n_pages * I32
    total += 2 * L * Bp * K * dh * BF16 + Bp * m.vocab * BF16
    if m.family == "hybrid":
        d_in, Hs, P, N = ssm_dims(m)
        total += 2 * L * Bp * Hs * P * N * F32
        total += 2 * L * Bp * (m.ssm_conv - 1) * (d_in + 2 * N) * BF16
    return float(total)


def prefill_hostdev_bytes(m, prompt_len: int) -> float:
    """Bytes between host and device for one prompt's prefill: the
    tokens up, the prompt's K and V for every layer, the last logits
    and a hybrid's final SSM and conv state down."""
    L, K, dh = m.n_layers, m.n_kv_heads, m.d_head
    total = prompt_len * I32 + 2 * L * prompt_len * K * dh * BF16 \
        + m.vocab * BF16
    if m.family == "hybrid":
        d_in, Hs, P, N = ssm_dims(m)
        total += L * Hs * P * N * F32 \
            + L * (m.ssm_conv - 1) * (d_in + 2 * N) * BF16
    return float(total)
