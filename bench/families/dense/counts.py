"""Operations and bytes that the algorithm needs, from shapes and live
lengths.

These count the work a call must do, not what the compiled program
happens to do: padding, dead batch rows and cache tiles past a row's live
length are never counted, so a program that stops doing them reads
closer to its roofline and never above it.  A multiply-add is two
operations.  Bias adds, norms, rotary embedding and softmax are left out
(each is under 1% of a step at these widths).
"""

from __future__ import annotations

from .arch import Arch


def layer_matmul_params(a: Arch) -> int:
    """Weights that one layer multiplies each token by: q, k, v, o and
    the gated MLP's three matrices."""
    return a.d_model * (a.q_dim + 2 * a.kv_dim) + a.q_dim * a.d_model \
        + 3 * a.d_model * a.d_ff


def unembed_flops(a: Arch) -> int:
    """One position's logits."""
    return 2 * a.d_model * a.vocab


def attn_flops(a: Arch, pairs: int) -> int:
    """Scores and weighted values over ``pairs`` (query, key) pairs in
    every layer: 2·dh for q·k and 2·dh for p·v, per head."""
    return 4 * a.head_dim * a.n_heads * a.n_layers * int(pairs)


def prefill_flops(a: Arch, prompt_len: int) -> int:
    """A prompt of ``prompt_len`` true tokens: every layer's matmuls for
    each token, causal attention over P(P+1)/2 pairs, and the logits of
    the last position only (all that a prefill returns)."""
    p = int(prompt_len)
    return 2 * layer_matmul_params(a) * a.n_layers * p \
        + attn_flops(a, p * (p + 1) // 2) + unembed_flops(a)


def decode_token_flops(a: Arch, ctx_len: int) -> int:
    """One decoded token whose attention reads ``ctx_len`` cache
    positions (its own included): the layers' matmuls, attention and
    the logits."""
    return 2 * layer_matmul_params(a) * a.n_layers \
        + attn_flops(a, ctx_len) + unembed_flops(a)


def decode_attn_work(a: Arch, ctx_len: int, code_bytes: int = 1,
                     act_bytes: int = 4):
    """(operations, bytes) of the decode-attention kernel for one token
    of one row, over all layers, at live length ``ctx_len``.

    Bytes: the k and v codes (``code_bytes`` each) and their one f32
    scale per head vector at the live positions, the query read and the
    output written (``act_bytes`` each).  Operations: 4·len·dh per head.
    """
    n = int(ctx_len)
    per_layer_bytes = 2 * n * a.n_kv_heads * (a.head_dim * code_bytes + 4) \
        + 2 * a.q_dim * act_bytes
    return attn_flops(a, n), per_layer_bytes * a.n_layers


def least_time(flops: float, nbytes: float, peaks: dict):
    """(seconds, bound) at the chip's peaks: the larger of operations over
    the bf16 peak and bytes over HBM bandwidth, and which one it was."""
    t_c = flops / peaks["flops_bf16"]
    t_m = nbytes / peaks["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
