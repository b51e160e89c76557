"""Operations and bytes that a decoder with routed experts needs (see
this family's ``arch.py``): each token runs the router and only the experts
it picks, whatever the program computes besides.  A multiply-add is two
operations; norms, rotary embedding, softmax and the gating products are
left out."""

from __future__ import annotations


def layer_matmul_params(a) -> int:
    """Weights one layer multiplies each token by: q, k, v, o, the
    router and the picked experts' three matrices."""
    return a.d_model * (a.q_dim + 2 * a.kv_dim) + a.q_dim * a.d_model \
        + a.d_model * a.n_experts \
        + a.experts_per_token * 3 * a.d_model * a.expert_d_ff


def attn_flops(a, pairs: int) -> int:
    return 4 * a.head_dim * a.n_heads * a.n_layers * int(pairs)


def prefill_flops(a, prompt_len: int) -> int:
    p = int(prompt_len)
    return 2 * layer_matmul_params(a) * a.n_layers * p \
        + attn_flops(a, p * (p + 1) // 2) + 2 * a.d_model * a.vocab


def decode_token_flops(a, ctx_len: int) -> int:
    return 2 * layer_matmul_params(a) * a.n_layers \
        + attn_flops(a, ctx_len) + 2 * a.d_model * a.vocab


def decode_attn_work(a, ctx_len: int, code_bytes: int = 1,
                     act_bytes: int = 4):
    """(operations, bytes) of decode attention for one token of one row
    over all layers: k and v codes with one f32 scale per head vector at
    the live positions, q read and the output written."""
    n = int(ctx_len)
    per_layer_bytes = 2 * n * a.n_kv_heads * (a.head_dim * code_bytes + 4) \
        + 2 * a.q_dim * act_bytes
    return attn_flops(a, n), per_layer_bytes * a.n_layers


def least_time(flops: float, nbytes: float, peaks: dict):
    t_c = flops / peaks["flops_bf16"]
    t_m = nbytes / peaks["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
