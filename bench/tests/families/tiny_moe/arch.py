"""Arch reader of a decoder family with routed experts, as a new
configuration brings it: every layer's MLP is ``num_experts`` gated-silu
experts, ``num_experts_per_tok`` of them picked per token by a softmax
router whose picked weights are renormalized to sum to one
(``norm_topk_prob``).  Attention is GQA with rotary embedding over the
whole head, RMSNorm throughout.

``bench/tests/test_harness.py`` copies this file into a copy of the
benchmark as a family that the benchmark does not have.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class MoeArch:
    d_model: int
    n_layers: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    n_experts: int
    experts_per_token: int
    expert_d_ff: int
    vocab: int
    tied: bool
    norm_eps: float
    qkv_bias: bool
    rope_theta: float
    split_layer: int

    @property
    def q_dim(self) -> int:
        return self.n_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.n_kv_heads * self.head_dim


def from_config(conf: dict) -> MoeArch:
    m = conf["model"]
    if not m["norm_topk_prob"]:
        raise ValueError("the family renormalizes the picked experts' "
                         "weights (norm_topk_prob true)")
    return MoeArch(
        d_model=int(m["hidden_size"]), n_layers=int(m["num_hidden_layers"]),
        n_heads=int(m["num_attention_heads"]),
        n_kv_heads=int(m["num_key_value_heads"]),
        head_dim=int(m["head_dim"]), n_experts=int(m["num_experts"]),
        experts_per_token=int(m["num_experts_per_tok"]),
        expert_d_ff=int(m["moe_intermediate_size"]),
        vocab=int(m["vocab_size"]), tied=bool(m["tie_word_embeddings"]),
        norm_eps=float(m["rms_norm_eps"]),
        qkv_bias=bool(conf["arch"]["qkv_bias"]),
        rope_theta=float(m["rope_theta"]),
        split_layer=int(conf["split_layer"]))


def program_fields(a: MoeArch) -> dict:
    """The program ``ModelConfig``'s fields that the file fixes: experts
    in every layer, full attention."""
    return {"d_model": a.d_model, "n_layers": a.n_layers,
            "n_heads": a.n_heads, "n_kv_heads": a.n_kv_heads,
            "head_dim": a.head_dim, "vocab_size": a.vocab,
            "tie_embeddings": a.tied, "norm": "rmsnorm",
            "qkv_bias": a.qkv_bias, "rope_theta": a.rope_theta,
            "split_layer": a.split_layer, "act": "silu",
            "n_experts": a.n_experts,
            "experts_per_token": a.experts_per_token,
            "moe_d_ff": a.expert_d_ff, "moe_every": 1, "sliding_window": 0}
