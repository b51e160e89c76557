"""A dense decoder's configuration file, as the yardstick reads it: the
arch module of the dense GQA family (``"family": "dense"`` in a
configuration file).

The benchmark's own view of a decoder: the published numbers of the
configuration file (``model``, under the source's key names) plus the
structural facts the plain reference needs (``arch``), among them the
program's departures from the published model that the reference
follows (``rotary_fraction``, where it differs from the published
``partial_rotary_factor``).  Nothing here comes from the program under
test.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Arch:
    d_model: int
    n_layers: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    tied: bool
    norm: str            # "rmsnorm" | "layernorm"
    norm_eps: float
    qkv_bias: bool
    rope_theta: float
    rotary_fraction: float
    split_layer: int     # agent layers [0, split) run at the agent's bits

    @property
    def q_dim(self) -> int:
        return self.n_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.n_kv_heads * self.head_dim


def from_config(conf: dict) -> Arch:
    """The dense decoder that the configuration file ``conf`` states."""
    m, a = conf["model"], conf["arch"]
    heads = int(m["num_attention_heads"])
    d = int(m["hidden_size"])
    if a["norm"] == "rmsnorm":
        eps = float(m["rms_norm_eps"])
    else:
        eps = float(m["layer_norm_eps"])
    return Arch(
        d_model=d, n_layers=int(m["num_hidden_layers"]), n_heads=heads,
        n_kv_heads=int(m["num_key_value_heads"]),
        head_dim=int(m.get("head_dim", d // heads)),
        d_ff=int(m["intermediate_size"]), vocab=int(m["vocab_size"]),
        tied=bool(m["tie_word_embeddings"]), norm=a["norm"],
        norm_eps=eps, qkv_bias=bool(a["qkv_bias"]),
        rope_theta=float(m["rope_theta"]),
        rotary_fraction=float(a.get("rotary_fraction",
                                    m.get("partial_rotary_factor", 1.0))),
        split_layer=int(conf["split_layer"]))


def program_fields(a: Arch) -> dict:
    """The program's ``ModelConfig`` fields that the file fixes, with
    their values: a dense gated-silu decoder with full attention and no
    experts."""
    return {"d_model": a.d_model, "n_layers": a.n_layers,
            "n_heads": a.n_heads, "n_kv_heads": a.n_kv_heads,
            "head_dim": a.head_dim, "d_ff": a.d_ff, "vocab_size": a.vocab,
            "tie_embeddings": a.tied, "norm": a.norm,
            "qkv_bias": a.qkv_bias, "rope_theta": a.rope_theta,
            "split_layer": a.split_layer, "act": "silu", "n_experts": 0,
            "sliding_window": 0}
