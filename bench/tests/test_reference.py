"""The plain reference against the program's own forward pass, at smoke
sizes on the CPU, for both architectures the benchmark serves.

The reference draws its weights from the seed itself; they must equal
the program's ``DecoderLM.init`` draws, and its logits must match
``DecoderLM.forward`` within float32 rounding.  Tolerance: 2e-5 of the
largest logit — both passes are float32 at "highest" precision, so they
differ only in the order of additions (blockwise against plain
attention), a few units in the last place per op over a few layers.
"""

import ast
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import run  # noqa: E402

from repro.configs import qwen2_0_5b, stablelm_3b  # noqa: E402
from repro.models.lm import DecoderLM  # noqa: E402

DENSE = BENCH / "families" / "dense"
ref = run.load_family(DENSE).reference
Arch = run.load_family(DENSE).arch.Arch
TOL = 2e-5


def arch_of(cfg) -> Arch:
    return Arch(d_model=cfg.d_model, n_layers=cfg.n_layers,
                n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
                head_dim=cfg.head_dim, d_ff=cfg.d_ff, vocab=cfg.vocab_size,
                tied=cfg.tie_embeddings, norm=cfg.norm,
                norm_eps=1e-6 if cfg.norm == "rmsnorm" else 1e-5,
                qkv_bias=cfg.qkv_bias, rope_theta=cfg.rope_theta,
                rotary_fraction=1.0, split_layer=cfg.split_layer)


CONFIGS = {"qwen2": qwen2_0_5b.smoke(), "stablelm": stablelm_3b.smoke()}


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_reference_weights_are_the_programs(name):
    cfg = CONFIGS[name]
    key = jax.random.PRNGKey(2 ** 31 + 5)
    prog = DecoderLM(cfg).init(key)
    mine = ref.init_params(arch_of(cfg), key)
    pairs = {"tok": prog["embed"]["tok"],
             "wq": prog["layers"]["attn"]["wq"],
             "wk": prog["layers"]["attn"]["wk"],
             "wv": prog["layers"]["attn"]["wv"],
             "wo": prog["layers"]["attn"]["wo"],
             "w_gate": prog["layers"]["ffn"]["wi_gate"],
             "w_up": prog["layers"]["ffn"]["wi_up"],
             "w_down": prog["layers"]["ffn"]["wo"]}
    if not cfg.tie_embeddings:
        pairs["unembed"] = prog["embed"]["unembed"]
    for k, v in pairs.items():
        np.testing.assert_allclose(np.asarray(mine[k]), np.asarray(v),
                                   rtol=1e-6, atol=1e-7, err_msg=k)


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_reference_logits_match_forward(name):
    cfg = CONFIGS[name]
    a = arch_of(cfg)
    key = jax.random.PRNGKey(7)
    s_len = 48
    toks = jax.random.randint(jax.random.PRNGKey(8), (s_len,), 0,
                              cfg.vocab_size)
    with jax.default_matmul_precision("highest"):
        want, _ = DecoderLM(cfg).forward(DecoderLM(cfg).init(key),
                                         {"tokens": toks[None]})
    p = ref.init_params(a, key)
    # every row a prompt row: no cache rounding, the plain forward pass
    got = ref.logits_rows(a, p, toks, jnp.asarray(s_len, jnp.int32),
                          n_rows=1, b_kv=8)
    scale = float(jnp.max(jnp.abs(want[0, -1])))
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0, -1]),
                               atol=TOL * scale, rtol=0)


def test_reference_rounds_the_agent_and_the_cache():
    a = arch_of(CONFIGS["qwen2"])
    p = ref.init_params(a, jax.random.PRNGKey(3))
    q = ref.agent_quantized(a, p, 8)
    for name in ("wq", "w_down"):
        w, wq = np.asarray(p[name]), np.asarray(q[name])
        assert not np.array_equal(w[:a.split_layer], wq[:a.split_layer])
        np.testing.assert_array_equal(w[a.split_layer:], wq[a.split_layer:])
        col_step = np.abs(w[0]).max(axis=0) / 127
        assert np.all(np.abs(w[0] - wq[0]) <= col_step / 2 + 1e-7)
    x = jax.random.normal(jax.random.PRNGKey(4), (5, 2, 16))
    r = np.asarray(ref.kv_round(x, 8))
    step = np.abs(np.asarray(x)).max(axis=-1, keepdims=True) / 127
    assert np.all(np.abs(r - np.asarray(x)) <= step / 2 + 1e-7)
    assert not np.array_equal(r, np.asarray(x))


def test_reference_imports_nothing_of_the_program():
    for f in ("reference.py", "arch.py"):
        tree = ast.parse((DENSE / f).read_text())
        names = [n.module or "" for n in ast.walk(tree)
                 if isinstance(n, ast.ImportFrom)]
        names += [a.name for n in ast.walk(tree) if isinstance(n, ast.Import)
                  for a in n.names]
        assert not [n for n in names if n.split(".")[0] == "repro"], f
