"""Plain reference of the routed-expert decoder of this family's ``arch.py``:
``jax.numpy`` in float32 at the "highest" precision, no cache, no
kernels, no batching, every expert computed and weighted by its gate.
It imports nothing of the program and draws its own weights from the
seed, by the draws the program's initializer documents.

The served operating point's semantics, as in the dense reference:
agent layers' matrices fake-quantized at b̂ per output column (an
expert stack's column over all its experts); cached keys and values
rounded to b_kv bits per head vector; prompt rows attend to the
unrounded keys and values, decoded rows to the stored ones.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def _normal(key, shape):
    return jax.random.normal(key, shape, jnp.float32) * shape[-2] ** -0.5


def _stacked(key, n, shape):
    return jax.vmap(lambda k: _normal(k, shape))(jax.random.split(key, n))


def _experts(key, a, shape):
    return jax.vmap(lambda k: _stacked(k, a.n_experts, shape))(
        jax.random.split(key, a.n_layers))


def init_params(a, key) -> dict:
    ks = jax.random.split(key, 6)
    k_tok, k_unembed = jax.random.split(ks[0])
    ka = jax.random.split(ks[1], 8)
    km = jax.random.split(ks[2], 4)
    L, d, f = a.n_layers, a.d_model, a.expert_d_ff
    p = {"tok": jax.random.normal(k_tok, (a.vocab, d), jnp.float32) * 0.02,
         "wq": _stacked(ka[0], L, (d, a.q_dim)),
         "wk": _stacked(ka[1], L, (d, a.kv_dim)),
         "wv": _stacked(ka[2], L, (d, a.kv_dim)),
         "wo": _stacked(ka[3], L, (a.q_dim, d)),
         "router": _stacked(km[0], L, (d, a.n_experts)),
         "e_gate": _experts(km[1], a, (d, f)),
         "e_up": _experts(km[2], a, (d, f)),
         "e_down": _experts(km[3], a, (f, d)),
         "ln1": jnp.ones((L, d), jnp.float32),
         "ln2": jnp.ones((L, d), jnp.float32),
         "ln_f": jnp.ones((d,), jnp.float32)}
    if not a.tied:
        p["unembed"] = _normal(k_unembed, (d, a.vocab))
    if a.qkv_bias:
        p.update(bq=jnp.zeros((L, a.q_dim), jnp.float32),
                 bk=jnp.zeros((L, a.kv_dim), jnp.float32),
                 bv=jnp.zeros((L, a.kv_dim), jnp.float32))
    return p


def _fake_quantize(w, bits: int):
    """[..., out] rounded per output column over all leading axes."""
    levels = 2 ** (bits - 1) - 1
    axes = tuple(range(w.ndim - 1))
    step = jnp.max(jnp.abs(w), axis=axes, keepdims=True) / levels
    step = jnp.where(step <= 0, 1.0, step)
    return jnp.sign(w) * jnp.clip(jnp.round(jnp.abs(w) / step), 0, levels) \
        * step


def agent_quantized(a, p: dict, b_hat: int) -> dict:
    out = dict(p)
    for name in ("wq", "wk", "wv", "wo", "router", "e_gate", "e_up",
                 "e_down"):
        w = p[name]
        q = jax.vmap(lambda m: _fake_quantize(m, b_hat))(w[:a.split_layer])
        out[name] = jnp.concatenate([q, w[a.split_layer:]], axis=0)
    return out


def _kv_round(x, bits: int):
    levels = 2 ** (bits - 1) - 1
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    scale = jnp.where(amax > 0, amax / levels, 1.0)
    return jnp.clip(jnp.round(x / scale), -levels, levels) * scale


def _fp8_round(x):
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, amax / 448.0, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(x.dtype) * scale


def _rms(a, x, g):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                        + a.norm_eps) * g


def _rope(a, x, pos):
    freq = a.rope_theta ** (-jnp.arange(0, a.head_dim, 2, dtype=jnp.float32)
                            / a.head_dim)
    ang = pos[:, None].astype(jnp.float32) * freq[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :a.head_dim // 2], x[..., a.head_dim // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def logits_rows(a, p: dict, tokens, n_prompt, *, n_rows: int, b_kv: int,
                lowp: bool = False):
    """Logits [n_rows, vocab] at positions n_prompt-1 ...
    n_prompt-2+n_rows of ``tokens`` (prompt, then the served tokens but
    the last, right-padded); ``lowp`` rounds every matmul's operands to
    fp8 first."""
    with jax.default_matmul_precision("highest"):
        return _logits_rows(a, p, tokens, n_prompt, n_rows=n_rows,
                            b_kv=b_kv, lowp=lowp)


@functools.partial(jax.jit, static_argnames=("a", "n_rows", "b_kv", "lowp"))
def _logits_rows(a, p, tokens, n_prompt, *, n_rows, b_kv, lowp):
    rnd = _fp8_round if lowp else (lambda x: x)
    s_len = tokens.shape[0]
    pos = jnp.arange(s_len)
    g = a.n_heads // a.n_kv_heads
    causal = pos[None, :] <= pos[:, None]
    stored = (pos >= n_prompt)[:, None, None, None]

    def attend(q, k, v):
        s = jnp.einsum("qkgd,tkd->qkgt", rnd(q), rnd(k)) * a.head_dim ** -0.5
        s = jnp.where(causal[:, None, None, :], s, -jnp.inf)
        return jnp.einsum("qkgt,tkd->qkgd", rnd(jax.nn.softmax(s, -1)),
                          rnd(v))

    def layer(x, lp):
        h = _rms(a, x, lp["ln1"])
        q, k, v = (rnd(h) @ rnd(lp[n]) for n in ("wq", "wk", "wv"))
        if a.qkv_bias:
            q, k, v = q + lp["bq"], k + lp["bk"], v + lp["bv"]
        q = _rope(a, q.reshape(s_len, a.n_heads, a.head_dim), pos)
        q = q.reshape(s_len, a.n_kv_heads, g, a.head_dim)
        k = _rope(a, k.reshape(s_len, a.n_kv_heads, a.head_dim), pos)
        v = v.reshape(s_len, a.n_kv_heads, a.head_dim)
        att = jnp.where(stored,
                        attend(q, _kv_round(k, b_kv), _kv_round(v, b_kv)),
                        attend(q, k, v))
        x = x + rnd(att.reshape(s_len, a.q_dim)) @ rnd(lp["wo"])
        h = _rms(a, x, lp["ln2"])
        probs = jax.nn.softmax(rnd(h) @ rnd(lp["router"]), -1)
        top, idx = jax.lax.top_k(probs, a.experts_per_token)
        gates = jnp.zeros_like(probs).at[pos[:, None], idx].set(
            top / jnp.sum(top, -1, keepdims=True))
        up = jnp.einsum("sd,edf->sef", rnd(h), rnd(lp["e_up"]))
        gate = jnp.einsum("sd,edf->sef", rnd(h), rnd(lp["e_gate"]))
        y = jnp.einsum("sef,efd->sed", rnd(jax.nn.silu(gate) * up),
                       rnd(lp["e_down"]))
        return x + jnp.einsum("sed,se->sd", y, gates), None

    per_layer = ("wq", "wk", "wv", "wo", "router", "e_gate", "e_up",
                 "e_down", "ln1", "ln2", "bq", "bk", "bv")
    x, _ = jax.lax.scan(layer, p["tok"][tokens],
                        {n: p[n] for n in per_layer if n in p})
    x = jax.lax.dynamic_slice_in_dim(x, n_prompt - 1, n_rows, axis=0)
    x = _rms(a, x, p["ln_f"])
    return rnd(x) @ rnd(p["tok"].T if a.tied else p["unembed"])
