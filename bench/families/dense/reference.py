"""Plain reference of the served decoder models: straightforward
``jax.numpy`` in float32 with matmuls at the "highest" precision, with
no cache, no kernels and no batching.

It imports nothing of the program under test and takes nothing the
program made.  It builds its own weights from the seed, by the same
draws the program's initializer documents (``init_params``), and applies
the served operating point's semantics itself:

- the agent layers ``[0, split)`` run on weights fake-quantized at b̂
  bits: per layer and per output column, step = absmax / (2^(b̂-1) - 1),
  magnitudes rounded to the step and clipped, sign kept;
- every cached key and value vector is stored at b_kv bits: one scale per
  head vector, step = absmax / (2^(b_kv-1) - 1), round, clip;
- a prompt's positions attend to the unrounded keys and values of the
  prompt (the prefill computes them before it stores them), and every
  decoded position attends to the stored (rounded) keys and values of all
  positions up to and including its own.

Departure shared with the program: rotary embedding turns the whole head
(``rotary_fraction`` 1.0), where stablelm-3b-4e1t turns a quarter of it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .arch import Arch

_QUERY_BLOCK = 512


# ---------------------------------------------------------------------------
# weights from the seed
# ---------------------------------------------------------------------------

def _stacked(key, n_layers, d_in, d_out):
    subs = jax.random.split(key, n_layers)
    return jax.vmap(lambda k: jax.random.normal(k, (d_in, d_out), jnp.float32)
                    * d_in ** -0.5)(subs)


def init_params(a: Arch, key) -> dict:
    """float32 weights from ``key``: embeddings N(0, 0.02²), every matrix
    N(0, 1/d_in), one key per layer split from the matrix's key; norm
    gains 1, norm and projection biases 0."""
    ks = jax.random.split(key, 6)
    k_tok, k_unembed = jax.random.split(ks[0])
    ka = jax.random.split(ks[1], 8)
    km = jax.random.split(ks[2], 3)
    L, d = a.n_layers, a.d_model
    p = {"tok": jax.random.normal(k_tok, (a.vocab, d), jnp.float32) * 0.02,
         "wq": _stacked(ka[0], L, d, a.q_dim),
         "wk": _stacked(ka[1], L, d, a.kv_dim),
         "wv": _stacked(ka[2], L, d, a.kv_dim),
         "wo": _stacked(ka[3], L, a.q_dim, d),
         "w_gate": _stacked(km[0], L, d, a.d_ff),
         "w_up": _stacked(km[1], L, d, a.d_ff),
         "w_down": _stacked(km[2], L, a.d_ff, d),
         "ln1": jnp.ones((L, d), jnp.float32),
         "ln2": jnp.ones((L, d), jnp.float32),
         "ln_f": jnp.ones((d,), jnp.float32)}
    if not a.tied:
        p["unembed"] = jax.random.normal(k_unembed, (d, a.vocab),
                                         jnp.float32) * d ** -0.5
    if a.norm == "layernorm":
        p.update(ln1_b=jnp.zeros((L, d), jnp.float32),
                 ln2_b=jnp.zeros((L, d), jnp.float32),
                 ln_f_b=jnp.zeros((d,), jnp.float32))
    if a.qkv_bias:
        p.update(bq=jnp.zeros((L, a.q_dim), jnp.float32),
                 bk=jnp.zeros((L, a.kv_dim), jnp.float32),
                 bv=jnp.zeros((L, a.kv_dim), jnp.float32))
    return p


_MATRICES = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")


def fake_quantize(w, bits: int):
    """Round a [in, out] matrix to ``bits`` per output column."""
    levels = 2 ** (bits - 1) - 1
    step = jnp.max(jnp.abs(w), axis=0, keepdims=True) / levels
    step = jnp.where(step <= 0, 1.0, step)
    return jnp.sign(w) * jnp.clip(jnp.round(jnp.abs(w) / step), 0, levels) \
        * step


def agent_quantized(a: Arch, p: dict, b_hat: int) -> dict:
    """``p`` with the agent layers' matrices fake-quantized at ``b_hat``."""
    out = dict(p)
    for name in _MATRICES:
        w = p[name]
        q = jax.vmap(lambda m: fake_quantize(m, b_hat))(w[:a.split_layer])
        out[name] = jnp.concatenate([q, w[a.split_layer:]], axis=0)
    return out


def kv_round(x, bits: int):
    """Store-and-read of cache vectors [..., dh] at ``bits``."""
    levels = 2 ** (bits - 1) - 1
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1, keepdims=True)
    scale = jnp.where(amax > 0, amax / levels, 1.0)
    return (jnp.clip(jnp.round(xf / scale), -levels, levels) * scale
            ).astype(x.dtype)


# ---------------------------------------------------------------------------
# the forward pass
# ---------------------------------------------------------------------------

def _norm(a: Arch, x, g, b=None):
    if a.norm == "layernorm":
        mu = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
        return (x - mu) / jnp.sqrt(var + a.norm_eps) * g + b
    ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x / jnp.sqrt(ms + a.norm_eps) * g


def _rope(a: Arch, x, pos):
    """Rotate the first ``rotary_fraction`` of each head, its two halves
    paired (x1, x2) -> (x1 cos - x2 sin, x1 sin + x2 cos)."""
    rd = int(a.head_dim * a.rotary_fraction)
    freq = a.rope_theta ** (-jnp.arange(0, rd, 2, dtype=jnp.float32) / rd)
    ang = pos[:, None].astype(jnp.float32) * freq[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    xr = x[..., :rd].astype(jnp.float32)
    x1, x2 = xr[..., :rd // 2], xr[..., rd // 2:]
    rot = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)
    return jnp.concatenate([rot.astype(x.dtype), x[..., rd:]], axis=-1)


def fp8_round(x):
    """x with each element rounded to float8 e4m3 after scaling the
    tensor's absmax to e4m3's largest value (448), then scaled back: the
    usual per-tensor fp8 recipe."""
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, amax / 448.0, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(x.dtype) * scale


def _operands(lowp: bool):
    """The rounding every matmul's operands take: none, or fp8."""
    return fp8_round if lowp else (lambda x: x)


def _attention(a: Arch, q, k, v, kq, vq, n_prompt, rnd):
    """Causal attention, query rows in blocks.  q [S, H, dh]; k, v (as
    computed) and kq, vq (as stored) [S, KV, dh].  Rows before
    ``n_prompt`` read k, v; later rows read kq, vq."""
    s_len = q.shape[0]
    g = a.n_heads // a.n_kv_heads
    qb = min(_QUERY_BLOCK, s_len)
    nb = s_len // qb
    qs = q.reshape(nb, qb, a.n_kv_heads, g, a.head_dim)
    scale = a.head_dim ** -0.5
    kpos = jnp.arange(s_len)

    def block(args):
        qblk, start = args
        rows = start + jnp.arange(qb)
        stored = (rows >= n_prompt)[None, None, :, None]
        causal = (kpos[None, :] <= rows[:, None])[None, None]
        out = 0.0
        for kk, vv, use in ((k, v, ~stored), (kq, vq, stored)):
            s = jnp.einsum("qkgd,tkd->kgqt", rnd(qblk), rnd(kk)) * scale
            s = jnp.where(causal, s, -jnp.inf)
            pr = jax.nn.softmax(s, axis=-1)
            o = jnp.einsum("kgqt,tkd->kgqd", rnd(pr), rnd(vv))
            out = out + jnp.where(use, o, 0.0)
        return out.transpose(2, 0, 1, 3)          # [qb, KV, G, dh]

    outs = jax.lax.map(block, (qs, jnp.arange(nb) * qb))
    return outs.reshape(s_len, a.q_dim)


def logits_rows(a: Arch, p: dict, tokens, n_prompt, *, n_rows: int,
                b_kv: int, lowp: bool = False):
    """Logits [n_rows, vocab] (float32) at positions n_prompt-1 ...
    n_prompt-2+n_rows of ``tokens`` [S]: the prompt followed by the
    served tokens but the last, right-padded to a fixed S (padding sits
    after every row read, so causality hides it).  Row i scores the i-th
    served token.  ``p`` holds the weights as served (agent layers
    already fake-quantized).  float32 throughout, matmuls at the
    "highest" precision; ``lowp`` rounds every matmul's operands to fp8
    first (``fp8_round``), the lower-precision control."""
    with jax.default_matmul_precision("highest"):
        return _logits_rows(a, p, tokens, n_prompt, n_rows=n_rows,
                            b_kv=b_kv, lowp=lowp)


@functools.partial(jax.jit, static_argnames=("a", "n_rows", "b_kv", "lowp"))
def _logits_rows(a, p, tokens, n_prompt, *, n_rows, b_kv, lowp):
    rnd = _operands(lowp)

    def mm(x, w):
        return rnd(x) @ rnd(w)

    s_len = tokens.shape[0]
    pos = jnp.arange(s_len)
    x = p["tok"][tokens]

    def layer(x, lp):
        h = _norm(a, x, lp["ln1"], lp.get("ln1_b"))
        q, k, v = mm(h, lp["wq"]), mm(h, lp["wk"]), mm(h, lp["wv"])
        if a.qkv_bias:
            q, k, v = q + lp["bq"], k + lp["bk"], v + lp["bv"]
        q = _rope(a, q.reshape(s_len, a.n_heads, a.head_dim), pos)
        k = _rope(a, k.reshape(s_len, a.n_kv_heads, a.head_dim), pos)
        v = v.reshape(s_len, a.n_kv_heads, a.head_dim)
        att = _attention(a, q, k, v, kv_round(k, b_kv), kv_round(v, b_kv),
                         n_prompt, rnd)
        x = x + mm(att, lp["wo"])
        h = _norm(a, x, lp["ln2"], lp.get("ln2_b"))
        x = x + mm(jax.nn.silu(mm(h, lp["w_gate"])) * mm(h, lp["w_up"]),
                   lp["w_down"])
        return x, None

    per_layer = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down", "ln1",
                 "ln2", "ln1_b", "ln2_b", "bq", "bk", "bv")
    x, _ = jax.lax.scan(layer, x, {n: p[n] for n in per_layer if n in p})
    x = jax.lax.dynamic_slice_in_dim(x, n_prompt - 1, n_rows, axis=0)
    x = _norm(a, x, p["ln_f"], p.get("ln_f_b"))
    return mm(x, p["tok"].T if a.tied else p["unembed"])
