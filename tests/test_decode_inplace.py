"""The fused decode step touches the int8 KV cache only where it lies
(DESIGN.md §13): per layer, one new entry per row is written in place
and the attention kernel reads the layer's codes out of the stack.

Checked on the traced program, so the check holds on every backend:
outside the kernel, no operation of the fused decode chunk takes or
makes a cache-sized array other than by carrying it (loops), viewing it
(reshapes) or handing it to the kernel — no layer slice, head
transpose, restack or scan over the cache as a sequence.  The optimized
HLO for the chip is checked in ``tests/test_tpu_compile.py``.
"""

import dataclasses

import jax
from jax.extend.core import ClosedJaxpr, Jaxpr
import jax.numpy as jnp
import pytest

from repro.configs.stablelm_3b import FULL
from repro.models.lm import DecoderLM
from repro.runtime.decode_engine import _build_fused_decode, _cache_sds

B, T = 4, 512

# MHA as stablelm (every query head its own KV head), and qwen2's GQA
# grouping (14 query heads over 2 KV heads); head_dim 64, 3 layers
SHAPES = {
    "mha": dict(d_model=256, n_heads=4, n_kv_heads=4),
    "gqa": dict(d_model=896, n_heads=14, n_kv_heads=2),
}

# what may touch a cache-sized array outside the kernel: carrying it
# through a loop or call, or viewing it under another shape
_PASS_THROUGH = {"while", "scan", "pjit", "closed_call", "cond",
                 "reshape", "pallas_call"}


def _model(shape):
    cfg = dataclasses.replace(FULL, head_dim=64, n_layers=3, d_ff=512,
                              vocab_size=640, split_layer=1,
                              **SHAPES[shape])
    return cfg, DecoderLM(cfg)


def _sub_jaxprs(eqn):
    for v in eqn.params.values():
        for x in (v if isinstance(v, (tuple, list)) else (v,)):
            if isinstance(x, ClosedJaxpr):
                yield x.jaxpr
            elif isinstance(x, Jaxpr):
                yield x


def _cache_moves(jaxpr, sizes):
    """Equations outside the kernel that move a cache-sized array: one
    of ``sizes`` elements (a layer's or the stack's codes or scales)."""
    def cache(v):
        return getattr(getattr(v, "aval", None), "size", 0) in sizes

    found = []
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name == "pallas_call":
            continue
        if name == "scan":
            # the scanned sequence (xs) and stacked outputs (ys) of a
            # scan are sliced and restacked every iteration
            p = eqn.params
            xs = eqn.invars[p["num_consts"] + p["num_carry"]:]
            ys = eqn.outvars[p["num_carry"]:]
            if any(cache(v) for v in (*xs, *ys)):
                found.append("scan over the cache")
        elif name not in _PASS_THROUGH and any(
                cache(v) for v in (*eqn.invars, *eqn.outvars)):
            found.append(name)
        for sub in _sub_jaxprs(eqn):
            found += _cache_moves(sub, sizes)
    return found


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_fused_decode_never_moves_the_cache(shape):
    cfg, model = _model(shape)
    params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    codes, scales, vec = _cache_sds(cfg, 8, B, T)
    scalar = jax.ShapeDtypeStruct((), jnp.int32)
    jaxpr = jax.make_jaxpr(_build_fused_decode(model, 8))(
        params, codes, codes, scales, scales, vec, vec, vec, scalar,
        scalar)
    layer_scales = B * T * cfg.n_kv_heads
    layer_codes = layer_scales * cfg.head_dim
    sizes = {n * m for n in (1, cfg.n_layers)
             for m in (layer_codes, layer_scales)}
    assert _cache_moves(jaxpr.jaxpr, sizes) == []
    # and the kernel is handed the whole stack, once per layer step
    calls = [e for e in jaxpr.eqns for e in _walk(e)
             if e.primitive.name == "pallas_call"]
    assert calls and all(
        any(v.aval.shape[0] == cfg.n_layers
            and v.aval.size == cfg.n_layers * layer_codes
            for v in e.invars) for e in calls)


def _walk(eqn):
    yield eqn
    for sub in _sub_jaxprs(eqn):
        for e in sub.eqns:
            yield from _walk(e)
