"""Compile rehearsals: the serving path's Pallas kernels at qwen2-0.5b
widths, compiled for a described TPU v5e chip.

Interpret mode never applies the TPU lowering's block rules, so a kernel
can pass every CPU test and still be refused on the chip.  These tests
compile each kernel of the serving path — the int8 and packed-int4
quantized matmuls, the fused group quantizer, and the quantized decode
attention over the cache buckets the decode engine warms — for one chip
of a described ``v5e:2x2`` topology, with nothing attached, and check
that the executable holds the Mosaic kernel (``tpu_custom_call``).

The topology is described inside a module-scoped fixture (never at
import): only one process at a time may load the TPU library, and every
test worker imports this file.
"""

import jax
import jax.numpy as jnp
import pytest

from repro.configs.qwen2_0_5b import FULL as QWEN
from repro.kernels import ops
from repro.kernels.decode_attn import quantized_decode_attention

GROUP = 128
ROWS = 128                      # the smallest kernel row bucket
# every distinct [K, N] projection of a qwen2-0.5b block: q/o, k/v,
# gate/up, down
PROJECTIONS = [
    (QWEN.d_model, QWEN.q_dim),
    (QWEN.d_model, QWEN.n_kv_heads * QWEN.head_dim),
    (QWEN.d_model, QWEN.d_ff),
    (QWEN.d_ff, QWEN.d_model),
]
DECODE_BATCH = 4                # launch/serve.py's default --max-batch


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means no compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compile(one_chip, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    exe = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in exe.as_text()
    assert exe.memory_analysis() is not None
    return exe


@pytest.mark.parametrize("k,n", PROJECTIONS)
def test_qmm_int8_compiles(one_chip, k, n):
    _compile(one_chip,
             lambda x, c, s: ops.quantized_matmul(x, c, s, interpret=False),
             ((ROWS, k), jnp.float32), ((k, n), jnp.int8),
             ((k // GROUP, n), jnp.float32))


@pytest.mark.parametrize("k,n", PROJECTIONS)
def test_qmm_int4_compiles(one_chip, k, n):
    _compile(one_chip,
             lambda x, p, s: ops.quantized_matmul_int4(x, p, s,
                                                       interpret=False),
             ((ROWS, k), jnp.float32), ((k // 2, n), jnp.int8),
             ((k // GROUP, n), jnp.float32))


@pytest.mark.parametrize("k,n", PROJECTIONS)
def test_group_quantize_compiles(one_chip, k, n):
    _compile(one_chip,
             lambda w: ops.group_quantize(w, group_size=GROUP,
                                          interpret=False),
             ((k, n), jnp.float32))


@pytest.mark.parametrize("t,container", [
    # every cache bucket the decode engine warms, int8 codes ...
    (16, jnp.int8), (32, jnp.int8), (64, jnp.int8), (128, jnp.int8),
    (256, jnp.int8),
    (256, jnp.float32),         # ... and the raw b_kv >= 16 container
])
def test_decode_attention_compiles(one_chip, t, container):
    """The decode step's form: new entries written into one layer of
    the stack in place (cache operands aliased to outputs), then
    attended."""
    b, h, kv, dh = DECODE_BATCH, QWEN.n_heads, QWEN.n_kv_heads, QWEN.head_dim
    n_l = QWEN.n_layers
    exe = _compile(
        one_chip,
        lambda q, kc, vc, ks, vs, n, at, kn, vn, ksn, vsn:
            quantized_decode_attention(q, kc, vc, ks, vs, n, at,
                                       (kn, vn, ksn, vsn), interpret=False),
        ((b, 1, h, dh), jnp.float32), ((n_l, b, kv, dh, t), container),
        ((n_l, b, kv, dh, t), container), ((n_l, b, kv, 1, t), jnp.float32),
        ((n_l, b, kv, 1, t), jnp.float32), ((b,), jnp.int32),
        ((), jnp.int32), ((b, kv, dh), container), ((b, kv, dh), container),
        ((b, kv), jnp.float32), ((b, kv), jnp.float32))
    assert "output_to_operand_aliasing" in exe.as_text()


# the long-context cell's slot block: 16 slots x 4096 positions, where a
# cache stays in HBM (a small one the compiler may stage whole in VMEM)
CELL_BATCH, CELL_T = 16, 4096


@pytest.mark.parametrize("kv,heads,d_model", [
    (4, 4, 256),            # MHA, as stablelm: every head its own KV
    (2, 14, 896),           # qwen2's GQA grouping: 14 heads over 2
])
def test_fused_decode_updates_cache_in_place(one_chip, monkeypatch, kv,
                                             heads, d_model):
    """The whole fused decode chunk, compiled for the chip: no copy,
    transpose or dynamic slice of one layer's codes or of the stack
    (the layer slice, head transpose, restack and loop-carry copies the
    step once made every token), and temporaries under one layer's
    codes (DESIGN.md §13)."""
    import dataclasses
    import re

    from repro.configs.stablelm_3b import FULL
    from repro.kernels import decode_attn
    from repro.models.lm import DecoderLM
    from repro.runtime.decode_engine import _build_fused_decode, _cache_sds

    # the model's kernel call asks the backend, which here is the CPU
    monkeypatch.setattr(decode_attn, "use_interpret", lambda: False)
    cfg = dataclasses.replace(FULL, d_model=d_model, n_heads=heads,
                              n_kv_heads=kv, head_dim=64, n_layers=3,
                              d_ff=512, vocab_size=512, split_layer=1)
    model = DecoderLM(cfg)

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip), tree)

    params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    codes, scales, vec = on_chip(_cache_sds(cfg, 8, CELL_BATCH, CELL_T))
    scalar = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    exe = jax.jit(_build_fused_decode(model, 8),
                  donate_argnums=(1, 2, 3, 4, 5, 6)).lower(
        on_chip(params), codes, codes, scales, scales, vec, vec, vec,
        scalar, scalar).compile()
    text = exe.as_text()
    assert "tpu_custom_call" in text
    layer = CELL_BATCH * CELL_T * kv * cfg.head_dim
    moves = []
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%(\S+) = \w+\[([\d,]*)\]", line)
        if not m or not any(op in m.group(1) for op in
                            ("copy", "transpose", "dynamic-slice")):
            continue
        size = 1
        for d in filter(None, m.group(2).split(",")):
            size *= int(d)
        if size in (layer, cfg.n_layers * layer):
            moves.append(m.group(1))
    assert moves == []
    assert exe.memory_analysis().temp_size_in_bytes < layer
