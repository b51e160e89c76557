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
    b, h, kv, dh = DECODE_BATCH, QWEN.n_heads, QWEN.n_kv_heads, QWEN.head_dim
    _compile(one_chip,
             lambda q, kc, vc, ks, vs, n: quantized_decode_attention(
                 q, kc, vc, ks, vs, n, interpret=False),
             ((b, 1, h, dh), jnp.float32), ((b, t, kv, dh), container),
             ((b, t, kv, dh), container), ((b, t, kv), jnp.float32),
             ((b, t, kv), jnp.float32), ((b,), jnp.int32))
