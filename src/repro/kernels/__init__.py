"""Pallas TPU kernels for the quantization hot path.

  qmm.py       — quantized-weight matmul (int8 / packed-int4 HBM-resident
                 weights, per-group scales, in-VMEM dequant before the MXU)
  quantize.py  — fused absmax group quantizer (+ the plain-jnp KV-cache
                 quantizer the decode engine traces in-graph)
  decode_attn.py — fused dequant-attend flash-decoding kernel over
                 int8-held KV codes (DESIGN.md §13)
  ops.py       — jit'd wrappers (dispatch rule, padding,
                 QuantizedLinear record)
  ref.py       — pure-jnp oracles the tests allclose against
  pallas_env.py — the interpret-mode rule (off-TPU only) every kernel's
                 ``interpret=None`` default routes through

Batch contract (DESIGN.md §3, §7): activations may carry any number of
leading dimensions — ``[S, K]``, ``[B, S, K]``, deeper stacks — which the
ops.py wrappers flatten into the kernel's M axis and restore on the way
out.  Rows are computed independently, so the batched serving engine
(``runtime/serve_engine.py``) packs many requests into one kernel dispatch
with per-request results bitwise identical to single-request serving.
"""

from .ops import (QuantizedLinear, group_quantize, quantize_linear,  # noqa: F401
                  quantized_matmul, quantized_matmul_int4)
