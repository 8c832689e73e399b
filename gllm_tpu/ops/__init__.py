"""Device-side ops.

The reference funnels every kernel call through one dispatch point
(/root/reference/gllm/_custom_ops.py:1-10) so backends can be swapped. Here the
same role is played by this package: elementwise/norm/rope/sampling ops are
plain jnp (XLA fuses them into neighboring matmuls); paged attention has an
XLA reference implementation (runs everywhere, used as the test oracle) and a
Pallas TPU kernel, selected via :func:`gllm_tpu.ops.attention.paged_attention`.
"""

from gllm_tpu.ops.layers import (fused_add_rms_norm, layer_norm, rms_norm,
                                 silu_and_mul, gelu_and_mul)
from gllm_tpu.ops.rope import apply_rope, compute_rope_cos_sin
from gllm_tpu.ops.kv_cache import write_kv, write_kv_quant
from gllm_tpu.ops.attention import paged_attention

__all__ = [
    "apply_rope",
    "compute_rope_cos_sin",
    "fused_add_rms_norm",
    "gelu_and_mul",
    "layer_norm",
    "paged_attention",
    "rms_norm",
    "silu_and_mul",
    "write_kv",
    "write_kv_quant",
]
