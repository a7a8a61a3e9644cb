"""ops — TPU kernel library (Pallas) with pure-XLA reference fallbacks.

The reference reaches hand-tuned kernels through orc SIMD in
tensor_transform (gst/nnstreamer/elements/gsttensortransform.c,
transform-orc.orc) and through vendor runtimes inside tensor_filter
subplugins. Here the hot ops are Pallas TPU kernels; every op also has a
jnp reference implementation used on CPU (tests) and for odd shapes —
the EdgeTPU ``device_type:dummy`` software-fallback pattern applied at the
kernel level.
"""

from nnstreamer_tpu.ops.flash_attention import flash_attention
from nnstreamer_tpu.ops.grouped_matmul import expert_tiles
from nnstreamer_tpu.ops.paged_attention import paged_attention
from nnstreamer_tpu.ops.preprocess import normalize_u8
from nnstreamer_tpu.ops.quantize import dequantize_int8, quantize_int8

__all__ = [
    "flash_attention",
    "paged_attention",
    "expert_tiles",
    "normalize_u8",
    "quantize_int8",
    "dequantize_int8",
]
