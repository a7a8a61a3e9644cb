"""Fused image preprocess — uint8 frame → normalized float, one VPU pass.

The reference does this as tensor_transform ``arithmetic``
(typecast + add + div) with orc SIMD on the host
(gst/nnstreamer/elements/gsttensortransform.c, transform-orc.orc). Here
the whole chain is one Pallas elementwise kernel: read u8, subtract mean,
multiply scale, cast — a single VMEM round trip instead of three
intermediate arrays.

(When a pipeline is region-fused, XLA already fuses the equivalent jnp
ops into the model program; this kernel serves the standalone-transform
path and odd hosts where the fusion pass is disabled.)
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from nnstreamer_tpu.ops.tiling import BLOCK_ROWS as _BLOCK_ROWS
from nnstreamer_tpu.ops.tiling import LANES as _LANES


def _normalize_reference(x, mean: float, scale: float, out_dtype):
    return ((x.astype(jnp.float32) - mean) * scale).astype(out_dtype)


def _kernel(x_ref, mean_ref, scale_ref, o_ref):
    mean = mean_ref[0, 0]
    scale = scale_ref[0, 0]
    x = x_ref[:]
    if x.dtype == jnp.uint8:
        # Mosaic has no direct uint8→float32 cast; widen via int32
        x = x.astype(jnp.int32)
    o_ref[:] = ((x.astype(jnp.float32) - mean) * scale).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("out_dtype", "interpret"))
def _normalize_2d(x2, mean, scale, out_dtype, interpret: bool):
    rows, _ = x2.shape  # caller pads rows to a _BLOCK_ROWS multiple
    grid = (rows // _BLOCK_ROWS,)
    return pl.pallas_call(
        _kernel,
        out_shape=jax.ShapeDtypeStruct(x2.shape, out_dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((_BLOCK_ROWS, _LANES), lambda i: (i, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((_BLOCK_ROWS, _LANES), lambda i: (i, 0)),
        interpret=interpret,
    )(x2, mean, scale)


def normalize_u8(x, mean: float = 127.5, scale: float = 1.0 / 127.5,
                 out_dtype=jnp.bfloat16, force: str | None = None):
    """(x - mean) * scale → out_dtype, for any-shape uint8/any input.

    Auto-selects the Pallas kernel on TPU (interpret mode when forced on
    CPU), the XLA reference otherwise.
    """
    on_tpu = jax.default_backend() == "tpu"
    if not (force == "pallas" or (force is None and on_tpu)):
        return _normalize_reference(x, mean, scale, out_dtype)

    from nnstreamer_tpu.ops.tiling import pad_to_tiles, unpad_from_tiles

    x2, n = pad_to_tiles(x)
    mean_s = jnp.array([[mean]], jnp.float32)
    scale_s = jnp.array([[scale]], jnp.float32)
    out2 = _normalize_2d(x2, mean_s, scale_s, jnp.dtype(out_dtype).name,
                         interpret=not on_tpu)
    return unpad_from_tiles(out2, n, x.shape)
