"""Int8 tensor quantization kernels — bandwidth compression for streams.

Plays the role the reference's sparse encoder plays (bandwidth saving on
tensor streams, gst/nnstreamer/elements/gsttensorsparseenc.c) for dense
activations: per-tensor absmax int8 with stochastic rounding on TPU (the
Pallas PRNG), deterministic nearest-rounding in the reference path. A
quantized frame ships 4× fewer bytes over query/pubsub transports.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from nnstreamer_tpu.ops.tiling import BLOCK_ROWS as _BLOCK_ROWS
from nnstreamer_tpu.ops.tiling import LANES as _LANES


def _quantize_reference(x):
    xf = x.astype(jnp.float32)
    scale = jnp.max(jnp.abs(xf)) / 127.0
    scale = jnp.maximum(scale, 1e-30)
    q = jnp.clip(jnp.round(xf / scale), -127, 127).astype(jnp.int8)
    return q, scale.reshape(1)


def dequantize_int8(q, scale):
    """int8 values + scalar scale → float32."""
    return q.astype(jnp.float32) * jnp.reshape(scale, ())


def _round_dithered(scaled, dither):
    # stochastic round to int8: uniform dither in [-0.5, 0.5) before
    # nearest-round has the same expectation as true stochastic rounding
    return jnp.clip(jnp.round(scaled + dither), -127, 127).astype(jnp.int8)


def _quant_kernel_prng(seed_ref, x_ref, scale_ref, q_ref):
    """TPU-only: dither from the on-core PRNG (no HBM dither traffic)."""
    pltpu.prng_seed(seed_ref[0] + pl.program_id(0))
    inv = 1.0 / scale_ref[0]
    scaled = jnp.clip(x_ref[:].astype(jnp.float32) * inv, -127.0, 127.0)
    # int32 bitcast (Mosaic has no uint32→f32 cast): uniform random int32
    # × 2⁻³² is already uniform in [-0.5, 0.5)
    bits = pltpu.bitcast(pltpu.prng_random_bits(scaled.shape), jnp.int32)
    dither = bits.astype(jnp.float32) * (1.0 / 4294967296.0)
    q_ref[:] = _round_dithered(scaled, dither)


def _quant_kernel_dither(x_ref, scale_ref, dither_ref, q_ref):
    """Interpret-mode variant: pltpu.prng_* has no CPU interpreter rule,
    so the dither is generated outside and streamed in."""
    inv = 1.0 / scale_ref[0]
    scaled = jnp.clip(x_ref[:].astype(jnp.float32) * inv, -127.0, 127.0)
    q_ref[:] = _round_dithered(scaled, dither_ref[:])


@functools.partial(jax.jit, static_argnames=("interpret",))
def _quantize_2d(x2, scale, seed, interpret: bool):
    rows, _ = x2.shape
    grid = (rows // _BLOCK_ROWS,)
    block = pl.BlockSpec((_BLOCK_ROWS, _LANES), lambda i: (i, 0))
    if interpret:
        dither = jax.random.uniform(
            jax.random.key(seed[0]), x2.shape, jnp.float32, -0.5, 0.5)
        return pl.pallas_call(
            _quant_kernel_dither,
            out_shape=jax.ShapeDtypeStruct(x2.shape, jnp.int8),
            grid=grid,
            in_specs=[block, pl.BlockSpec(memory_space=pltpu.SMEM), block],
            out_specs=block,
            interpret=True,
        )(x2, scale, dither)
    return pl.pallas_call(
        _quant_kernel_prng,
        out_shape=jax.ShapeDtypeStruct(x2.shape, jnp.int8),
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            block,
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=block,
    )(seed, x2, scale)


def quantize_int8(x, seed: int = 0, force: str | None = None):
    """Per-tensor absmax int8. Returns (int8 values, scale[1]).

    TPU path adds stochastic dither from the on-core PRNG so repeated
    streaming quantization doesn't bias activations; reference path is
    deterministic nearest (CPU tests stay reproducible).
    """
    on_tpu = jax.default_backend() == "tpu"
    if not (force == "pallas" or (force is None and on_tpu)):
        return _quantize_reference(x)

    from nnstreamer_tpu.ops.tiling import pad_to_tiles, unpad_from_tiles

    xf = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(xf)) / 127.0, 1e-30).reshape(1)
    x2, n = pad_to_tiles(xf)
    q2 = _quantize_2d(x2, scale, jnp.array([seed], jnp.int32),
                      interpret=not on_tpu)
    return unpad_from_tiles(q2, n, x.shape), scale
