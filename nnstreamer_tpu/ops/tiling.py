"""Shared lane/row tiling helpers for elementwise Pallas kernels.

TPU VPU tiles are (sublane, 128-lane); elementwise kernels here flatten
any-shape arrays to a (rows, 128) layout padded to a whole number of
kernel row-blocks, run the grid, and strip the padding.
"""

from __future__ import annotations

import jax.numpy as jnp

LANES = 128
BLOCK_ROWS = 256


def pad_to_tiles(x, dtype=None):
    """Flatten + zero-pad to (N*BLOCK_ROWS, LANES); returns (x2d, n_valid).

    An array narrower than 32 bits is widened by its own dispatch first
    and narrowed again after the reshape: with libtpu 0.0.34 on a v5e,
    XLA took 125 s to COMPILE any program that flattens a
    ``uint8[8,224,224,3]`` parameter (0.4 s for the same array as
    float32; measured in PR 21) — casting inside that program does not
    help, which is also why calling this under ``jit`` with such a
    parameter still pays those two minutes once."""
    x = jnp.asarray(x)
    if dtype is not None:
        x = x.astype(dtype)
    n = x.size
    narrow = x.dtype if x.dtype.itemsize < 4 else None
    if narrow is not None:
        x = x.astype(jnp.int32 if jnp.issubdtype(narrow, jnp.integer)
                     else jnp.float32)
    flat = jnp.ravel(x)
    pad = (-n) % (LANES * BLOCK_ROWS)
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    x2d = flat.reshape(-1, LANES)
    return (x2d if narrow is None else x2d.astype(narrow)), n


def unpad_from_tiles(x2d, n_valid: int, shape):
    """Inverse of :func:`pad_to_tiles`."""
    return x2d.reshape(-1)[:n_valid].reshape(shape)
