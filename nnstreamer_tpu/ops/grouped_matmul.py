"""The routed experts' matmuls — sorted tiles of rows, each through the
gated MLP of the expert it was routed to, as one grouped matmul.

``models/hybrid.py moe_ffn`` sorts the (token, choice) pairs by held
expert into a padded buffer ``x [rows, d]`` whose every ``tile`` rows
belong to one expert (``tile_expert``), the live tiles first. The tile
loop (:func:`expert_tiles_reference`) is a ``fori_loop`` with the live
count as its trip count: XLA makes it a ``while`` whose body slices a
tile's weights out of ``[n_held, d, 2f]`` / ``[n_held, f, d]`` and only
then multiplies, so nothing of tile i+1 is in flight while tile i
computes. The kernel here is the same walk as a Pallas grid: the grid's
leading axis is the STATIC ``rows // tile``, ``tile_expert`` and the live
count ride in by scalar prefetch, and the ``index_map``s pick tile i's
expert, so the pipeline fetches step i+1's weight blocks while step i
computes. Consecutive tiles of one expert name the same block and fetch
nothing; so does every step past the live count (it names the last live
step's blocks, does no matmul and leaves its rows of ``out`` zero: pairs
that fell on an absent expert read such a row under a gate of 0.0).

An inner grid axis chunks the expert's width ``f`` where whole blocks
would not fit the chip's VMEM twice over (:func:`expert_blocks`): the
``a`` half and the ``b`` half of ``w_in = [a | b]`` are two views of the
one array, and ``out`` gathers the chunks' float32 partial products.

The numeric contract is :func:`gated_mlp`'s: operands in ``dtype``,
float32 accumulation, ``silu(a) * b`` rounded to ``dtype`` before the
second matmul, float32 out. Chunking ``f`` changes the order of a float32
sum and nothing else.

``expert_tiles`` auto-selects like ``paged_attention``: the kernel on a
TPU for shapes it takes, the tile loop elsewhere.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from nnstreamer_tpu.log import get_logger

log = get_logger("grouped-matmul")

#: the VMEM a described or interpreted chip is taken to have (a TPU v5e's;
#: on a TPU the chip says its own)
VMEM_BYTES = 128 * 1024 * 1024


def gated_mlp(h, w_in, w_out, dtype):
    """``(silu(a) * b) . w_out`` with ``[a | b] = h . w_in``."""
    ab = jnp.dot(h, w_in.astype(dtype), preferred_element_type=jnp.float32)
    a, b = jnp.split(ab, 2, axis=-1)
    return jnp.dot((jax.nn.silu(a) * b).astype(dtype), w_out.astype(dtype),
                   preferred_element_type=jnp.float32)


def expert_tiles_reference(x, tile_expert, n_live, w_in, w_out, tile: int):
    """The tile loop: ``out [rows, d]`` float32, tile ``i < n_live`` of
    ``x`` through expert ``tile_expert[i]``, every other row zero."""
    def one_tile(i, out):
        w = tile_expert[i]
        y = gated_mlp(lax.dynamic_slice_in_dim(x, i * tile, tile),
                      w_in[w], w_out[w], x.dtype)
        return lax.dynamic_update_slice_in_dim(out, y, i * tile, 0)

    return lax.fori_loop(0, n_live, one_tile,
                         jnp.zeros(x.shape, jnp.float32))


def expert_blocks(d: int, f: int, tile: int, dtype,
                  vmem_bytes: int = VMEM_BYTES) -> tuple:
    """``(f_chunk, vmem_limit_bytes)``: the rule for the kernel's blocks.

    A grid step holds, twice over (the pipeline's two buffers), a chunk of
    ``f_chunk`` columns of the expert (``[d, f_chunk]`` of ``a``, of ``b``
    and ``[f_chunk, d]`` of ``w_out``), the tile of ``x`` and the float32
    tile of ``out``; once, the float32 ``a``, ``b`` and their product.
    ``f_chunk`` is the largest multiple of 128 lanes dividing ``f`` for
    which that is at most HALF the chip's VMEM: blocks of several MB are
    what reaches the HBM rate, and the other half is the compiler's room.
    The limit handed to Mosaic is what the step holds and half as much
    again."""
    size = jnp.dtype(dtype).itemsize

    def held(fc):
        return (2 * (3 * d * fc * size + tile * d * (size + 4))
                + 3 * tile * fc * 4)

    chunks = [fc for fc in range(f, 0, -128) if f % fc == 0]
    fc = next((c for c in chunks if held(c) <= vmem_bytes // 2), chunks[-1])
    return fc, min(vmem_bytes, held(fc) * 3 // 2)


def _kernel(expert_ref, live_ref, x_ref, a_ref, b_ref, out_w_ref, o_ref):
    i, j = pl.program_id(0), pl.program_id(1)
    live = i < live_ref[0]

    @pl.when(jnp.logical_not(live))
    def _dead():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(live)
    def _tile():
        x = x_ref[...]
        a = jnp.dot(x, a_ref[...], preferred_element_type=jnp.float32)
        b = jnp.dot(x, b_ref[...], preferred_element_type=jnp.float32)
        y = jnp.dot((jax.nn.silu(a) * b).astype(x.dtype), out_w_ref[...],
                    preferred_element_type=jnp.float32)

        @pl.when(j == 0)
        def _first():
            o_ref[...] = y

        @pl.when(j > 0)
        def _more():
            o_ref[...] += y


@functools.partial(jax.jit, static_argnames=(
    "tile", "f_chunk", "vmem_limit_bytes", "interpret"))
def _expert_tiles(x, tile_expert, n_live, w_in, w_out, tile: int,
                  f_chunk: int, vmem_limit_bytes: int, interpret: bool):
    """Kernel entry: ``x [rows, d]`` and the weights in one dtype."""
    rows, d = x.shape
    f = w_out.shape[1]
    chunks = f // f_chunk

    # a step past the live count names the last live step's blocks: the
    # pipeline sees an index it already holds and fetches nothing
    def at(i, j, expert_ref, live_ref):
        last = jnp.maximum(live_ref[0] - 1, 0)
        return (expert_ref[jnp.minimum(i, last)],
                jnp.where(i < live_ref[0], j, chunks - 1),
                jnp.minimum(i, last))

    def half(offset):
        def index(i, j, *refs):
            e, c, _ = at(i, j, *refs)
            return e, 0, offset + c
        return pl.BlockSpec((None, d, f_chunk), index)

    def out_w_index(i, j, *refs):
        e, c, _ = at(i, j, *refs)
        return e, c, 0

    return pl.pallas_call(
        _kernel,
        out_shape=jax.ShapeDtypeStruct((rows, d), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,      # tile_expert, the live tile count
            grid=(rows // tile, chunks),
            in_specs=[
                pl.BlockSpec((tile, d), lambda i, j, *refs:
                             (at(i, j, *refs)[2], 0)),
                half(0), half(chunks),
                pl.BlockSpec((None, f_chunk, d), out_w_index),
            ],
            out_specs=pl.BlockSpec((tile, d), lambda i, j, *_: (i, 0))),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=vmem_limit_bytes),
        interpret=interpret,
        name="nns_expert_tiles",
    )(tile_expert.astype(jnp.int32),
      jnp.asarray(n_live, jnp.int32).reshape(1), x, w_in, w_in, w_out)


def _pallas_reject(x, w_in, w_out, tile: int) -> str | None:
    """Why these shapes cannot go to the kernel, or None when they can.

    What was proved: Mosaic (libtpu 0.0.34, for a TPU v5e) compiles the
    kernel for bfloat16 experts of ``[2048, 1024] + [512, 2048]`` at tiles
    of 8 and 32 rows and of ``[4096, 1536] + [768, 4096]`` at 32 and 128
    (``tests/test_paged_attention.py``), and the chip served both cells
    from it; the interpreter holds cut-down twins, float32 too, to the
    tile loop (``tests/test_grouped_matmul.py``). The checks below are
    what the layout needs: whole lanes, whole sublanes."""
    if len(x.shape) != 2 or len(w_in.shape) != 3 or len(w_out.shape) != 3:
        return "not x [rows, d], w_in [n, d, 2f], w_out [n, f, d]"
    (rows, d), (n, f, _) = x.shape, w_out.shape
    if w_in.shape != (n, d, 2 * f) or w_out.shape != (n, f, d):
        return (f"w_in {tuple(w_in.shape)} and w_out {tuple(w_out.shape)} "
                f"are not [n, {d}, 2f] and [n, f, {d}]")
    if jnp.dtype(x.dtype) not in (jnp.dtype(jnp.bfloat16),
                                  jnp.dtype(jnp.float32)):
        return f"dtype {x.dtype} is neither bfloat16 nor float32"
    if d % 128:
        return f"model width {d} is not a multiple of 128 lanes"
    if f % 128:
        return f"expert width {f} is not a multiple of 128 lanes"
    if tile % 8 or rows % tile:
        return (f"a tile of {tile} rows is no multiple of 8 sublanes, or "
                f"{rows} rows are no whole tiles")
    return None


def expert_matmul_form(x, w_in, w_out, tile: int) -> str:
    """Which form :func:`expert_tiles` builds in auto mode for these
    arguments (arrays or shapes): ``"grouped_kernel"`` or ``"tile_loop"``."""
    if jax.default_backend() != "tpu" or _pallas_reject(x, w_in, w_out, tile):
        return "tile_loop"
    return "grouped_kernel"


@functools.lru_cache(maxsize=256)
def _log_reference_choice(x_shape, w_in_shape, dtype, why: str) -> None:
    log.warning("expert_tiles%s/%s %s runs the XLA tile loop, not the "
                "Pallas kernel: %s", x_shape, w_in_shape, dtype, why)


def expert_tiles(x, tile_expert, n_live, w_in, w_out, tile: int,
                 force: str | None = None):
    """``out [rows, d]`` float32: every ``tile`` rows of ``x [rows, d]``
    through the gated MLP of the expert ``tile_expert [rows // tile]``
    names (``w_in [n, d, 2f]``, ``w_out [n, f, d]``), for the first
    ``n_live`` tiles (a traced scalar); the rows of every other tile come
    out ZERO. Weights wider than ``x`` are read in ``x``'s dtype.

    ``force``: None (auto: the kernel on a TPU for shapes it takes, else
    the tile loop), "pallas" (always the kernel: Mosaic on a TPU, the
    Pallas interpreter elsewhere, which is how the CPU tests run it) or
    "reference". The kernel's one instruction is named
    ``nns_expert_tiles``."""
    on_tpu = jax.default_backend() == "tpu"
    why_not = _pallas_reject(x, w_in, w_out, tile)
    if force == "pallas":
        if why_not:
            raise ValueError(f"expert_tiles: {x.shape} through "
                             f"{w_in.shape}: {why_not}")
    elif force == "reference" or not on_tpu or why_not:
        if force is None and on_tpu:
            _log_reference_choice(tuple(x.shape), tuple(w_in.shape),
                                  str(x.dtype), why_not)
        return expert_tiles_reference(x, tile_expert, n_live, w_in, w_out,
                                      tile)
    vmem = pltpu.get_tpu_info().vmem_capacity_bytes if on_tpu else VMEM_BYTES
    f_chunk, limit = expert_blocks(x.shape[1], w_out.shape[1], tile, x.dtype,
                                   vmem)
    return _expert_tiles(x, tile_expert, n_live, w_in.astype(x.dtype),
                         w_out.astype(x.dtype), tile=tile, f_chunk=f_chunk,
                         vmem_limit_bytes=limit, interpret=not on_tpu)
