"""The recurrent mixers' lane-state update — one step of every lane's
state of one layer, in place in the state arena.

``models/hybrid.py`` keeps one slot a decode LANE and recurrent layer in
``arena [layers, lanes, heads, rows, cols]`` (float32 in both published
configurations), a carry of the K-step scan. A step of a layer reads each
live lane's ``[heads, rows, cols]``, decays it, adds a rank-one write and
reduces the new state to the mixer's output. As plain ``jax.numpy``
(:func:`lane_state_reference`) XLA schedules that as two or three passes
over the layer: a select-update fusion that reads and writes it, then a
second read for the output. The kernel here is one pass: a Pallas grid of
(lane, head block) over the WHOLE arena, aliased to its output, the layer a
static block index; each tile is fetched once, updated in VMEM, its output
reduced from the tile in hand, and written back once. An empty lane's tile
is written back as read, bit for bit, and its output is zero.

Three rules share the grid, the aliasing, the block rule, the reject rule
and the name ``nns_lane_state``; each brings a reference (the tests'
oracle, and the form off a TPU) and a kernel body of the same arithmetic,
float32 elementwise with float32 sums, nothing narrowed:

``"mamba2"`` (tile ``[head_dim, state]``): ``S <- S exp(dt A) + (x dt) (x)
B``, ``y = S . C`` — operands ``(x [b, h, p], step [b, h], a [h], bm [b, n],
cm [b, n])``.

``"gated_delta"`` (tile ``[key, value]``): ``models/gated_delta.py
gated_delta_step``'s own order — operands ``(q [b, h, k], k [b, h, k], v
[b, h, v], g [b, h], beta [b, h])``.

``"mamba1"`` (tile STATE-MAJOR ``[state, channels]``, the channels of
``models/sambay.py``'s selective scan cut into ``heads`` blocks so that a
tile's columns are whole lanes): the decay is a matrix, not a scalar a head:
``S <- S exp(dt[c] A[n, c]) + B[n] (x dt)[c]``, ``y[c] = sum_n S[n, c] C[n]``
— operands ``(x [b, h, c], step [b, h, c], a [h, n, c], bm [b, n], cm [b,
n])``. ``a`` is a PARAMETER, the same block for every lane: it has no lane
axis, its block index does not move from lane to lane and the pipeline
fetches it once a head block. The sum over ``n`` runs down the sublanes.

Vectors that multiply a tile along its rows come in transposed, ``[rows,
heads]``, so that a head's column is a static lane slice; the decay, a
scalar a head that multiplies the whole tile, comes as such a column too,
and the scalars that multiply a row (the write strength, ``k . q``) as one
``[8, heads]`` tile a lane (all made outside the kernel, from arrays a
hundredth of the state and less). Only the live lanes ride in by scalar
prefetch: 32-48 KB of float32 scalars there left the chip unable to
finish a LATER program that prefetches scalars (PERF.md, PR 36).

``update`` auto-selects like ``expert_tiles``: the kernel on a TPU for
shapes it takes, the reference elsewhere.
"""

from __future__ import annotations

import functools
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from nnstreamer_tpu.log import get_logger
from nnstreamer_tpu.models.gated_delta import gated_delta_step
from nnstreamer_tpu.ops.grouped_matmul import VMEM_BYTES

log = get_logger("lane-state")

MAMBA2, GATED_DELTA, MAMBA1 = "mamba2", "gated_delta", "mamba1"


class LaneSlot(NamedTuple):
    """A handle on one layer of the state arena: what a mixer is given,
    and hands back updated."""
    arena: jax.Array    # [layers, lanes, heads, rows, cols]
    layer: int


def mamba2_step(state, x, step, a, bm, cm):
    """One token of the state-space recurrence: ``state [b, heads,
    head_dim, n]``, ``x [b, heads, head_dim]``, ``step [b, heads]``, ``a
    [heads]`` (negative), ``bm``/``cm [b, n]``, all float32 → ``(y [b,
    heads, head_dim], state)``."""
    kept = state * jnp.exp(step * a)[..., None, None]
    new = kept + (x * step[..., None])[..., None] * bm[:, None, None, :]
    return jnp.einsum("bhpn,bn->bhp", new, cm), new


def mamba1_step(state, x, step, a, bm, cm):
    """One token of the selective scan, state-major: ``state [b, heads, n,
    c]``, ``x``/``step [b, heads, c]``, ``a [heads, n, c]`` (negative),
    ``bm``/``cm [b, n]``, all float32 → ``(y [b, heads, c], state)``."""
    kept = state * jnp.exp(step[:, :, None, :] * a)
    new = kept + bm[:, None, :, None] * (x * step)[:, :, None, :]
    return jnp.sum(new * cm[:, None, :, None], axis=2), new


def lane_state_reference(rule: str, slot: LaneSlot, live, operands):
    """The plain form: the layer's slice through the rule's step, an empty
    lane reading zeros and keeping what its slot holds, then set back."""
    states, layer = slot    # every layer's; not the pool's block arena
    state = states[layer]
    lane = live[:, None, None, None]
    out, new = _RULES[rule].step(
        jnp.where(lane, state.astype(jnp.float32), 0.0), *operands)
    new = jnp.where(lane, new.astype(state.dtype), state)
    return out, LaneSlot(states.at[layer].set(new), layer)


def head_block(heads: int, tile_bytes: int,
               vmem_bytes: int = VMEM_BYTES) -> tuple:
    """``(hb, vmem_limit_bytes)``: the rule for the kernel's blocks.

    A grid step holds ``hb`` heads' tiles four times over (in and out,
    each with the pipeline's two buffers). ``hb`` is the largest divisor
    of ``heads``, a multiple of 8 sublanes or all of them, for which that
    is at most a THIRTY-SECOND of the chip's VMEM: blocks of 1 MiB on a
    v5e, 32 and 16 heads of the two published configurations. A block of
    a megabyte reaches the rate a read-and-write stream gets (0.850 ms a
    layer of 537 MB at 32 heads a step, 0.844 at a whole lane of 128,
    0.947 at 16: PERF.md, PR 36), and the loop over a block's heads is
    unrolled: a whole lane a step made the decode program's text four
    times as long and its LOAD 5 s longer (``setup_s`` +10 %). The limit
    handed to Mosaic is what the step holds and as much again for its
    vectors."""
    fits = [hb for hb in range(heads, 0, -1)
            if heads % hb == 0 and (hb % 8 == 0 or hb == heads)]
    hb = next((b for b in fits if 4 * b * tile_bytes <= vmem_bytes // 32),
              fits[-1])
    return hb, min(vmem_bytes, 8 * hb * tile_bytes + (4 << 20))


def _transposed(x, hb: int):
    """``x [lanes, heads, r]`` as ``[lanes, heads // hb, r, hb]``: a head
    block's vectors side by side, one a lane of the tile."""
    lanes, heads, r = x.shape
    return jnp.swapaxes(x.reshape(lanes, heads // hb, hb, r), -1, -2)


def _per_head(scalars, hb: int):
    """Scalars ``[lanes, heads]`` each as one ``[lanes, heads // hb, 8,
    hb]``: scalar ``i`` of a block's head ``h`` at ``[i, h]`` (a tile of 8
    sublanes, the rows past the scalars zero)."""
    lanes, heads = scalars[0].shape
    rows = jnp.stack(list(scalars) + [jnp.zeros_like(scalars[0])]
                     * (8 - len(scalars)), axis=1)
    return jnp.swapaxes(rows.reshape(lanes, 8, heads // hb, hb), 1, 2)


def _decay_columns(decay, rows: int, hb: int):
    """A head's decay ``[lanes, heads]`` down a column of its tile's
    ``rows``, transposed: Mosaic broadcasts a value along sublanes OR
    lanes, so a scalar that multiplies a whole tile comes as a column."""
    return _transposed(jnp.broadcast_to(decay[..., None],
                                        decay.shape + (rows,)), hb)


def _mamba2_pack(operands, hb: int):
    x, step, a, bm, cm = operands
    return [_decay_columns(jnp.exp(step * a), x.shape[-1], hb),
            _transposed(x * step[..., None], hb), bm[:, None], cm[:, None]]


def _mamba2_body(vectors, s_in, s_out, yt, hb: int):
    """``hb`` heads of one lane: the decay and xdt, transposed ``[p, hb]``,
    B and C ``[1, n]``; the tiles in and out ``[hb, p, n]``; y^T ``[p,
    hb]``. ``y`` sums each row of ``S * C`` along the lanes: the MXU does
    that sum, every float32 product through a matrix of ones at HIGHEST
    precision (the three bfloat16 parts of a float32 times 1.0 are exact,
    the accumulator is float32), where a reduce a row would put 65,536
    lane rotations a layer on the XLU (0.91 against 0.84 ms a layer of the
    granite cell; a copy alone takes 0.84: PERF.md, PR 36)."""
    dect, xt, bm, cm = vectors
    b_row, c_row = bm[...], cm[...]
    ones = jnp.ones((c_row.shape[1], 128), jnp.float32)
    for h in range(hb):
        new = s_in[h] * dect[:, h:h + 1] + xt[:, h:h + 1] * b_row
        s_out[h] = new
        yt[:, h:h + 1] = jnp.dot(
            new * c_row, ones, precision=lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)[:, :1]


def _delta_pack(operands, hb: int):
    q, k, v, g, beta = operands
    lanes, heads, cols = v.shape
    return [_decay_columns(jnp.exp(g), k.shape[-1], hb),
            _per_head([beta, jnp.sum(k * q, axis=-1)], hb),
            _transposed(k, hb), _transposed(q, hb),
            v.reshape(lanes, heads // hb, hb, cols)]


def _delta_body(vectors, s_in, s_out, o, hb: int):
    """``hb`` heads of one lane: the decay, k and q, transposed ``[key,
    hb]``, beta and ``k . q`` a head ``[8, hb]``, v ``[hb, value]``; the
    tiles; o ``[hb, value]``."""
    dect, per_head, kt, qt, v = vectors
    for h in range(hb):
        beta, kq = (jnp.broadcast_to(per_head[i:i + 1, h:h + 1],
                                     (1, v.shape[1])) for i in range(2))
        kept = s_in[h] * dect[:, h:h + 1]
        k_col = kt[:, h:h + 1]
        read = jnp.sum(kept * k_col, axis=0, keepdims=True)
        seen = jnp.sum(kept * qt[:, h:h + 1], axis=0, keepdims=True)
        d = beta * (v[h:h + 1, :] - read)
        o[h:h + 1, :] = seen + kq * d
        s_out[h] = kept + k_col * d


def _mamba1_pack(operands, hb: int):
    x, step, a, bm, cm = operands
    lanes, heads, cols = x.shape

    def rows(v):
        return v.reshape(lanes, heads // hb, hb, cols)

    return [rows(step), rows(x * step), bm[:, :, None], cm[:, :, None],
            a.reshape((heads // hb, hb) + a.shape[1:])]


def _mamba1_body(vectors, s_in, s_out, y, hb: int):
    """``hb`` channel blocks of one lane: the step and ``x dt`` a row each
    ``[hb, c]``, B and C a column ``[n, 1]``, ``a [hb, n, c]`` the
    parameter; the tiles ``[hb, n, c]``; y ``[hb, c]``."""
    step, xdt, bm, cm, a = vectors
    b_col, c_col = bm[...], cm[...]
    for h in range(hb):
        new = s_in[h] * jnp.exp(step[h:h + 1, :] * a[h]) \
            + b_col * xdt[h:h + 1, :]
        s_out[h] = new
        y[h:h + 1, :] = jnp.sum(new * c_col, axis=0, keepdims=True)


class _Rule(NamedTuple):
    step: Callable      # the reference: (state, *operands) -> (out, state)
    pack: Callable      # (operands, hb) -> the kernel's vectors
    body: Callable      # the kernel's: hb heads of one lane
    out_by_rows: bool   # a head's output lies along its tile's rows
    shared: int = 0     # the pack's last vectors with no lane axis


_RULES = {
    MAMBA2: _Rule(mamba2_step, _mamba2_pack, _mamba2_body, True),
    GATED_DELTA: _Rule(gated_delta_step, _delta_pack, _delta_body, False),
    MAMBA1: _Rule(mamba1_step, _mamba1_pack, _mamba1_body, False, shared=1),
}


def _kernel(live, *refs, body, hb: int):
    *vectors, s_in, out, s_out = refs

    @pl.when(live[pl.program_id(0)] == 0)
    def _empty():
        s_out[...] = s_in[...]
        out[...] = jnp.zeros_like(out)

    @pl.when(live[pl.program_id(0)] != 0)
    def _live():
        body(vectors, s_in, s_out, out, hb)


@functools.partial(jax.jit, static_argnames=(
    "rule", "layer", "hb", "vmem_limit_bytes", "interpret"))
def _lane_state(arena, live, vectors, *, rule: str, layer: int, hb: int,
                vmem_limit_bytes: int, interpret: bool):
    """Kernel entry. ``vectors``: ``[lanes, heads // hb, r, c]`` (a block a
    grid step) or ``[lanes, r, c]`` (one block a lane); the rule's last
    ``shared`` of them ``[heads // hb, ...]``, the same for every lane.
    Returns ``(out, arena)``, ``out [lanes, heads // hb, r, c]`` as the
    rule's body writes it."""
    _, lanes, heads, rows, cols = arena.shape
    out_shape = (lanes, heads // hb) + (
        (rows, hb) if _RULES[rule].out_by_rows else (hb, cols))
    own = len(vectors) - _RULES[rule].shared

    def blocked(shape):
        if len(shape) == 3:
            return pl.BlockSpec((None,) + tuple(shape[1:]),
                                lambda i, j, *_: (i, 0, 0))
        return pl.BlockSpec((None, None) + tuple(shape[2:]),
                            lambda i, j, *_: (i, j, 0, 0))

    def shared(shape):
        zeros = (0,) * (len(shape) - 1)
        return pl.BlockSpec((None,) + tuple(shape[1:]),
                            lambda i, j, *_: (j,) + zeros)

    tile = pl.BlockSpec((None, None, hb, rows, cols),
                        lambda i, j, *_: (layer, i, j, 0, 0))
    out, new = pl.pallas_call(
        functools.partial(_kernel, body=_RULES[rule].body, hb=hb),
        out_shape=(jax.ShapeDtypeStruct(out_shape, jnp.float32),
                   jax.ShapeDtypeStruct(arena.shape, arena.dtype)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,          # the live lanes
            grid=(lanes, heads // hb),
            in_specs=[blocked(v.shape) for v in vectors[:own]]
            + [shared(v.shape) for v in vectors[own:]] + [tile],
            out_specs=(blocked(out_shape), tile)),
        input_output_aliases={1 + len(vectors): 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=vmem_limit_bytes),
        interpret=interpret,
        name="nns_lane_state",
    )(live.astype(jnp.int32), *vectors, arena)
    return out, new


def _pallas_reject(rule: str, arena, operands=()) -> str | None:
    """Why this arena cannot go to the kernel, or None when it can.

    What was proved: Mosaic (libtpu 0.0.34, for a TPU v5e) compiles the
    bodies at the cells' shapes, ``f32[9, 64, 128, 64, 128]``, ``f32[3,
    128, 32, 128, 128]`` and state-major ``f32[9, 64, 1, 16, 5120]``
    (``tests/test_paged_attention.py``), and the chip held the first two to
    the reference (``chip_smoke.py``) and the third through its cell's
    check (PERF.md, PR 39); the
    interpreter holds cut-down twins (``tests/test_lane_state.py``). The
    checks below are what the layout needs: a float32 tile of whole
    sublanes and whole lanes."""
    if rule not in _RULES:
        return f"no rule {rule!r}"
    if len(arena.shape) != 5:
        return "not an arena [layers, lanes, heads, rows, cols]"
    if jnp.dtype(arena.dtype) != jnp.dtype(jnp.float32):
        return f"a state of {arena.dtype} is not float32"
    if any(jnp.dtype(x.dtype) != jnp.dtype(jnp.float32) for x in operands):
        return "an operand is not float32"
    rows, cols = arena.shape[3:]
    if rows % 8:
        return f"a tile of {rows} rows is no multiple of 8 sublanes"
    if cols % 128:
        return f"a tile of {cols} columns is no multiple of 128 lanes"
    return None


def state_update_form(rule: str, arena) -> str:
    """Which form :func:`update` builds in auto mode for this arena (an
    array or a shape): ``"lane_kernel"`` or ``"reference"``."""
    if jax.default_backend() != "tpu" or _pallas_reject(rule, arena):
        return "reference"
    return "lane_kernel"


@functools.lru_cache(maxsize=256)
def _log_reference_choice(rule: str, shape, dtype, why: str) -> None:
    log.warning("lane_state %s %s %s runs the XLA reference, not the "
                "Pallas kernel: %s", rule, shape, dtype, why)


def update(rule: str, slot: LaneSlot, live, operands,
           force: str | None = None):
    """One token of every lane of layer ``slot.layer``: ``(out [lanes,
    heads, rows or cols], LaneSlot)``, the arena updated in place where it
    is a buffer the caller gives up (a scan's carry, a donated argument).
    ``live [lanes]`` bool: an empty lane keeps its slot bit for bit (its
    ``out`` is nothing anyone reads). ``operands``: the rule's, as the
    module says.

    ``force``: None (auto: the kernel on a TPU for arenas it takes, else
    the reference), "pallas" (always the kernel: Mosaic on a TPU, the
    Pallas interpreter elsewhere, which is how the CPU tests run it) or
    "reference". The kernel's one instruction is named
    ``nns_lane_state``."""
    arena, layer = slot
    on_tpu = jax.default_backend() == "tpu"
    why_not = _pallas_reject(rule, arena, operands)
    if force == "pallas":
        if why_not:
            raise ValueError(f"lane_state.update: {rule} over "
                             f"{arena.shape} {arena.dtype}: {why_not}")
    elif force == "reference" or not on_tpu or why_not:
        if force is None and on_tpu:
            _log_reference_choice(rule, tuple(arena.shape), str(arena.dtype),
                                  why_not)
        return lane_state_reference(rule, slot, live, operands)
    _, lanes, heads, rows, cols = arena.shape
    vmem = pltpu.get_tpu_info().vmem_capacity_bytes if on_tpu else VMEM_BYTES
    hb, limit = head_block(heads, rows * cols * 4, vmem)
    out, new = _lane_state(
        arena, live, _RULES[rule].pack(operands, hb), rule=rule,
        layer=int(layer), hb=hb, vmem_limit_bytes=limit,
        interpret=not on_tpu)
    if _RULES[rule].out_by_rows:
        out = jnp.swapaxes(out, -1, -2)
    return out.reshape(lanes, heads, -1), LaneSlot(new, layer)
