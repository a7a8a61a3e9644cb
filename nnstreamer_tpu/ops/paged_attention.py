"""Paged decode attention — one query token a lane against the blocks its
table names, read where they lie in the pool's arena.

The gather form (``models/transformer.py`` ``_paged_gather`` +
``_attend_cache``) copies every lane's WHOLE block table out of the arena,
relays it out and attends over all ``MB * T`` slots under a mask, whatever
the lanes hold. The kernel here leaves the arena in HBM and walks each
lane's LIVE blocks only (``pos // T + 1`` of them): the grid is the lanes,
and inside a lane a loop whose trip count is the lane's own fetches a run
of ``chunk`` blocks at a time by async DMA, double-buffered, and folds it
into an online softmax. The block table, the layer and the positions ride
in by scalar prefetch.

A block half is ``[T, hk, dh]``, the head axis INSIDE the token axis, or
HEADS-MAJOR ``[hk, T, dh]`` where the codec that made the arena says so
(``heads_major``: fewer key-value heads than a tile's 8 rows;
``models/transformer.py`` ``kv_heads_major``). Which of the two is an
ARGUMENT, never read off the shape: ``[2, 16, dh]`` is both. Either way a
block's ``T * hk`` rows go to the MXU as they lie, with no transpose of
any block: ``q [hq, dh]`` against all of them gives ``[hq, T * hk]``
scores of which a query head keeps the columns of its own key-value head
(token-major ``col % hk == head // group``, heads-major ``(col % (T * hk))
// T == head // group``; the rest are masked like the slots past ``pos``,
and a column's slot is ``col // hk``, heads-major ``(col // (T * hk)) * T +
col % T``). The MXU does ``hk`` times the arithmetic a transpose would
save and has the room: decode attention is bound by the bytes. The flat
view ``[T * hk, dh]`` of a block half has to be a bitcast of the arena as
the chip tiles it, or XLA relays out the WHOLE arena every step: with
``(hk, dh)`` minor and ``hk`` = 2 it was no bitcast (two rows to a tile;
2.3 ms a step at 537 MB: PERF.md, PR 31), with ``(T, dh)`` minor it is,
as with 8 or 16 heads token-major.

The numeric contract is ``_attend_cache``'s: keys and values as stored,
float32 scores, float32 softmax, float32 probabilities x values, one
rounding at the end; only the order of the sums differs (blockwise).
bfloat16 x bfloat16 products are exact in float32, so the scores take one
MXU pass; the float32 probabilities go in as three bfloat16 parts whose
sum is the float32 value (8 + 8 + 8 mantissa bits), against values that
ARE bfloat16: no operand is narrowed.

A LATENT arena (``v_width``; ``serving/kvpool.py``, one part of ``[T,
width]`` a block) holds one row a token that every query head shares: the
key is the row at all its columns, the value the first ``v_width`` columns
OF THE SAME ROW, so one DMA a block serves both, every head keeps every
column (``hk`` = 1: the mask above has nothing to mask), and the output is
``[hq, v_width]`` a lane. The same kernel, built with ``v_width`` and under
the name ``nns_mla_paged_decode``; its chunk is ``LATENT_CHUNK_BLOCKS``
(a block is a fifth of a per-head block's bytes at 16 heads).

A WINDOW (``window``: lane ``i`` attends over slots ``max(0, pos - window
+ 1) .. pos``) gives the loop a lower bound beside ``pos``: it starts at
the block that holds the window's first slot, so what lies before it is
neither fetched nor waited for (its table entries may be sentinel: the
engine has given those blocks back), and the slots of that first block that
are too old are masked as the slots past ``pos`` are. The same kernel, under
the name ``nns_window_paged_decode``.

``paged_attention`` auto-selects like ``flash_attention``: the kernel on a
TPU for shapes it takes, the gather form elsewhere.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from nnstreamer_tpu.log import get_logger

log = get_logger("paged-attention")

_NEG_BIG = -1e30

#: blocks fetched and attended over at a time (one DMA a block, all in
#: flight together); what is past a lane's live blocks is neither fetched
#: nor waited for
CHUNK_BLOCKS = 8
#: the same for a latent arena: 32 blocks of 16 rows are 512 rows a matmul
#: and 1.3 MB of VMEM for both buffers at 576 columns
LATENT_CHUNK_BLOCKS = 32


def _split3(p):
    """float32 ``p`` as three bfloat16 parts that sum to it."""
    hi = p.astype(jnp.bfloat16)
    r = p - hi.astype(jnp.float32)
    mid = r.astype(jnp.bfloat16)
    lo = (r - mid.astype(jnp.float32)).astype(jnp.bfloat16)
    return hi, mid, lo


def _kernel(layer_ref, bt_ref, pos_ref, q_ref, pages_ref, o_ref,
            buf, sem, m_scr, l_scr, acc_scr, *, scale: float,
            block_tokens: int, kv_heads: int, chunk: int,
            v_width: int | None = None, heads_major: bool = False,
            window: int | None = None):
    lane = pl.program_id(0)
    layer = layer_ref[0]
    pos = pos_ref[lane]
    zero_block = pages_ref.shape[1] - 1
    hq, dh = q_ref.shape[1:]
    group = hq // kv_heads
    rows = chunk * block_tokens * kv_heads       # key rows of a chunk
    n_blocks = pos // block_tokens + 1           # the lane's live blocks
    if window is not None:       # of which the window's are read: from
        oldest = jnp.maximum(pos - (window - 1), 0)   # this slot's block
        first = oldest // block_tokens
    n_chunks = (n_blocks + chunk - 1) // chunk if window is None \
        else (n_blocks - first + chunk - 1) // chunk
    exact = lax.Precision.HIGHEST if q_ref.dtype == jnp.float32 else None

    @pl.when(lane == 0)
    def _clear():
        # rows no DMA of this call has filled are masked, and 0 x what
        # they hold must be 0: nothing a fresh buffer may hold is allowed
        buf[...] = jnp.zeros_like(buf)

    def copies(c, slot):
        """(live?, copy) of each block of chunk ``c`` into ``buf[slot]``."""
        for i in range(chunk):
            j = c * chunk + i if window is None else first + c * chunk + i
            blk = jnp.minimum(bt_ref[lane, jnp.minimum(j, bt_ref.shape[1] - 1)],
                              zero_block)
            yield j < n_blocks, pltpu.make_async_copy(
                pages_ref.at[layer, blk], buf.at[slot, i], sem.at[slot])

    def start(c, slot):
        for live, copy in copies(c, slot):
            pl.when(live)(copy.start)

    def wait(c, slot):
        for live, copy in copies(c, slot):
            pl.when(live)(copy.wait)

    m_scr[...] = jnp.full_like(m_scr, _NEG_BIG)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)
    start(0, 0)

    col = lax.broadcasted_iota(jnp.int32, (hq, rows), 1)
    head = lax.broadcasted_iota(jnp.int32, (hq, rows), 0)
    if heads_major:                              # a block: [hk, T] rows
        blk_rows = block_tokens * kv_heads
        own_head = lax.div(lax.rem(col, blk_rows), block_tokens) \
            == lax.div(head, group)
        slot_of = lax.div(col, blk_rows) * block_tokens \
            + lax.rem(col, block_tokens)
    else:                                        # a block: [T, hk] rows
        own_head = lax.rem(col, kv_heads) == lax.div(head, group)
        slot_of = lax.div(col, kv_heads)         # slot within the chunk

    def body(c, carry):
        slot = lax.rem(c, 2)

        @pl.when(c + 1 < n_chunks)
        def _prefetch():
            start(c + 1, 1 - slot)

        wait(c, slot)

        def scores(q, k):
            return lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                   precision=exact,
                                   preferred_element_type=jnp.float32)

        if v_width is None:
            v = buf[slot, :, 1].reshape(rows, dh)
            s = scores(q_ref[0], buf[slot, :, 0].reshape(rows, dh)) * scale
        else:
            # the row's first columns are the value AND the greater part
            # of the key: two products over whole lane tiles, not one
            # over a width that is no multiple of 128
            v = buf[slot, :, 0, :, :v_width].reshape(rows, v_width)
            rest = buf[slot, :, 0, :, v_width:].reshape(rows, dh - v_width)
            s = (scores(q_ref[0, :, :v_width], v)
                 + scores(q_ref[0, :, v_width:], rest)) * scale
        if window is None:
            seen = slot_of <= pos - c * (chunk * block_tokens)
        else:
            at = (first + c * chunk) * block_tokens + slot_of
            seen = (at <= pos) & (at >= oldest)
        s = jnp.where(own_head & seen, s, _NEG_BIG)
        m_prev = m_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_new = l_scr[:, :1] * corr + jnp.sum(p, axis=-1, keepdims=True)
        if v.dtype == jnp.bfloat16:
            pv = sum(jnp.dot(part, v, preferred_element_type=jnp.float32)
                     for part in _split3(p))
        else:
            pv = jnp.dot(p, v.astype(jnp.float32), precision=exact,
                         preferred_element_type=jnp.float32)
        acc_scr[...] = acc_scr[...] * corr + pv
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)
        return carry

    lax.fori_loop(0, n_chunks, body, 0)
    o_ref[0] = (acc_scr[...] / l_scr[:, :1]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "chunk", "interpret",
                                             "v_width", "heads_major",
                                             "window"))
def _paged_decode(q, pages, layer, bt, pos_c, scale: float, chunk: int,
                  interpret: bool, v_width: int | None = None,
                  heads_major: bool = False, window: int | None = None):
    """Kernel entry: ``q [b, hq, dh]``, the arena leaf whole."""
    b, hq, dh = q.shape
    if v_width is None:
        L, ntot, two, T, hk, _ = pages.shape
        if heads_major:
            T, hk = hk, T
        # a block half's [T, hk] (heads-major [hk, T]) rows as one axis:
        # the same bytes in the same order, so the blocks go to the MXU
        # as the DMA lands them
        flat = pages.reshape(L, ntot, two, T * hk, dh)
    else:
        (L, ntot, two, T, _), hk, flat = pages.shape, 1, pages
    dv = dh if v_width is None else v_width
    kern = functools.partial(_kernel, scale=scale, block_tokens=T,
                             kv_heads=hk, chunk=chunk, v_width=v_width,
                             heads_major=heads_major, window=window)
    return pl.pallas_call(
        kern,
        out_shape=jax.ShapeDtypeStruct((b, hq, dv), q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,      # layer, block table, positions
            grid=(b,),
            in_specs=[pl.BlockSpec((1, hq, dh), lambda i, *_: (i, 0, 0)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, hq, dv), lambda i, *_: (i, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, chunk, two, T * hk, dh), pages.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.VMEM((hq, 128), jnp.float32),   # running max
                pltpu.VMEM((hq, 128), jnp.float32),   # running sum
                pltpu.VMEM((hq, dv), jnp.float32),    # output accumulator
            ]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="nns_window_paged_decode" if window is not None
        else "nns_paged_decode" if v_width is None
        else "nns_mla_paged_decode",
    )(jnp.asarray(layer, jnp.int32).reshape(1), bt.astype(jnp.int32),
      pos_c.astype(jnp.int32), q, flat)


def _pallas_reject(q, pages, bt, v_width: int | None = None,
                   heads_major: bool = False,
                   window: int | None = None) -> str | None:
    """Why these shapes cannot go to the kernel, or None when they can.
    ``heads_major`` says which of a six-axis arena's axes 3 and 4 is the
    block's tokens and which its heads; it is never guessed.

    What was proved: Mosaic (libtpu 0.0.34, for a TPU v5e) compiles the
    kernel for bfloat16 arenas of 16 tokens a block, TOKEN-MAJOR at head
    dim 128 with 16 query over 16 key-value heads and 32 over 8 (groups
    of 1 and 4), HEADS-MAJOR at head dim 256 with 16 over 2 (a group of
    8; ``tests/test_paged_attention.py``), and the chip served all three
    from it (16 over 2 token-major too, until PR 34: it compiled and
    served, behind a relayout of the whole arena a step); the interpreter
    holds the same shapes and heads-major 1, 2 and 4 key-value heads at
    head dims 128 and 256, float32 too, to the gather form. The checks
    below are what the layout
    needs (whole lanes, whole sublanes), which is wider than what was
    proved: a head dim of 384 or a group of 16 would pass them untried.
    A latent arena (``v_width``) was compiled and served from at 16 heads
    over rows of 576 columns, the value the first 512
    (``tests/test_paged_attention.py``; PERF.md, PR 33). With a ``window``
    it was compiled and served from token-major at 48 query over 8
    key-value heads of 128 (PERF.md, PR 37); a window over a latent arena
    was never built. Since PR 39 the query rows need be whole FLOAT32
    sublanes only (8: the scores, the running max and sum and the
    accumulator are float32 ``[hq, ..]``), not whole sublanes of the
    arena's dtype: a bfloat16 query block of 40 rows, which is the whole
    array's extent, Mosaic takes, and both kernels were compiled and
    served from at 40 query rows over 10 key-value heads of 128,
    heads-major (a differential-attention pair entry: PERF.md, PR 39)."""
    if window is not None and v_width is not None:
        return "a window over a latent arena is not built"
    if not hasattr(pages, "shape") or \
            len(pages.shape) != (6 if v_width is None else 5):
        return "the arena is not one [L, NTOT, 2, T, h, dh] leaf (or " \
               "heads-major [L, NTOT, 2, h, T, dh]), nor with v_width " \
               "one [L, NTOT, 1, T, width]"
    if heads_major and v_width is not None:
        return "a latent arena has no heads to put first"
    b, one, hq, dh = q.shape
    T, hk = pages.shape[3], 1 if v_width is not None else pages.shape[4]
    if heads_major:
        T, hk = hk, T
    sublanes = 8 * 4 // jnp.dtype(pages.dtype).itemsize
    if v_width is not None and (dh != pages.shape[-1] or v_width % 128
                                or not 0 < v_width < dh):
        return (f"queries of {dh} against rows of {pages.shape[-1]} "
                f"columns, or a value width ({v_width}) that is no "
                f"multiple of 128 lanes inside the row")
    if one != 1:
        return f"{one} query tokens a lane, not 1"
    if q.dtype != pages.dtype:
        return f"query {q.dtype} against {pages.dtype} keys and values"
    if jnp.dtype(q.dtype) not in (jnp.dtype(jnp.bfloat16),
                                  jnp.dtype(jnp.float32)):
        return f"dtype {q.dtype} is neither bfloat16 nor float32"
    if dh % 128 and v_width is None:
        return f"head dim {dh} is not a multiple of 128 lanes"
    if hq % hk:
        return f"{hq} query heads are no multiple of {hk} key-value heads"
    if hq % 8 or (T * hk) % sublanes:
        return (f"{hq} query heads are no multiple of 8 float32 sublanes "
                f"(the scores' rows) or {T} x {hk} rows a block no "
                f"multiple of {sublanes} sublanes")
    if bt.shape[0] != b:
        return f"{bt.shape[0]} block tables for {b} lanes"
    return None


def paged_attention_form(q, pages, bt, v_width: int | None = None,
                         heads_major: bool = False,
                         window: int | None = None) -> str:
    """Which form :func:`paged_attention` builds in auto mode for these
    arguments (arrays or shapes): ``"paged_kernel"`` or ``"gather"``."""
    if jax.default_backend() != "tpu" or \
            _pallas_reject(q, pages, bt, v_width, heads_major, window):
        return "gather"
    return "paged_kernel"


@functools.lru_cache(maxsize=256)
def _log_reference_choice(q_shape, pages_shape, dtype, why: str) -> None:
    log.warning("paged_attention%s/%s %s runs the XLA gather form, not the "
                "Pallas kernel: %s", q_shape, pages_shape, dtype, why)


def _window_tables(pages, bt, pos_c, window: int, heads_major: bool):
    """The blocks a window can touch, out of each lane's table: ``(bt_w [b,
    nb], slots [b, nb * T])``, the table entries from the block of the
    window's first slot on and the position each gathered slot holds."""
    T = pages.shape[4 if heads_major else 3]
    nb = min(bt.shape[1], (window - 1) // T + 2)
    first = jnp.maximum(pos_c - (window - 1), 0) // T
    idx = jnp.minimum(first[:, None] + jnp.arange(nb), bt.shape[1] - 1)
    return jnp.take_along_axis(bt, idx, axis=1), \
        first[:, None] * T + jnp.arange(nb * T)


def _scope(window, scope):
    return scope or ("attend" if window is None else "attend_window")


def paged_attention_reference(q, pages, layer, bt, pos_c, scale=None,
                              v_width: int | None = None,
                              heads_major: bool = False,
                              window: int | None = None,
                              scope: str | None = None):
    """The gather form: every lane's whole table copied out of the arena
    (into ``[b, MB * T, hk, dh]`` whichever the arena's order),
    masked to ``slot <= pos_c`` and attended over by ``_attend_cache``
    (a latent arena's rows as one key-value head whose value is the
    row's first ``v_width`` columns). With a ``window``, the blocks the
    window can touch alone, masked to ``pos_c - window < slot <= pos_c``."""
    from nnstreamer_tpu.models.transformer import (
        _attend_cache,
        _paged_gather,
    )

    with jax.named_scope("kv_gather"):
        if window is None:
            g = _paged_gather(pages, layer, bt, heads_major)
            slots = jnp.arange(g.shape[2])
            mask = slots[None, None, None, :] <= pos_c[:, None, None, None]
        else:
            bt_w, slots = _window_tables(pages, bt, pos_c, window,
                                         heads_major)
            g = _paged_gather(pages, layer, bt_w, heads_major)
            mask = ((slots <= pos_c[:, None])
                    & (slots > pos_c[:, None] - window))[:, None, None, :]
        if v_width is None:
            ck, cv = g[:, 0], g[:, 1]
        else:
            ck = g[:, 0, :, None]
            cv = ck[..., :v_width]
    with jax.named_scope(_scope(window, scope)):
        return _attend_cache(q, ck, cv, mask, q.shape[-1], q.dtype,
                             scale=scale)


def paged_attention(q, pages, layer, bt, pos_c, scale: float | None = None,
                    force: str | None = None,
                    chunk_blocks: int | None = None,
                    v_width: int | None = None,
                    heads_major: bool = False,
                    window: int | None = None,
                    scope: str | None = None):
    """Decode attention of ``q [b, 1, hq, dh]`` over a paged cache.

    ``pages`` is the arena's value leaf WHOLE, ``[L, NTOT, 2, T, hk, dh]``
    or, with ``heads_major`` (the arena's codec says which: its
    ``heads_major``), ``[L, NTOT, 2, hk, T, dh]``
    (``serving/kvpool.py``), ``layer`` the layer to read (a Python int or
    a traced scalar), ``bt [b, MB]`` the block tables (entries ≥ NTOT-1
    read the zero block at NTOT-1) and ``pos_c [b]`` each lane's last
    written slot: lane ``i`` attends over slots ``0..pos_c[i]`` of the
    blocks ``bt[i]`` names. ``scale`` defaults to ``dh ** -0.5``; ``hk``
    fewer than ``hq`` is grouped-query attention (query head ``i`` reads
    key-value head ``i // (hq / hk)``). A lane whose table is all
    sentinel reads one zero block and comes out zero.

    ``v_width``: ``pages`` is a latent arena ``[L, NTOT, 1, T, width]``,
    ``q [b, 1, hq, width]``; every query head attends over the lane's rows
    at all their columns and the value is the rows' first ``v_width``
    columns: ``[b, 1, hq, v_width]`` comes back. ``chunk_blocks`` defaults
    to ``CHUNK_BLOCKS``, for a latent arena ``LATENT_CHUNK_BLOCKS``.

    ``window``: lane ``i`` attends over slots ``max(0, pos_c[i] - window
    + 1) .. pos_c[i]`` only, and the table entries of the blocks before the
    one that holds the first of them are never read.

    ``force``: None (auto: the kernel on a TPU for shapes it takes, else
    the gather form), "pallas" (always the kernel: Mosaic on a TPU, the
    Pallas interpreter elsewhere, which is how the CPU tests run it) or
    "reference". The kernel's instructions lie under the scope
    ``attend``; the gather form keeps ``kv_gather`` and ``attend``. With a
    ``window`` the scope is ``attend_window`` in both, so that a trace
    tells a model's two kinds of attention layer apart; ``scope`` names
    another in their place (a layer that reads another layer's blocks:
    ``attend_cross``).
    """
    on_tpu = jax.default_backend() == "tpu"
    if window is not None and window <= 0:
        raise ValueError(f"paged_attention: window ({window}) must be "
                         f"positive")
    why_not = _pallas_reject(q, pages, bt, v_width, heads_major, window)
    if force == "pallas":
        if why_not:
            raise ValueError(
                f"paged_attention: {q.shape} over {pages.shape}: {why_not}")
    elif force == "reference" or not on_tpu or why_not:
        if force is None and on_tpu:
            _log_reference_choice(tuple(q.shape), tuple(pages.shape),
                                  str(q.dtype), why_not)
        return paged_attention_reference(q, pages, layer, bt, pos_c, scale,
                                         v_width, heads_major, window,
                                         scope)
    scale = q.shape[-1] ** -0.5 if scale is None else scale
    if chunk_blocks is None:
        chunk_blocks = CHUNK_BLOCKS if v_width is None \
            else LATENT_CHUNK_BLOCKS
    with jax.named_scope(_scope(window, scope)):
        out = _paged_decode(
            q[:, 0], pages, layer, bt, pos_c, scale=float(scale),
            chunk=min(int(chunk_blocks), bt.shape[1]),
            interpret=not on_tpu, v_width=v_width, heads_major=heads_major,
            window=window)
    return out[:, None]
