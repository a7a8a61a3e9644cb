"""Flash attention — tiled online-softmax attention as a Pallas TPU kernel.

Grid is (batch, heads, q_blocks, k_blocks); the TPU executes the trailing
grid axis sequentially on one core, so the running max/sum/accumulator
live in VMEM scratch across k-steps while K/V stream through VMEM one
``block_k`` tile at a time — the [seq, seq] score matrix never exists and
VMEM holds O(block) state regardless of context length. bfloat16 in/out,
float32 softmax and accumulation.

The tiles come from the shapes (:func:`tile_plan`): what a k-step costs
beside its two products — the accumulator read, rescaled and written, the
running max and sum stored, two cross-lane reductions a row group, the
grid step itself — does not shrink with the tile, so a step should hold as
many scores as VMEM likes: up to 1024 x 1024, the whole prompt under that.
A live tile takes one of two steps: one that no edge of the mask crosses
(wholly under the diagonal and, with a window, wholly inside the band) is
multiplied, exponentiated and summed with no iota, compare or select; the
diagonal's tiles and those the band's lower edge crosses are masked first.
Queries and keys go to the MXU as they are stored (a product of two
bfloat16 values is exact in float32); the scale is applied to the float32
scores, once; the probabilities are float32 in the kernel's text (what
the MXU makes of a float32 operand at default precision is its own:
on a v5e it rounds it to bfloat16, one pass — PERF.md, PR 38).

The causally dead k-tiles of a q-tile are skipped with predicated
execution and not fetched: the steps past a q-tile's last live tile name
that tile again, and equal consecutive block indices move nothing.

``window``: query ``i`` sees key ``j`` iff ``0 <= i - j < window`` (a band
under the diagonal). The k axis of the grid then has only as many steps as
a q-tile's band can touch, and step ``ik`` of q-tile ``iq`` maps to the
band's ``ik``-th k-tile: the tiles before the band are neither fetched nor
multiplied. The kernel is then called ``nns_band_flash_prefill``.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from nnstreamer_tpu.log import get_logger

log = get_logger("flash-attention")

#: scores below this act as -inf without producing exp() NaNs in fully
#: masked tiles
_NEG_BIG = -1e30


def attention_reference(q, k, v, causal: bool = True, scale=None,
                        window: int | None = None):
    """Plain XLA attention, [batch, seq, heads, dim] layout; fp32 softmax.

    The canonical single-device reference — parallel.ring re-exports this
    for its unsharded path. ``scale`` defaults to ``dim ** -0.5``; ``k``
    and ``v`` may have fewer heads than ``q`` (grouped queries: query head
    ``i`` reads key-value head ``i // group``). ``window``: query ``i``
    sees key ``j`` iff ``0 <= i - j < window`` (causal only).
    """
    _check_window(window, causal)
    scale = q.shape[-1] ** -0.5 if scale is None else scale
    group = q.shape[2] // k.shape[2]
    if group > 1:
        k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        qi = jnp.arange(q.shape[1])[:, None]
        ki = jnp.arange(k.shape[1])[None, :]
        seen = qi >= ki if window is None \
            else (qi >= ki) & (qi - ki < window)
        s = jnp.where(seen, s, _NEG_BIG)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    return o.astype(q.dtype)


def _check_window(window, causal) -> None:
    if window is not None and (window <= 0 or not causal):
        raise ValueError(f"attention: window ({window}) must be positive "
                         f"and goes with causal=True")


def _live_tiles(iq, block_q: int, block_k: int, window: int | None,
                maximum=jnp.maximum):
    """The first and the last k-tile that q-tile ``iq`` sees a key of: from
    the first tile, or the one its band starts in, to the diagonal's
    (``maximum``: ``max`` where ``iq`` is a plain integer)."""
    last = ((iq + 1) * block_q - 1) // block_k
    if window is None:
        return 0, last
    return maximum(iq * block_q - (window - 1), 0) // block_k, last


def _edge_crosses(q0, k0, block_q: int, block_k: int, window: int | None):
    """Whether the tile of queries ``q0 …`` and keys ``k0 …`` holds a pair
    the mask hides: a key after its query (the diagonal crosses it) or,
    under a window, one that far behind it (the band's lower edge does).
    A live tile that no edge crosses is seen whole and takes the step
    without a mask. Integers and traced values alike."""
    crosses = k0 + block_k - 1 > q0
    if window is not None:
        crosses |= q0 + block_q - 1 - k0 >= window
    return crosses


#: the most rows and the most keys of a tile the plan asks for. Measured on
#: a v5e (PERF.md, PR 38: every cell's shapes, tiles of 256 to 2048): a
#: 1024 x 1024 step costs 4.1 us where its scores in 256 x 256 steps cost
#: 16 x 1.04 us; wider keys (1536, 2048) win nothing more and cost a band
#: its edges.
_TILE = 1024
#: what the plan lets :func:`_vmem_bytes` reach. A Mosaic kernel may use
#: 16 MiB of VMEM on a v5e unless it asks for more, and this one does not
#: ask: past 1024 x 1024 there is nothing to win. The estimate is within
#: an eighth of what the compiler said it needed where it refused (16.48 MB
#: at 1024 x 1024 over float32 heads of 256, estimate 16.0), so the plan
#: keeps an eighth under the limit.
_VMEM_BUDGET = 14 << 20


def _vmem_bytes(block_q: int, block_k: int, d: int, dv: int,
                size: int) -> int:
    """What a step holds in VMEM, roughly: the four blocks double-buffered,
    the three scratch arrays, the float32 score tile, and the second
    product's result beside the rescaled accumulator."""
    blocks = 2 * size * (block_q + block_k) * (d + dv)
    scratch = 4 * block_q * (2 * 128 + dv)
    return blocks + scratch + 4 * block_q * block_k + 8 * block_q * dv


class TilePlan(NamedTuple):
    """How :func:`flash_attention` tiles one shape. ``masked + unmasked``
    are the live steps of one head's grid, ``q_tiles * k_steps`` all of
    them: the rest do nothing and fetch nothing."""
    block_q: int
    block_k: int
    q_tiles: int
    k_steps: int      # the k axis of the grid: steps a q-tile takes
    masked: int       # live steps an edge of the mask crosses
    unmasked: int     # live steps that need no mask

    @property
    def dead(self) -> int:
        return self.q_tiles * self.k_steps - self.masked - self.unmasked


def _fit(s: int, want: int) -> int:
    """The largest block of whole 128-row groups, at most ``want`` rows,
    that tiles ``s``; all of ``s`` when it is no longer than ``want``.
    Where none does the answer is ``want``, which :func:`_pallas_reject`
    turns down: such a sequence runs the reference, as it always did."""
    if s <= want:
        return s
    return next((block for block in range(want, 0, -128) if s % block == 0),
                want)


def _k_steps(sk: int, block_q: int, block_k: int, window: int | None) -> int:
    """The k axis of the grid: every k-tile, or under a window the most a
    q-tile's band can touch."""
    nk = sk // block_k
    if window is not None:
        nk = min(nk, (window + block_q - 2) // block_k + 2)
    return nk


@functools.lru_cache(maxsize=256)
def tile_plan(sq: int, sk: int, d: int, dv: int, window: int | None = None,
              dtype=jnp.bfloat16, block_q: int | None = None,
              block_k: int | None = None, causal: bool = True) -> TilePlan:
    """The tiles for these shapes and what the grid does with them, from
    the static sizes alone. ``block_q`` / ``block_k``: the caller's own
    tiles, counted the same way. The rule (one for the band and the full
    triangle and every head width: the sweep found no shape that wants
    another): both blocks the largest divisor of the sequence up to
    ``_TILE``, the rows halved while :func:`_vmem_bytes` is over
    ``_VMEM_BUDGET`` (float32 heads of 256: 512 rows)."""
    want_q = _TILE
    while want_q > 128 and _vmem_bytes(
            want_q, _TILE, d, dv, jnp.dtype(dtype).itemsize) > _VMEM_BUDGET:
        want_q //= 2
    block_q = _fit(sq, want_q) if block_q is None else min(block_q, sq)
    block_k = _fit(sk, _TILE) if block_k is None else min(block_k, sk)
    q_tiles, k_steps = sq // block_q, _k_steps(sk, block_q, block_k, window)
    if not causal:
        return TilePlan(block_q, block_k, q_tiles, k_steps, 0,
                        q_tiles * k_steps)
    masked = unmasked = 0
    for iq in range(q_tiles):
        first, last = _live_tiles(iq, block_q, block_k, window, max)
        for kt in range(first, min(last, first + k_steps - 1) + 1):
            crosses = _edge_crosses(iq * block_q, kt * block_k, block_q,
                                    block_k, window)
            masked += crosses
            unmasked += not crosses
    return TilePlan(block_q, block_k, q_tiles, k_steps, masked, unmasked)


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            causal: bool, scale: float, block_q: int, block_k: int,
            window: int | None = None):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)
    # step ki reads k-tile kt; past a q-tile's last live tile the index
    # map names that tile again and the step does nothing
    first, last = _live_tiles(qi, block_q, block_k, window)
    kt = first + ki
    live = kt <= last if causal else True
    crosses = _edge_crosses(qi * block_q, kt * block_k, block_q, block_k,
                            window) if causal else False

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _NEG_BIG)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    def _step(masked: bool):
        # queries and keys go to the MXU as stored: a product of two
        # bfloat16 values is exact in float32, so casting them first buys
        # passes and no digits; the scale is applied to the float32 scores
        s = lax.dot_general(q_ref[0, 0], k_ref[0, 0],
                            (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
        if masked:
            # i - j of the tile's corner; the rest is a constant of the shape
            behind = (qi * block_q - kt * block_k) + (
                lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
                - lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1))
            seen = behind >= 0 if window is None \
                else (behind >= 0) & (behind < window)
            s = jnp.where(seen, s, _NEG_BIG)
        m_prev = m_scr[:, :1]                              # [bq, 1]
        l_prev = l_scr[:, :1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
        # the probabilities stay float32 into the second product
        acc_scr[:] = acc_scr[:] * corr + lax.dot_general(
            p, v_ref[0, 0].astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    if causal:
        pl.when(live & crosses)(functools.partial(_step, True))
        pl.when(live & ~crosses)(functools.partial(_step, False))
    else:
        _step(False)

    @pl.when(ki == nk - 1)
    def _finish():
        l = jnp.maximum(l_scr[:, :1], 1e-30)
        o_ref[0, 0] = (acc_scr[:] / l).astype(o_ref.dtype)


def _k_tile(iq, ik, *, causal: bool, block_q: int, block_k: int,
            window: int | None):
    """The k-tile that step ``ik`` of q-tile ``iq`` reads. The steps past
    the q-tile's last live tile name that tile again: equal consecutive
    block indices are not fetched again, so the dead part of the grid
    moves nothing."""
    if not causal:
        return ik
    first, last = _live_tiles(iq, block_q, block_k, window)
    return jnp.minimum(first + ik, last)


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k",
                                             "interpret", "scale", "window"))
def _flash_bhsd(q, k, v, causal: bool, block_q: int, block_k: int,
                interpret: bool, scale=None, window: int | None = None):
    """Kernel entry on [batch, heads, seq, dim] layout. ``k``/``v`` with
    fewer heads than ``q``: the grid walks the query heads and head ``i``
    streams the tiles of key-value head ``i // group``. ``v`` may be
    narrower or wider than ``q`` and ``k``: the output has its width."""
    b, h, sq, d = q.shape
    sk, dv = k.shape[2], v.shape[3]
    group = h // k.shape[1]
    scale = d ** -0.5 if scale is None else scale
    kv_head = (lambda ih: ih) if group == 1 else (lambda ih: ih // group)
    nk = _k_steps(sk, block_q, block_k, window)
    k_tile = functools.partial(_k_tile, causal=causal, block_q=block_q,
                               block_k=block_k, window=window)
    grid = (b, h, sq // block_q, nk)
    kern = functools.partial(_kernel, causal=causal, scale=scale,
                             block_q=block_q, block_k=block_k, window=window)
    # batch/head/q-block axes are independent → declare them parallel so
    # the TPU distributes them instead of walking the whole grid
    # sequentially (measured 500x on a [4,512,8,64] prefill); only the
    # trailing k axis carries the online-softmax accumulator and stays
    # sequential ("arbitrary")
    return pl.pallas_call(
        kern,
        out_shape=jax.ShapeDtypeStruct((b, h, sq, dv), q.dtype),
        grid=grid,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
            pl.BlockSpec((1, 1, block_k, d), lambda ib, ih, iq, ik: (
                ib, kv_head(ih), k_tile(iq, ik), 0)),
            pl.BlockSpec((1, 1, block_k, dv), lambda ib, ih, iq, ik: (
                ib, kv_head(ih), k_tile(iq, ik), 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, dv),
                               lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),  # running max
            pltpu.VMEM((block_q, 128), jnp.float32),  # running sum
            pltpu.VMEM((block_q, dv), jnp.float32),   # output accumulator
        ],
        interpret=interpret,
        name="nns_flash_prefill" if window is None
        else "nns_band_flash_prefill",
    )(q, k, v)


def _pallas_reject(q, k, block_q: int, block_k: int, v=None) -> str | None:
    """Why these shapes cannot go to the kernel, or None when they can.

    The bounds are what Mosaic (libtpu 0.0.34, TPU v5e) was seen to
    compile, bf16 and f32: q/k blocks of 8, 16, 24, 40, 128 and 256 rows
    — bf16 included, although its native tile is 16 rows — and head dims
    8 to 256 (the head dim is the blocks' lane dimension, legal at any
    size because it spans the whole array dimension). Values of another
    width than the keys (128 beside 192: PERF.md, PR 33) are held to the
    same bounds. Since PR 38 (the same libtpu, compiled and run on the
    chip, bf16): q blocks of 64 to 1536 rows against k blocks of 64 to
    2048 at heads of 128, 64 to 1536 both at 192/128, 64 to 1024 both at
    heads of 64 and 256; what is refused there is VMEM, not a shape
    (2048 x 1024 at heads of 128; for a described v5e also 1024 x 1024
    over float32 heads of 256: 16.48 MB asked of 16), which is
    :func:`tile_plan`'s to stay under, not this function's."""
    b, sq, h, d = q.shape
    sk = k.shape[1]
    dv = d if v is None else v.shape[-1]
    if sq % block_q or sk % block_k:
        return (f"seq ({sq}, {sk}) is not a multiple of the blocks "
                f"({block_q}, {block_k})")
    if block_q % 8 or block_k % 8:
        return f"blocks ({block_q}, {block_k}) are not multiples of 8 rows"
    if d % 8 or d > 256 or dv % 8 or dv > 256:
        return (f"head dim {d} (values {dv}) is not a multiple of 8 in "
                f"[8, 256]")
    if h % k.shape[2]:
        return (f"{h} query heads are no multiple of {k.shape[2]} "
                f"key-value heads")
    return None


@functools.lru_cache(maxsize=256)
def _log_reference_choice(q_shape, k_shape, dtype, why: str) -> None:
    """Auto mode gave way to the XLA reference ON A TPU: say so, once per
    shape, so the choice is visible in the serving log."""
    log.warning("flash_attention%s/%s %s runs the XLA reference, not the "
                "Pallas kernel: %s", q_shape, k_shape, dtype, why)


@functools.lru_cache(maxsize=256)
def _log_tile_plan(q_shape, k_shape, dv: int, dtype, window,
                   plan: TilePlan) -> None:
    """Say once a shape how the kernel tiles it and how many of a head's
    steps are live, masked and dead: static, so it costs a run nothing."""
    log.info("flash_attention%s/%s values %d %s window %s: tiles %d x %d, "
             "%d q-tiles of %d k-steps a head: %d unmasked, %d masked, "
             "%d dead", q_shape, k_shape, dv, dtype, window, plan.block_q,
             plan.block_k, plan.q_tiles, plan.k_steps, plan.unmasked,
             plan.masked, plan.dead)


def flash_attention(q, k, v, causal: bool = True, block_q: int | None = None,
                    block_k: int | None = None, force: str | None = None,
                    scale: float | None = None, window: int | None = None):
    """Attention on [batch, seq, heads, dim] tensors; ``scale`` and fewer
    key-value heads as in :func:`attention_reference`; the values may
    have another width than queries and keys, which the output takes.
    ``window``: the band ``0 <= i - j < window``, its dead k-tiles skipped.
    ``block_q`` / ``block_k``: the tiles; left out, :func:`tile_plan`
    makes them from the shapes.

    ``force``: None (auto: the Pallas kernel on a TPU for tileable
    shapes, else the XLA reference), "pallas" (always the kernel — Mosaic
    on a TPU, the Pallas interpreter elsewhere, which is how the CPU
    tests run it), or "reference".
    """
    _check_window(window, causal)
    ref = functools.partial(attention_reference, q, k, v, causal=causal,
                            scale=scale, window=window)
    if force == "reference":
        return ref()
    on_tpu = jax.default_backend() == "tpu"
    if force != "pallas" and not on_tpu:
        return ref()
    plan = tile_plan(q.shape[1], k.shape[1], q.shape[-1], v.shape[-1],
                     window, jnp.dtype(q.dtype), block_q, block_k, causal)
    why_not = _pallas_reject(q, k, plan.block_q, plan.block_k, v)
    if why_not and force == "pallas":
        raise ValueError(
            f"flash_attention: shapes {q.shape}/{k.shape} not tileable "
            f"by ({plan.block_q},{plan.block_k}): {why_not}")
    if why_not:
        _log_reference_choice(tuple(q.shape), tuple(k.shape), str(q.dtype),
                              why_not)
        return ref()
    _log_tile_plan(tuple(q.shape), tuple(k.shape), v.shape[-1], str(q.dtype),
                   window, plan)
    qt = q.swapaxes(1, 2)  # [b, h, s, d]
    kt = k.swapaxes(1, 2)
    vt = v.swapaxes(1, 2)
    out = _flash_bhsd(qt, kt, vt, causal, plan.block_q, plan.block_k,
                      interpret=not on_tpu, scale=scale, window=window)
    return out.swapaxes(1, 2)
