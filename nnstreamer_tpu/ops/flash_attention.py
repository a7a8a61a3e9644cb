"""Flash attention — tiled online-softmax attention as a Pallas TPU kernel.

Grid is (batch, heads, q_blocks, k_blocks); the TPU executes the trailing
grid axis sequentially on one core, so the running max/sum/accumulator
live in VMEM scratch across k-steps while K/V stream through VMEM one
``block_k`` tile at a time — the [seq, seq] score matrix never exists and
VMEM holds O(block) state regardless of context length. Causally-dead
k-tiles are skipped with predicated execution. bfloat16 in/out, fp32
accumulation — the MXU-friendly shape of the computation.

``window``: query ``i`` sees key ``j`` iff ``0 <= i - j < window`` (a band
under the diagonal). The k axis of the grid then has only as many steps as
a q-tile's band can touch, and step ``ik`` of q-tile ``iq`` maps to the
band's ``ik``-th k-tile: the tiles before the band are neither fetched nor
multiplied (the few steps past a q-tile's last live tile name that tile
again, so nothing is fetched for them either). The kernel is then called
``nns_band_flash_prefill``.
"""

from __future__ import annotations

import functools

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


def _band_tiles(iq, block_q: int, block_k: int, window: int):
    """The first and the last k-tile that q-tile ``iq``'s band touches."""
    lo = jnp.maximum(iq * block_q - (window - 1), 0) // block_k
    hi = ((iq + 1) * block_q - 1) // block_k
    return lo, hi


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            causal: bool, scale: float, block_q: int, block_k: int,
            window: int | None = None):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)
    if window is not None:   # step ki is the band's ki-th k-tile
        first, last = _band_tiles(qi, block_q, block_k, window)
        kt = first + ki

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _NEG_BIG)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # a k-tile is causally dead when its first key comes after the last
    # query of this q-tile
    live = True if not causal else ki * block_k <= (qi + 1) * block_q - 1
    if window is not None:
        live = kt <= last

    @pl.when(live)
    def _step():
        q = q_ref[0, 0].astype(jnp.float32) * scale       # [bq, d]
        k = k_ref[0, 0].astype(jnp.float32)               # [bk, d]
        v = v_ref[0, 0].astype(jnp.float32)
        s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
        if causal:
            q_pos = qi * block_q + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = (ki if window is None else kt) * block_k \
                + lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
            seen = q_pos >= k_pos if window is None \
                else (q_pos >= k_pos) & (q_pos - k_pos < window)
            s = jnp.where(seen, s, _NEG_BIG)
        m_prev = m_scr[:, :1]                              # [bq, 1]
        l_prev = l_scr[:, :1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[:] = acc_scr[:] * corr + lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(ki == nk - 1)
    def _finish():
        l = jnp.maximum(l_scr[:, :1], 1e-30)
        o_ref[0, 0] = (acc_scr[:] / l).astype(o_ref.dtype)


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
    nk = sk // block_k
    if window is not None:  # the k-tiles a q-tile's band can touch
        nk = min(nk, (window + block_q - 2) // block_k + 2)

    def k_tile(iq, ik):
        """The k-tile that step ``ik`` of q-tile ``iq`` reads."""
        if window is None:
            return ik
        first, last = _band_tiles(iq, block_q, block_k, window)
        return jnp.minimum(first + ik, last)

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
    same bounds."""
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


def flash_attention(q, k, v, causal: bool = True, block_q: int = 256,
                    block_k: int = 256, force: str | None = None,
                    scale: float | None = None, window: int | None = None):
    """Attention on [batch, seq, heads, dim] tensors; ``scale`` and fewer
    key-value heads as in :func:`attention_reference`; the values may
    have another width than queries and keys, which the output takes.
    ``window``: the band ``0 <= i - j < window``, its dead k-tiles skipped.

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
    block_q = min(block_q, q.shape[1])
    block_k = min(block_k, k.shape[1])
    on_tpu = jax.default_backend() == "tpu"
    why_not = _pallas_reject(q, k, block_q, block_k, v)
    if force == "pallas":
        if why_not:
            raise ValueError(
                f"flash_attention: shapes {q.shape}/{k.shape} not tileable "
                f"by ({block_q},{block_k}): {why_not}")
    elif not on_tpu:
        return ref()
    elif why_not:
        _log_reference_choice(tuple(q.shape), tuple(k.shape), str(q.dtype),
                              why_not)
        return ref()
    qt = q.swapaxes(1, 2)  # [b, h, s, d]
    kt = k.swapaxes(1, 2)
    vt = v.swapaxes(1, 2)
    out = _flash_bhsd(qt, kt, vt, causal, block_q, block_k,
                      interpret=not on_tpu, scale=scale, window=window)
    return out.swapaxes(1, 2)
