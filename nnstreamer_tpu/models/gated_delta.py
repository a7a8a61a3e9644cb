"""The gated delta rule: the recurrence of a linear-attention mixer whose
state is corrected, not only added to (PAPERS.md, Gated DeltaNet).

Per head the state is a matrix ``S [key, value]``, float32. One token:

    S <- exp(g) S            decay, g <= 0
    r  = S^T k               what the state holds under this key
    S <- S + k (x) b (v - r) write the CORRECTION, b in (0, 1)
    o  = S^T q

The read ``r`` depends on everything written before it, so a prompt cannot
be summed the way the state-space-duality form sums one
(``models/hybrid.py ssd_chunked``): inside a chunk the corrections solve a
unit lower-triangular system (the WY form), which
:func:`gated_delta_chunked` solves row by row, exactly, and between chunks
the recurrence runs on whole-chunk states. :func:`gated_delta_step` is the
recurrence for one token of every lane. Both are plain float32 ``jax.numpy``
with no configuration: the mixer around them (projections, convolution,
norm) is ``models/hybrid.py``'s.
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax

_HI = lax.Precision.HIGHEST


def l2norm(x, eps: float = 1e-6):
    """``x / sqrt(sum(x^2) + eps)`` over the last axis."""
    return x * lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


def gated_delta_step(state, q, k, v, g, beta):
    """One token: ``state [.., key, value]``, ``q``/``k [.., key]``,
    ``v [.., value]``, ``g``/``beta [..]``, all float32 → ``(o [..,
    value], state)``. The output is taken from the decayed state and the
    correction apart, ``S'^T q + (k.q) d``, so that both reads of the state
    are of the same array (one pass over it) and only the write is a
    second. Products and sums are elementwise: no operand is narrowed."""
    kept = state * jnp.exp(g)[..., None, None]
    read = jnp.sum(kept * k[..., :, None], axis=-2)
    seen = jnp.sum(kept * q[..., :, None], axis=-2)
    d = beta[..., None] * (v - read)
    o = seen + jnp.sum(k * q, axis=-1, keepdims=True) * d
    return o, kept + k[..., :, None] * d[..., None, :]


def _unit_lower_inverse(a):
    """``(I + a)^-1`` for ``a [.., n, n]`` strictly lower triangular, by
    forward substitution one row at a time (row ``i`` needs the rows above
    it). A product of powers of ``a`` would take six matrix products, and
    lose every digit where the entries of ``a`` are of size one."""
    n = a.shape[-1]

    def row(i, t):
        r = lax.dynamic_slice_in_dim(t, i, 1, axis=-2)          # [.., 1, n]
        new = r + jnp.sum(jnp.swapaxes(r, -1, -2) * t, axis=-2,
                          keepdims=True)
        return lax.dynamic_update_slice_in_dim(t, new, i, axis=-2)

    # t = -a: row i of (I + a)^-1 - I is -a_i (I + what the rows above gave)
    return lax.fori_loop(1, n, row, -a) + jnp.eye(n, dtype=a.dtype)


def gated_delta_chunked(q, k, v, g, beta, chunk: int):
    """The recurrence from a zero state over whole sequences, chunk by
    chunk: ``q``/``k [b, s, h, key]``, ``v [b, s, h, value]``, ``g``/``beta
    [b, s, h]``, all float32 → ``(o [b, s, h, value], S [b, h, key,
    value])``. A position with ``g = 0`` and ``beta = 0`` passes the state
    through unchanged, which is how padding is kept out of it."""
    b, s_in, h, dk = q.shape
    c = min(chunk, s_in)
    if s_in % c:  # whole chunks: the rows added have g = beta = 0
        pad = [(0, 0), (0, c - s_in % c), (0, 0)]
        q, k, v = (jnp.pad(x, pad + [(0, 0)]) for x in (q, k, v))
        g, beta = jnp.pad(g, pad), jnp.pad(beta, pad)
    n = q.shape[1] // c

    def chunks(x):  # [b, s, h, ..] -> [b, h, n, c, ..]
        x = x.reshape((b, n, c) + x.shape[2:])
        return jnp.moveaxis(x, 3, 1)

    q, k, v, g, beta = (chunks(x) for x in (q, k, v, g, beta))
    acc = jnp.cumsum(g, axis=-1)                       # <= 0, falling
    lower = jnp.tril(jnp.ones((c, c), bool))
    # decay from position j to position i >= j of one chunk
    decay = jnp.where(lower, jnp.exp(jnp.where(
        lower, acc[..., :, None] - acc[..., None, :], 0.0)), 0.0)
    kb = k * beta[..., None]
    within = jnp.einsum("bhnik,bhnjk->bhnij", kb, k, precision=_HI) * decay
    solve = _unit_lower_inverse(jnp.where(lower & ~jnp.eye(c, dtype=bool),
                                          within, 0.0))
    # each position's correction had the chunk started from a zero state,
    # and what a state entering the chunk takes off it
    fresh = jnp.einsum("bhnij,bhnjv->bhniv", solve, v * beta[..., None],
                       precision=_HI)
    carried = jnp.einsum("bhnij,bhnjk->bhnik", solve,
                         kb * jnp.exp(acc)[..., None], precision=_HI)
    attend = jnp.einsum("bhnik,bhnjk->bhnij", q, k, precision=_HI) * decay
    to_end = jnp.exp(acc[..., -1:] - acc)              # [b, h, n, c]
    state = jnp.zeros((b, h, dk, v.shape[-1]), jnp.float32)
    out = []
    for i in range(n):
        d = fresh[:, :, i] - jnp.einsum("bhik,bhkv->bhiv", carried[:, :, i],
                                        state, precision=_HI)
        out.append(
            jnp.einsum("bhik,bhkv->bhiv",
                       q[:, :, i] * jnp.exp(acc[:, :, i])[..., None], state,
                       precision=_HI)
            + jnp.einsum("bhij,bhjv->bhiv", attend[:, :, i], d,
                         precision=_HI))
        state = state * jnp.exp(acc[:, :, i, -1])[..., None, None] \
            + jnp.einsum("bhik,bhiv->bhkv",
                         k[:, :, i] * to_end[:, :, i][..., None], d,
                         precision=_HI)
    o = jnp.moveaxis(jnp.stack(out, axis=2), 1, 3)     # [b, n, c, h, v]
    return o.reshape(b, n * c, h, -1)[:, :s_in], state
