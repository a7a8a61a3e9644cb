"""What the serving engine needs of a language model, as one record.

``ContinuousBatchingEngine`` used to import the dense block's builders by
name. A model whose layers are of more than one kind, or that keeps state
other than keys and values, brings its own: its configuration's ``family``
property returns a :class:`ModelFamily`, and the engine, the pool and the
benchmark's drivers take parameter init, the prefill and paged-decode
builders and the description of a lane's state from there.

The record also says which parameter leaves the family's programs read
through ``.astype(cfg.dtype)``; :func:`serving_params` makes, once, the
tree a server's programs will read from the tree it was given.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple


@dataclasses.dataclass(frozen=True)
class ModelFamily:
    name: str
    #: ``init_params(cfg, seed) -> params``
    init_params: Callable
    #: ``build_prefill(cfg, max_seq, attention_fn=, kv_codec=) ->
    #: prefill(params, tokens[b, s], lengths[b]) -> (logits[b, vocab],
    #: cache)``; ``cache`` is what ``BlockPool.scatter_prefill`` takes
    build_prefill: Callable
    #: ``build_paged_decode_step(cfg, block_tokens, max_seq, kv_codec=,
    #: paged_attention_fn=) -> step(params, token[b], arena, bt[b, MB],
    #: pos[b]) -> (logits, arena)`` or ``(logits, arena, counts)`` with
    #: ``counts`` a dict of int32 scalars named as ``counters``;
    #: ``paged_attention_fn`` is ``ops.paged_attention`` or None (the
    #: gather form)
    build_paged_decode_step: Callable
    #: ``kv_entry(cfg) -> (layers, parts, shape)``: what the block arena
    #: holds for one token of one layer, ``parts`` arrays of ``shape``:
    #: keys and values per head are ``(layers, 2, (heads, head_dim))``, one
    #: latent row shared by every head ``(layers, 1, (width,))``. The
    #: arena leaf is ``[layers, blocks, parts, T, *shape]``, or, for a
    #: shape ``(heads, head_dim)`` whose heads are no multiple of 8,
    #: heads-major
    #: ``[layers, blocks, parts, heads, T, head_dim]``: this shape is ALL
    #: that decides the order (``models/transformer.py``
    #: ``kv_heads_major``, applied by the codec that makes the arena)
    kv_entry: Callable
    #: ``kv_window(cfg) -> None``, or ``(layers, window)``: the family has
    #: attention layers of TWO kinds. ``kv_entry``'s layers see everything
    #: and keep every block of a stream; beside them, this many layers see
    #: the last ``window`` positions only (key ``j`` iff ``0 <= i - j <
    #: window``). They get a block arena of their own, of the same entry
    #: form (``serving/kvpool.py`` ``BlockPool.win``), a table of their own
    #: a lane, and the engine gives a lane's blocks back to that arena once
    #: they lie wholly behind the window. The decode step then takes the
    #: tables as ``{"kv": bt, "win": bt_w}`` and the arenas as ``{"kv":
    #: pages, "win": pages_w}``, and the prefill's cache has both keys. A
    #: family may state ``lane_state`` as well: the arenas and the cache
    #: then have a third key, ``"state"`` (``models/sambay.py``)
    kv_window: Callable = lambda cfg: None
    #: ``latent_value_width(cfg)``: for a one-part entry, how many of the
    #: row's first columns are the value (``ops.paged_attention``
    #: ``v_width``); None for keys and values per head
    latent_value_width: Callable = lambda cfg: None
    #: ``lane_state(cfg) -> None``, or what each decode lane holds beside
    #: its blocks: ``{"layers": n, leaf: (shape, dtype), ...}``. A family
    #: with lane state is served on the paged path only, a stream keeps
    #: its lane for life, and options that need such state copied, shared
    #: or sharded are refused (``ContinuousBatchingEngine``). The decode
    #: step's arenas are then ``{"kv": pages, "state": {leaf: array}}``
    #: (with ``kv_window`` also ``"win"``), and the prefill's cache has the
    #: same keys
    lane_state: Callable = lambda cfg: None
    #: names of the per-step counts the decode step returns
    counters: Tuple[str, ...] = ()
    #: ``prefill_counters(cfg, rows) -> {name: n}``: what ONE prompt's
    #: prefill over a bucket of ``rows`` positions computed, for a family
    #: whose prefill does not run every layer over every row (``rows`` 0
    #: names the counters); the engine sums them into ``stats``
    prefill_counters: Optional[Callable] = None
    #: ``expert_matmul(cfg, tokens) -> form``: the form the family's
    #: routed experts run in for that many tokens a call
    #: (``ops/grouped_matmul.py``); None for a family with none
    expert_matmul: Optional[Callable] = None
    #: ``state_update(cfg, lanes) -> form``: the form the decode step
    #: updates that many lanes' recurrent state in
    #: (``ops/lane_state.py``); None for a family with no lane state
    state_update: Optional[Callable] = None
    #: the engine options the family's programs bring, of ``prefix_cache``,
    #: ``speculate``, ``prefill_chunk``, ``kv_quant`` and ``mesh``; any
    #: other is refused at construction with ``refusal``, which says what
    #: the family lacks for them and where ``ROADMAP.md`` queues it
    brings: Tuple[str, ...] = ()
    refusal: str = ""
    #: ``build_chunk_decode(cfg, max_seq, kv_codec=)`` and
    #: ``build_paged_chunk(cfg, block_tokens, max_seq, kv_codec=)``:
    #: several query positions a lane (chunked ingestion, prefix
    #: extension, speculation's verification); None where ``brings`` names
    #: none of them
    build_chunk_decode: Optional[Callable] = None
    build_paged_chunk: Optional[Callable] = None
    #: names (a leaf's own key in the parameter tree) of the leaves every
    #: program of the family reads as ``leaf.astype(cfg.dtype)`` and in no
    #: other width; what a program reads as stored (norm scales, a head's
    #: float32 table) is not named
    read_in_dtype: Tuple[str, ...] = ()


def serving_params(cfg, params) -> Tuple[Any, Dict[str, int]]:
    """The tree a server's programs read, made once from the tree it was
    given: ``(held, record)``.

    One rule for every family, decided by what the input shows: a leaf
    the family names in ``read_in_dtype`` that is WIDER than ``cfg.dtype``
    is rounded to it, all such leaves in one jitted call; every other leaf
    (one already in ``cfg.dtype``, one narrower, one a program reads as
    stored) is passed through as the same array. The builders keep their
    ``.astype(cfg.dtype)``: on a leaf of that dtype it is the identity and
    the program has no ``convert``, so the rounding that a program given
    the wide tree repeats at every call happens here, once, to the same
    values bit for bit.

    ``record``: ``weight_bytes_given``, ``weight_bytes_held`` and
    ``weight_leaves_narrowed``."""
    import jax
    import jax.numpy as jnp

    dtype = jnp.dtype(cfg.dtype)
    names = cfg.family.read_in_dtype
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    held = [leaf for _, leaf in flat]

    def nbytes():
        return sum(int(a.size) * jnp.dtype(a.dtype).itemsize for a in held)

    record = {"weight_bytes_given": nbytes()}
    wide = [i for i, (path, leaf) in enumerate(flat)
            if getattr(path[-1], "key", None) in names
            and jnp.issubdtype(leaf.dtype, jnp.floating)
            and jnp.dtype(leaf.dtype).itemsize > dtype.itemsize]
    if wide:
        narrow = jax.jit(lambda leaves: [a.astype(dtype) for a in leaves])(
            [held[i] for i in wide])
        for i, a in zip(wide, narrow):
            held[i] = a
    record.update(weight_bytes_held=nbytes(),
                  weight_leaves_narrowed=len(wide))
    return jax.tree_util.tree_unflatten(treedef, held), record
