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
import math
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
    #: ``prefill_counters(cfg, rows) -> {name: n}``: what a prefill over a
    #: bucket of ``rows`` positions computed of ONE row, for a family
    #: whose prefill does not run every layer over every row (``rows`` 0
    #: names the counters); the engine sums them into ``stats`` for every
    #: row of a program, a row that holds no prompt too
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
    #: ``build_paged_chunk(cfg, block_tokens, max_seq, kv_codec=)``: the
    #: one builder of several query positions a lane, written into the
    #: block arena (chunked ingestion, prefix extension, speculation's
    #: verification); None where ``brings`` names none of them
    build_paged_chunk: Optional[Callable] = None
    #: names (a leaf's own key in the parameter tree) of the leaves every
    #: program of the family reads as ``leaf.astype(cfg.dtype)`` and in no
    #: other width; what a program reads as stored (norm scales, a head's
    #: float32 table) is not named
    read_in_dtype: Tuple[str, ...] = ()
    #: ``token_share(cfg) -> {leaf name: share}``: for a leaf that one
    #: token's matmuls read only a share of (routed experts: ``experts per
    #: token / router outputs`` of the experts held), that share; a matrix
    #: not named is read whole (:func:`token_params`)
    token_share: Callable = lambda cfg: {}


#: the chip's published peaks, the ones ``PERF.md``'s rooflines divide by
#: (Google Cloud, "TPU v5e": 197 TFLOP/s bfloat16, 819 GB/s of HBM; the
#: benchmark's ``benchmark/peaks.json`` holds the same)
PEAK_FLOPS = 197e12
PEAK_HBM_BYTES_PER_S = 819e9
#: the share of that arithmetic peak a prefill program reaches, measured
#: on the chip: a qwen3-next prefill of R x s tokens adds 17.5-18.5 ms a
#: thousand tokens where its 2 x 208 M parameters a token take 2.1 ms at
#: the peak (11-12 %: the delta rule's row-by-row solve and its float32
#: products are in it), a granite one 52-64 us a token against 16.5 (26-32
#: %). A program's rows cost the same whether they hold a prompt or not, so
#: the rows rule takes the lower share: one that is too high batches past
#: the point where the rows' own work outgrows the weight read it shares
PREFILL_PEAK_SHARE = 0.12
#: the most prompts one prefill program takes (:func:`prefill_rows`)
PREFILL_ROWS_MAX = 8
#: leaves a prompt's tokens do not multiply by: the embedding is looked up
#: a row a token, the head multiplies one row a prompt
_ONCE_A_PROMPT = ("embed", "lm_head")


def token_params(cfg, params) -> float:
    """The parameters one prompt token's matmuls read, from the shapes of
    the tree ``params`` alone (arrays or ``jax.ShapeDtypeStruct``): every
    matrix (a leaf of two or more axes) but the embedding and the head, a
    leaf the family names in ``token_share`` at its share."""
    import jax

    shares = cfg.family.token_share(cfg)
    total = 0.0
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        name = getattr(path[-1], "key", None)
        if len(leaf.shape) >= 2 and name not in _ONCE_A_PROMPT:
            total += math.prod(leaf.shape) * shares.get(name, 1.0)
    return total


def balance_tokens(cfg, params, weight_bytes: int) -> float:
    """T_w: the prompt tokens whose matmuls take as long, at the rate a
    prefill reaches, as one read of ``weight_bytes`` (the weights held) at
    the HBM peak: ``weight_bytes / 819e9 x 197e12 x PREFILL_PEAK_SHARE /
    (2 x token_params)``. A prefill over fewer tokens than this waits on
    its weights, not on its arithmetic."""
    return (weight_bytes / PEAK_HBM_BYTES_PER_S * PEAK_FLOPS
            * PREFILL_PEAK_SHARE
            / (2.0 * max(token_params(cfg, params), 1.0)))


def prefill_rows(balance: float, bucket: int) -> int:
    """Prompts one prefill program over ``bucket`` positions takes: the
    largest power of two at most ``min(PREFILL_ROWS_MAX, balance /
    bucket)``, and 1 below two. Each row then costs its share of ONE read
    of the weights where, alone, it would cost a whole read."""
    rows = 1
    while rows < PREFILL_ROWS_MAX and 2 * rows * bucket <= balance:
        rows *= 2
    return rows


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
