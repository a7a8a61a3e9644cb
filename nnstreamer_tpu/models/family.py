"""What the serving engine needs of a language model, as one record.

``ContinuousBatchingEngine`` used to import the dense block's builders by
name. A model whose layers are of more than one kind, or that keeps state
other than keys and values, brings its own: its configuration's ``family``
property returns a :class:`ModelFamily`, and the engine, the pool and the
benchmark's drivers take parameter init, the prefill and paged-decode
builders and the description of a lane's state from there.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Tuple


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
    #: ``kv_layout(cfg) -> (layers, heads, head_dim)`` of the block arena
    kv_layout: Callable
    #: ``lane_state(cfg) -> None``, or what each decode lane holds beside
    #: its blocks: ``{"layers": n, leaf: (shape, dtype), ...}``. A family
    #: with lane state is served on the paged path only, a stream keeps
    #: its lane for life, and options that need such state copied, shared
    #: or sharded are refused (``ContinuousBatchingEngine``)
    lane_state: Callable = lambda cfg: None
    #: names of the per-step counts the decode step returns
    counters: Tuple[str, ...] = ()
