"""The draft of the serving engine's speculative decoding (``speculate=K``).

``ContinuousBatchingEngine`` (``serving/engine.py``) drafts K tokens a
lane with a shallow copy of the target and has the target verify all K+1
positions in ONE chunk pass over the block arena
(``models/transformer.py build_paged_chunk``); greedy acceptance keeps
the emitted stream identical to target-only greedy decoding
(``tests/test_speculative_engine.py``). A rejected suffix needs no
cleanup: the chunk writes slot i before any query attends it, so stale
kv past the accepted prefix is unreachable and is overwritten when
generation gets there.

:func:`draft_from_target` makes that draft.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Tuple

from nnstreamer_tpu.models.transformer import TransformerConfig


def draft_from_target(cfg: TransformerConfig, params: Any,
                      n_layers: int) -> Tuple[TransformerConfig, Any]:
    """Depth-pruned self-speculative draft: the target's FIRST
    ``n_layers`` layers (params are stacked [L, ...], so the draft is a
    zero-copy slice) sharing the embedding — no separately-trained draft
    model needed, and early layers correlate strongly with the full
    model's prediction, which is what acceptance length depends on.
    """
    if not 0 < n_layers <= cfg.n_layers:
        raise ValueError(
            f"draft_from_target: n_layers must be in (0, {cfg.n_layers}], "
            f"got {n_layers}")
    draft_cfg = dataclasses.replace(cfg, n_layers=n_layers)
    draft_params = {
        k: (v if k in ("embed", "ln_f") else v[:n_layers])
        for k, v in params.items()
    }
    return draft_cfg, draft_params
