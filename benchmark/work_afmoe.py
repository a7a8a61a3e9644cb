"""Bytes a decode step of the AFMoE configuration has to move through HBM
and operations its band prefill has to do, from the shapes of its parameters
and from the program's own counters: the numerators of its roofline shares.
Kept with the benchmark so that no PR that claims a gain can change them.

Every weight is counted at the width the engine HOLDS it, an expert only if
the program's counter says a token reached it (``moe_experts_hit``), the full
layers' keys and values by the blocks the program's counter says a step's
attention had to read (``kv_blocks_live``) and the window layers' by theirs
(``kv_window_blocks_live``: the window's blocks, not the context's): no
share can read over 100 %."""

from __future__ import annotations

from benchmark.work_hybrid import EXPERT_LEAVES, MOE_FIXED_LEAVES, _nbytes

#: a layer's leaves by the part of the step that has to read them
MIXER_LEAVES = ("wq", "wk", "wv", "wg", "wo", "q_norm", "k_norm")
DENSE_LEAVES = ("dense_in", "dense_out")


def param_bytes(params) -> dict:
    """Held bytes of the parameter tree by part: ``mixers`` (attention of
    both kinds), ``dense`` (the leading layers' MLPs), ``moe_fixed``
    (routers and shared experts), ``experts`` (all held experts),
    ``one_expert`` (one expert of one layer), ``expert_layers``, ``head``
    (read whole once a step), ``embed`` (a step reads one row a lane) and
    ``norms`` (the sandwich norms, the selection bias)."""
    out = {"mixers": 0, "dense": 0, "moe_fixed": 0, "experts": 0, "norms": 0,
           "one_expert": 0, "expert_layers": 0}
    for lp in params["layers"]:
        for name, leaf in lp.items():
            part = ("mixers" if name in MIXER_LEAVES else
                    "dense" if name in DENSE_LEAVES else
                    "moe_fixed" if name in MOE_FIXED_LEAVES else
                    "experts" if name in EXPERT_LEAVES else "norms")
            out[part] += _nbytes(leaf)
        if "w_in" in lp:
            out["expert_layers"] += 1
            out["one_expert"] = sum(_nbytes(lp[n]) // lp[n].shape[0]
                                    for n in EXPERT_LEAVES)
    out["head"] = _nbytes(params["lm_head"])
    out["embed"] = _nbytes(params["embed"])
    out["norms"] += _nbytes(params["ln_f"])
    return out


def token_bytes(cfg) -> int:
    """One token's keys and values in ONE layer, in the cache's width."""
    import numpy as np

    return 2 * cfg.n_kv_heads * cfg.head_dim * np.dtype(cfg.dtype).itemsize


def decode_bytes_per_step(params, cfg, lanes_live: float,
                          experts_hit_per_layer: float,
                          blocks_read_per_step: float,
                          window_blocks_read_per_step: float,
                          block_tokens: int) -> dict:
    """The least one decode step has to move, by mechanism:

    - ``window_read_bytes_per_step``: the keys and values of the blocks a
      step's WINDOW layers have to read (``window_blocks_read_per_step``:
      the mean of the program's ``kv_window_blocks_live`` a step, one
      layer's), in every window layer, once: what the windowed paged kernel
      alone has to move;
    - ``full_read_bytes_per_step``: the same for the full layers
      (``blocks_read_per_step``: ``kv_blocks_live`` a step);
    - ``attnmix_bytes_per_step``: both, the mixers' weights once and one
      token's keys and values a live lane and layer written;
    - ``moe_bytes_per_step``: routers and shared experts once, and each
      held expert that received a token once (``experts_hit_per_layer``:
      their mean number an expert layer and step, counted by the program);
    - ``decode_bytes_per_step``: both of these, the leading dense layers'
      MLPs, the output head and the norms once, one embedding row a live
      lane. Activations are left out as negligible.

    ``decode_bytes_by_part`` splits the last by leaf group, for
    ``PERF.md``."""
    parts = param_bytes(params)
    row, T = token_bytes(cfg), int(block_tokens)
    win_read = row * cfg.window_layers * float(window_blocks_read_per_step) * T
    full_read = row * cfg.full_layers * float(blocks_read_per_step) * T
    written = row * cfg.n_layers * float(lanes_live)
    experts = parts["one_expert"] * float(experts_hit_per_layer) \
        * parts["expert_layers"]
    by_part = {
        "experts_hit": experts, "moe_fixed": float(parts["moe_fixed"]),
        "mixer_weights": float(parts["mixers"]), "window_read": win_read,
        "full_read": full_read, "rows_written": written,
        "dense_ffn": float(parts["dense"]), "head": float(parts["head"]),
        "norms": float(parts["norms"]),
        "embed_rows": parts["embed"] / cfg.vocab * float(lanes_live),
    }
    return {
        "window_read_bytes_per_step": win_read,
        "full_read_bytes_per_step": full_read,
        "attnmix_bytes_per_step": parts["mixers"] + win_read + full_read
        + written,
        "moe_bytes_per_step": parts["moe_fixed"] + experts,
        "decode_bytes_per_step": sum(by_part.values()),
        "decode_bytes_by_part": by_part,
    }


def band_attention_flops(cfg, tokens: int) -> float:
    """Operations ONE window layer's attention has to do over a prompt of
    ``tokens`` positions: a multiply and an add for each of ``head_dim``
    dims of each (query, key) pair inside the band ``0 <= i - j < window``,
    for the scores and again for the values, in every query head. A kernel
    that computes whole tiles does more than this."""
    w = min(cfg.window, tokens)
    pairs = w * (w + 1) // 2 + (tokens - w) * w
    return 4.0 * cfg.head_dim * cfg.n_heads * pairs
