"""Bytes a decode step of the Qwen3-Next configuration has to move through
HBM, from the shapes of its parameters and its state and from the program's
own count of the experts that received a token: the numerators of its memory
roofline shares. Kept with the benchmark so that no PR that claims a gain
can change them. Every byte is counted at the width it is HELD in, and an
expert only if the program's counter says a token reached it, in the expert
layers' share and in the whole step's alike: with 128 lanes x 10 choices
over 512 experts about one held expert in eleven is not hit, and a count of
every held expert would let a share read over 100 %."""

from __future__ import annotations

from benchmark.work_hybrid import (
    ATTN_LEAVES,
    EXPERT_LEAVES,
    MOE_FIXED_LEAVES,
    _nbytes,
    kv_bytes_per_token,
)

#: a layer's leaves by the part of the step that has to read them (the
#: attention, expert and fixed leaves of ``work_hybrid`` with this family's)
LINATTN_LEAVES = ("la_in", "la_ba", "conv_w", "dt_bias", "A_log", "norm",
                  "la_out")
GATED_ATTN_LEAVES = ATTN_LEAVES + ("wg", "q_norm", "k_norm")
FIXED_LEAVES = MOE_FIXED_LEAVES + ("shared_gate",)


def param_bytes(params) -> dict:
    """Held bytes of the parameter tree by part: ``linattn`` and ``attn``
    (the mixers), ``moe_fixed`` (routers, shared experts and their gates),
    ``experts`` (all held experts), ``one_expert`` (one expert of one
    layer), ``head`` (the output head: its own leaf, read whole once a
    step), ``embed`` (the embedding: a step reads one row a lane) and
    ``norms``."""
    out = {"linattn": 0, "attn": 0, "moe_fixed": 0, "experts": 0, "norms": 0}
    for lp in params["layers"]:
        for name, leaf in lp.items():
            part = ("linattn" if name in LINATTN_LEAVES else
                    "attn" if name in GATED_ATTN_LEAVES else
                    "moe_fixed" if name in FIXED_LEAVES else
                    "experts" if name in EXPERT_LEAVES else "norms")
            out[part] += _nbytes(leaf)
    first = params["layers"][0]
    out["one_expert"] = sum(_nbytes(first[n]) // first[n].shape[0]
                            for n in EXPERT_LEAVES)
    out["head"] = _nbytes(params["lm_head"])
    out["embed"] = _nbytes(params["embed"])
    out["norms"] += _nbytes(params["ln_f"])
    return out


def state_bytes_per_lane(cfg) -> int:
    """Delta-rule state and convolution tail of one lane, all its layers."""
    import numpy as np

    state = cfg.la_value_heads * cfg.la_key_dim * cfg.la_value_dim \
        * np.dtype(cfg.ssm_state_dtype).itemsize
    tail = (cfg.la_conv - 1) * cfg.la_conv_dim * np.dtype(cfg.dtype).itemsize
    return cfg.la_layers * (state + tail)


def decode_bytes_per_step(params, cfg, lanes_live: float,
                          experts_hit_per_layer: float,
                          live_tokens: float) -> dict:
    """The least one decode step has to move, by mechanism:

    - ``linattn_bytes_per_step``: the delta-rule mixers' weights once, and
      the state and tail of every live lane read and written once;
    - ``moe_bytes_per_step``: routers, shared experts and their gates once,
      and each held expert that received a token once
      (``experts_hit_per_layer``: their mean number a layer and step,
      counted by the program);
    - ``decode_bytes_per_step``: both of these, the attention layer's
      weights, the output head and the norms once, the live keys and values
      once, one embedding row a live lane. Activations and the new token's
      writes are left out as negligible.

    ``by_part`` splits the last by leaf group, for ``PERF.md``."""
    parts = param_bytes(params)
    state = 2.0 * state_bytes_per_lane(cfg) * float(lanes_live)
    experts = parts["one_expert"] * float(experts_hit_per_layer) \
        * cfg.n_layers
    by_part = {
        "experts_hit": experts, "moe_fixed": float(parts["moe_fixed"]),
        "linattn_weights": float(parts["linattn"]), "state": state,
        "attn_weights": float(parts["attn"]),
        "kv": kv_bytes_per_token(cfg) * float(live_tokens),
        "head": float(parts["head"]), "norms": float(parts["norms"]),
        "embed_rows": parts["embed"] / cfg.vocab * float(lanes_live),
    }
    return {
        "linattn_bytes_per_step": parts["linattn"] + state,
        "moe_bytes_per_step": parts["moe_fixed"] + experts,
        "decode_bytes_per_step": sum(by_part.values()),
        "decode_bytes_by_part": by_part,
    }
