"""Bytes a decode step of the hybrid family has to move through HBM, from
the shapes of its parameters and its state: the numerators of its memory
roofline shares. Kept with the benchmark so that no PR that claims a gain
can change them. Every byte is counted at the width it is stored in."""

from __future__ import annotations

#: a layer's leaves by the part of the step that has to read them
SSM_LEAVES = ("ssm_in", "conv_w", "conv_b", "dt_bias", "A_log", "D", "norm",
              "ssm_out")
ATTN_LEAVES = ("wq", "wk", "wv", "wo")
MOE_FIXED_LEAVES = ("router", "shared_in", "shared_out")
EXPERT_LEAVES = ("w_in", "w_out")


def _nbytes(x) -> int:
    return int(x.size) * int(x.dtype.itemsize)


def param_bytes(params) -> dict:
    """Stored bytes of the parameter tree by part: ``ssm`` and ``attn``
    (the mixers), ``moe_fixed`` (routers and shared MLPs), ``experts`` (all
    held experts), ``one_expert`` (one expert of one layer), ``head`` (the
    tied embedding, read once as the output head) and ``norms``."""
    out = {"ssm": 0, "attn": 0, "moe_fixed": 0, "experts": 0, "norms": 0}
    for lp in params["layers"]:
        for name, leaf in lp.items():
            part = ("ssm" if name in SSM_LEAVES else
                    "attn" if name in ATTN_LEAVES else
                    "moe_fixed" if name in MOE_FIXED_LEAVES else
                    "experts" if name in EXPERT_LEAVES else "norms")
            out[part] += _nbytes(leaf)
    first = params["layers"][0]
    out["one_expert"] = sum(_nbytes(first[n]) // first[n].shape[0]
                            for n in EXPERT_LEAVES)
    out["head"] = _nbytes(params["embed"])
    out["norms"] += _nbytes(params["ln_f"])
    return out


def state_bytes_per_lane(cfg) -> int:
    """Recurrent state and convolution tail of one lane, all state-space
    layers."""
    import numpy as np

    state = cfg.ssm_heads * cfg.ssm_head_dim * cfg.ssm_state \
        * np.dtype(cfg.ssm_state_dtype).itemsize
    tail = (cfg.ssm_conv - 1) * cfg.conv_dim * np.dtype(cfg.dtype).itemsize
    return cfg.ssm_layers * (state + tail)


def kv_bytes_per_token(cfg) -> int:
    """Keys and values of one token over the attention layers."""
    import numpy as np

    return 2 * cfg.attn_layers * cfg.n_kv_heads * cfg.head_dim \
        * np.dtype(cfg.dtype).itemsize


def decode_bytes_per_step(params, cfg, lanes_live: float,
                          experts_hit_per_layer: float,
                          live_tokens: float) -> dict:
    """The least one decode step has to move, by mechanism:

    - ``ssm_bytes_per_step``: the state-space mixers' weights once, and
      the state of every live lane read and written;
    - ``moe_bytes_per_step``: routers and shared MLPs once, and each held
      expert that received a token once (``experts_hit_per_layer``: their
      mean number a layer and step, counted by the program);
    - ``decode_bytes_per_step``: every held weight once (all held
      experts: with 64 lanes x 10 choices each is hit), the state twice,
      the live keys and values once. Activations and the new token's
      writes are left out as negligible."""
    parts = param_bytes(params)
    state = 2.0 * state_bytes_per_lane(cfg) * float(lanes_live)
    return {
        "ssm_bytes_per_step": parts["ssm"] + state,
        "moe_bytes_per_step": parts["moe_fixed"] + parts["one_expert"]
        * float(experts_hit_per_layer) * cfg.n_layers,
        "decode_bytes_per_step": float(sum(
            parts[k] for k in ("ssm", "attn", "moe_fixed", "experts", "head",
                               "norms"))) + state
        + kv_bytes_per_token(cfg) * float(live_tokens),
    }
