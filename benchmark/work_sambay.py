"""Bytes a decode step of the SambaY configuration has to move through HBM,
from the shapes of its parameters and its lane memory and from the
program's own counters: the numerators of its roofline shares. Kept with
the benchmark so that no PR that claims a gain can change them.

Every byte is counted at the width HELD and each stored row once a layer
that reads it, whatever kernel implements the read: the full layer's rows
by the blocks the program's counters say a step's attention had to read
(``kv_blocks_live``: the full layer's own read; ``kv_shared_reads``: the
cross layers', which own no cache and read the same blocks again, once a
layer), the window layers' by theirs (``kv_window_blocks_live``: the
window's blocks, not the context's), a lane's recurrent state read and
written once a layer: no share can read over 100 %."""

from __future__ import annotations

#: a layer's leaves by the part of the step that has to read them
SSM_LEAVES = ("ssm_in", "conv_w", "conv_b", "x_proj", "dt_proj", "dt_bias",
              "A_log", "D", "ssm_out")
ATTN_LEAVES = ("wqkv", "bqkv", "wq", "bq", "wo", "bo", "lam_q1", "lam_k1",
               "lam_q2", "lam_k2", "sub_norm")
GMU_LEAVES = ("gmu_in", "gmu_out")
MLP_LEAVES = ("mlp_in", "mlp_out")


def _nbytes(x) -> int:
    return int(x.size) * int(x.dtype.itemsize)


def param_bytes(params) -> dict:
    """Held bytes of the parameter tree by part: ``ssm``, ``attn`` and
    ``gmu`` (the mixers), ``mlp``, ``norms``, and ``head`` (the tied
    embedding, read whole once a step as the output head; a step reads one
    more row of it a lane as the embedding)."""
    out = {"ssm": 0, "attn": 0, "gmu": 0, "mlp": 0, "norms": 0}
    for lp in params["layers"]:
        for name, leaf in lp.items():
            part = ("ssm" if name in SSM_LEAVES else
                    "attn" if name in ATTN_LEAVES else
                    "gmu" if name in GMU_LEAVES else
                    "mlp" if name in MLP_LEAVES else "norms")
            out[part] += _nbytes(leaf)
    out["head"] = _nbytes(params["embed"])
    out["norms"] += _nbytes(params["ln_f"]) + _nbytes(params["ln_f_b"])
    return out


def token_bytes(cfg) -> int:
    """One token's keys and values in ONE layer, in the cache's width."""
    import numpy as np

    return 2 * cfg.kv_pairs * cfg.pair_width * np.dtype(cfg.dtype).itemsize


def state_bytes_per_lane(cfg) -> dict:
    """One lane's recurrent state (``ssm``) and convolution tails
    (``conv``), all Mamba layers."""
    import numpy as np

    return {"ssm": cfg.ssm_layers * cfg.ssm_state * cfg.ssm_inner
            * np.dtype(cfg.ssm_state_dtype).itemsize,
            "conv": cfg.ssm_layers * (cfg.ssm_conv - 1) * cfg.ssm_inner
            * np.dtype(cfg.dtype).itemsize}


def decode_bytes_per_step(params, cfg, lanes_live: float,
                          blocks_read_per_step: float,
                          shared_reads_per_step: float,
                          window_blocks_read_per_step: float,
                          block_tokens: int) -> dict:
    """The least one decode step has to move, by mechanism:

    - ``state_rw_bytes_per_step``: every live lane's recurrent state read
      and written, every Mamba layer, once: what the lane-state kernel
      alone has to move;
    - ``ssm_bytes_per_step``: that, the convolution tails likewise, and the
      Mamba mixers' weights once;
    - ``shared_kv_read_bytes_per_step``: the ONE full layer's rows, read by
      it (``blocks_read_per_step``: the mean of the program's
      ``kv_blocks_live`` a step) and again by every cross layer
      (``shared_reads_per_step``: ``kv_shared_reads`` a step): what the
      ``attend`` and ``attend_cross`` kernels have to move;
    - ``window_read_bytes_per_step``: the rows of the blocks a step's
      window layers have to read (``window_blocks_read_per_step``:
      ``kv_window_blocks_live`` a step, one layer's), every window layer;
    - ``decode_bytes_per_step``: all of these, every other held weight once
      (the tied embedding once, as the head), one token's keys and values a
      live lane and caching layer written, one embedding row a live lane.
      Activations are left out as negligible.

    ``decode_bytes_by_part`` splits the last, for ``PERF.md``."""
    parts = param_bytes(params)
    row, T = token_bytes(cfg), int(block_tokens)
    lane = state_bytes_per_lane(cfg)
    state_rw = 2.0 * lane["ssm"] * float(lanes_live)
    conv_rw = 2.0 * lane["conv"] * float(lanes_live)
    shared = row * T * (float(blocks_read_per_step)
                        + float(shared_reads_per_step))
    win_read = row * T * cfg.window_layers \
        * float(window_blocks_read_per_step)
    written = row * (cfg.window_layers + 1) * float(lanes_live)
    by_part = {
        "ssm_weights": float(parts["ssm"]), "state_rw": state_rw,
        "conv_rw": conv_rw, "attn_weights": float(parts["attn"]),
        "shared_kv_read": shared, "window_read": win_read,
        "rows_written": written, "gmu_weights": float(parts["gmu"]),
        "mlp_weights": float(parts["mlp"]), "head": float(parts["head"]),
        "norms": float(parts["norms"]),
        "embed_rows": parts["head"] / cfg.vocab * float(lanes_live),
    }
    return {
        "state_rw_bytes_per_step": state_rw,
        "ssm_bytes_per_step": parts["ssm"] + state_rw + conv_rw,
        "shared_kv_read_bytes_per_step": shared,
        "window_read_bytes_per_step": win_read,
        "decode_bytes_per_step": sum(by_part.values()),
        "decode_bytes_by_part": by_part,
    }
