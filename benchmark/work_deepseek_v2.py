"""Bytes a decode step of the DeepSeek-V2 configuration has to move through
HBM, from the shapes of its parameters and from the program's own counters:
the numerators of its memory roofline shares. Kept with the benchmark so
that no PR that claims a gain can change them.

Every weight is counted at the width the engine HOLDS it, an expert only if
the program's counter says a token reached it (``moe_experts_hit``), and the
latent rows by the blocks the program's counter says a step's attention had
to read (``kv_blocks_live``) at the ``kv_lora_rank + qk_rope_head_dim``
values a row the mathematics needs, not at the width the arena tiles them
to: no share can read over 100 %."""

from __future__ import annotations

from benchmark.work_hybrid import EXPERT_LEAVES, MOE_FIXED_LEAVES, _nbytes

#: a layer's leaves by the part of the step that has to read them
MLA_LEAVES = ("wq", "wkv_a", "kv_norm", "wkv_b", "wo")
DENSE_LEAVES = ("dense_in", "dense_out")


def param_bytes(params) -> dict:
    """Held bytes of the parameter tree by part: ``mla`` (the mixers),
    ``dense`` (the leading layers' MLPs), ``moe_fixed`` (routers and shared
    experts), ``experts`` (all held experts), ``one_expert`` (one expert of
    one layer), ``expert_layers``, ``head`` (read whole once a step),
    ``embed`` (a step reads one row a lane) and ``norms``."""
    out = {"mla": 0, "dense": 0, "moe_fixed": 0, "experts": 0, "norms": 0,
           "one_expert": 0, "expert_layers": 0}
    for lp in params["layers"]:
        for name, leaf in lp.items():
            part = ("mla" if name in MLA_LEAVES else
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


def row_bytes(cfg) -> int:
    """One token's latent rows, all layers, at the values the mathematics
    needs (``[c | k_r]``) in the cache's width."""
    import numpy as np

    return cfg.n_layers * (cfg.kv_lora_rank + cfg.qk_rope_dim) \
        * np.dtype(cfg.dtype).itemsize


def decode_bytes_per_step(params, cfg, lanes_live: float,
                          experts_hit_per_layer: float,
                          blocks_read_per_step: float,
                          block_tokens: int) -> dict:
    """The least one decode step has to move, by mechanism:

    - ``latent_read_bytes_per_step``: the latent rows of the blocks a
      step's attention has to read (``blocks_read_per_step``: the mean of
      the program's ``kv_blocks_live`` a step, every lane's live blocks),
      in every layer, once: what the paged kernel alone has to move;
    - ``mla_bytes_per_step``: that, the mixers' weights once and one row a
      live lane and layer written;
    - ``moe_bytes_per_step``: routers and shared experts once, and each
      held expert that received a token once (``experts_hit_per_layer``:
      their mean number an expert layer and step, counted by the program);
    - ``decode_bytes_per_step``: both of these, the leading dense layers'
      MLPs, the output head and the norms once, one embedding row a live
      lane. Activations are left out as negligible.

    ``decode_bytes_by_part`` splits the last by leaf group, for
    ``PERF.md``."""
    parts = param_bytes(params)
    rows = row_bytes(cfg)
    read = rows * float(blocks_read_per_step) * int(block_tokens)
    written = rows * float(lanes_live)
    experts = parts["one_expert"] * float(experts_hit_per_layer) \
        * parts["expert_layers"]
    by_part = {
        "experts_hit": experts, "moe_fixed": float(parts["moe_fixed"]),
        "mla_weights": float(parts["mla"]), "latent_read": read,
        "latent_written": written, "dense_ffn": float(parts["dense"]),
        "head": float(parts["head"]), "norms": float(parts["norms"]),
        "embed_rows": parts["embed"] / cfg.vocab * float(lanes_live),
    }
    return {
        "latent_read_bytes_per_step": read,
        "mla_bytes_per_step": parts["mla"] + read + written,
        "moe_bytes_per_step": parts["moe_fixed"] + experts,
        "decode_bytes_per_step": sum(by_part.values()),
        "decode_bytes_by_part": by_part,
    }
