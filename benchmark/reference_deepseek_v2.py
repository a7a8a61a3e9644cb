"""Plain reference of the DeepSeek-V2 decoder (multi-head latent attention,
a leading dense layer, routed experts of which a share is held, shared
experts), independent of the code under test.

The forward pass of ``DeepSeek-V2-Lite``'s published description
(``model_type`` ``deepseek_v2``, arXiv:2405.04434), written out in
straightforward ``jax.numpy``, float32 at the highest matmul precision: no
kernel, no cache, no absorption. Attention is the EXPANDED form at every
position: keys and values per head out of the latent through ``wkv_b``,
queries and keys ``nope + rope`` wide, computed a block of queries at a
time so that a forward over a few thousand tokens fits beside the served
model. The router is float32, as published. It is given the same expert
share as the program (``cfg.experts_held``): gates are the softmax over ALL
router outputs at the chosen experts, not renormalised, and what the absent
experts would add is left out. Weights come in as the program stores them
and are widened to float32 here.

``cfg`` is anything with the configuration's numbers as attributes
(``nnstreamer_tpu.models.mla.MLAConfig`` has them all).

Departures from the published code, none in the equations:
- rotary pairs are half-split (dim ``i`` turns with dim ``i + rope / 2``);
  the checkpoint stores them interleaved and the published code
  de-interleaves them first: storage, with seeded weights;
- norm scales are the effective scale;
- ``wq`` is ``[d, heads, nope + rope]``, ``wkv_b`` ``[rank, heads, nope +
  v]``, ``wo`` ``[heads, v, d]`` (the checkpoint's matrices, reshaped), the
  two shared experts one gated MLP of twice the width (as published).

What the program under test does differently, each within the limits of the
cell's check (``workloads/dsv2lite_longctx_closed.json`` ``tolerances``):
activations, weights and the cached row in bfloat16; router operands in
bfloat16 with float32 sums (a near tie between the sixth and seventh expert
may fall the other way); decode attention in the absorbed order of sums,
with the absorbed query rounded to bfloat16.

The keyword arguments of :func:`deepseek_v2_check` are the WRONG models of
``benchmark/controls_deepseek_v2.py``, kept to show that the comparison
tells them from the right one.
"""

from __future__ import annotations

import math

from benchmark.reference_hybrid import _f32, _gated, _rmsnorm

#: queries attended over at a time
QUERY_BLOCK = 512


def mscale(factor: float, m: float) -> float:
    return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0


def frequencies(cfg, plain: bool = False):
    """The angle a position turns each rotary pair by: ``theta ** (-2 i /
    dim)``, and with YaRN that times ``(1 - ramp_i) + ramp_i / factor``,
    the ramp rising from the pair that turns ``beta_fast`` times over the
    original window to the pair that turns ``beta_slow`` times."""
    import jax.numpy as jnp

    dim = cfg.qk_rope_dim
    i = jnp.arange(dim // 2, dtype=jnp.float32)
    f = cfg.rope_theta ** (-2.0 * i / dim)
    if plain or cfg.rope_factor == 1:
        return f

    def pair(turns):
        return dim * math.log(cfg.rope_original_max / (2 * math.pi * turns)) \
            / (2 * math.log(cfg.rope_theta))

    low = max(math.floor(pair(cfg.rope_beta_fast)), 0)
    high = min(math.ceil(pair(cfg.rope_beta_slow)), dim - 1)
    ramp = jnp.clip((i - low) / max(high - low, 0.001), 0.0, 1.0)
    return f * ((1.0 - ramp) + ramp / cfg.rope_factor)


def rotate(x, freqs, factor: float = 1.0):
    """Positions 0.. on all dims of ``x [s, heads, dim]``, half-split."""
    import jax.numpy as jnp

    half = x.shape[-1] // 2
    angle = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * freqs
    cos, sin = (jnp.cos(angle)[:, None] * factor,
                jnp.sin(angle)[:, None] * factor)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _latent_attention(h, lp, cfg, no_mscale=False, plain_rotary=False,
                      no_kv_norm=False):
    """``(out [s, d], rows [s, rank + rope])``: causal attention in the
    expanded form, and the rows ``[c | k_r]`` a cache would hold."""
    import jax
    import jax.numpy as jnp

    s = h.shape[0]
    rank, nope = cfg.kv_lora_rank, cfg.qk_nope_dim
    freqs = frequencies(cfg, plain_rotary)
    turn = mscale(cfg.rope_factor, cfg.rope_mscale) \
        / mscale(cfg.rope_factor, cfg.rope_mscale_all_dim)
    q = jnp.einsum("sd,dhc->shc", h, _f32(lp["wq"]))
    q_nope, q_rope = q[..., :nope], rotate(q[..., nope:], freqs, turn)
    ckr = h @ _f32(lp["wkv_a"])
    c = ckr[:, :rank] if no_kv_norm \
        else _rmsnorm(ckr[:, :rank], lp["kv_norm"], cfg.rms_eps)
    k_r = rotate(ckr[:, None, rank:], freqs, turn)               # [s,1,rope]
    kv = jnp.einsum("sr,rhc->shc", c, _f32(lp["wkv_b"]))
    k_nope, v = kv[..., :nope], kv[..., nope:]
    scale = (nope + cfg.qk_rope_dim) ** -0.5
    if not no_mscale:
        scale *= mscale(cfg.rope_factor, cfg.rope_mscale_all_dim) ** 2
    out = []
    for lo in range(0, s, QUERY_BLOCK):
        hi = min(lo + QUERY_BLOCK, s)
        scores = (jnp.einsum("qhc,shc->hqs", q_nope[lo:hi], k_nope[:hi])
                  + jnp.einsum("qhc,sc->hqs", q_rope[lo:hi],
                               k_r[:hi, 0])) * scale
        causal = jnp.arange(lo, hi)[:, None] >= jnp.arange(hi)[None, :]
        probs = jax.nn.softmax(jnp.where(causal, scores, -1e30), axis=-1)
        out.append(jnp.einsum("hqs,shc->qhc", probs, v[:hi]))
    a = jnp.concatenate(out)
    return (jnp.einsum("shc,hcd->sd", a, _f32(lp["wo"])),
            jnp.concatenate([c, k_r[:, 0]], axis=-1))


def routed_experts(h, lp, cfg, held=None, renormalise=False):
    """``sum_e gate_e . expert_e(h)`` over the chosen experts that are held
    (``held = (lo, hi)``, default ``cfg.experts_held``); ``h [s, d]``. The
    gates are the softmax over all router outputs at the chosen experts,
    times ``routed_scaling_factor``. ``renormalise`` is the WRONG layer
    (gates renormalised over the chosen)."""
    import jax
    import jax.numpy as jnp

    lo, hi = cfg.experts_held if held is None else held
    p = jax.nn.softmax(h @ _f32(lp["router"]), axis=-1)          # [s, E]
    gates, choice = jax.lax.top_k(p, cfg.experts_per_token)
    if renormalise:
        gates = gates / gates.sum(-1, keepdims=True)
    gates = gates * cfg.routed_scaling_factor
    first = cfg.experts_held[0]  # the weights hold experts first..

    def one(out, e):
        gate = jnp.sum(jnp.where(choice == e, gates, 0.0), axis=-1)
        y = _gated(h, lp["w_in"][e - first], lp["w_out"][e - first])
        return out + gate[:, None] * y, None

    out, _ = jax.lax.scan(one, jnp.zeros_like(h), jnp.arange(lo, hi))
    return out


def deepseek_v2_hidden(params, tokens, cfg, renormalise=False,
                       no_shared=False, **attention):
    """The residual stream after the last layer, ``[s, d]``, and every
    layer's cache rows ``[layers, s, rank + rope]``."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        x = _f32(params["embed"][tokens])
        rows = []
        for lp in params["layers"]:
            out, row = _latent_attention(
                _rmsnorm(x, lp["ln1"], cfg.rms_eps), lp, cfg, **attention)
            rows.append(row)
            x = x + out
            h = _rmsnorm(x, lp["ln2"], cfg.rms_eps)
            if "dense_in" in lp:
                x = x + _gated(h, lp["dense_in"], lp["dense_out"])
                continue
            x = x + routed_experts(h, lp, cfg, renormalise=renormalise)
            if not no_shared:
                x = x + _gated(h, lp["shared_in"], lp["shared_out"])
        return x, jnp.stack(rows)


def deepseek_v2_check(params, tokens, first, count: int, stop, cfg, **wrong):
    """``(logprobs [count, vocab], {"rows": [layers, s, width]})``: the
    log-probabilities of the token after each of the positions ``first ..
    first + count - 1`` of ``tokens`` (int32 ``[s]``; causal, so what
    follows a position does not matter to it), and every position's cache
    rows (``stop`` is not needed: a row is its own position's)."""
    import jax

    del stop
    with jax.default_matmul_precision("highest"):
        x, rows = deepseek_v2_hidden(params, tokens, cfg, **wrong)
        x = jax.lax.dynamic_slice_in_dim(x, first, count)
        x = _rmsnorm(x, params["ln_f"], cfg.rms_eps)
        return (jax.nn.log_softmax(x @ _f32(params["lm_head"]).T),
                {"rows": rows})


def deepseek_v2_logprobs(params, tokens, first, count: int, cfg, **wrong):
    """The log-probabilities of ``deepseek_v2_check`` alone."""
    return deepseek_v2_check(params, tokens, first, count, 0, cfg, **wrong)[0]
