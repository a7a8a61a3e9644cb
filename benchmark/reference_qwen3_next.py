"""Plain reference of the Qwen3-Next decoder (gated delta rule and gated
attention mixers, routed experts of which a share is held, one gated shared
expert), independent of the code under test.

The forward pass of ``Qwen3-Next-80B-A3B-Instruct``'s published description
(``model_type`` ``qwen3_next``), written out in straightforward
``jax.numpy``, float32 at the highest matmul precision: no kernel, no cache,
no batching, no chunking. The delta rule is the SEQUENTIAL recurrence, one
state update a token (decay, read, correct, write, then the output), where
the program under test solves a prompt chunk by chunk and decodes against a
stored state. The expert layer is ``reference_hybrid``'s (the same
semantics: gates are the softmax over the chosen experts, which is the
published softmax over all, top-10, renormalised; the same share
``cfg.experts_held``, what the absent experts would add left out). Weights
come in as the program stores them and are widened to float32 here.

``cfg`` is anything with the configuration's numbers as attributes
(``nnstreamer_tpu.models.hybrid.HybridConfig`` has them all).

Departures from the published code, none in the equations:
- norm scales are the effective scale (the checkpoint stores ``scale - 1``
  for all but the mixer's gated norm): storage, with seeded weights;
- ``la_in`` is laid out ``[q | k | v | z]`` and ``la_ba`` ``[b | a]``, whole
  blocks side by side (the checkpoint interleaves them by key head), and the
  attention's query and gate are two leaves (``wq``, ``wg``; the checkpoint
  interleaves them by head): storage;
- the recurrent state is float32 (the published kernels keep it so too);
- the multi-token-prediction module is left out: a drafter, no part of the
  forward pass.

The keyword arguments of :func:`qwen3_next_check` are the WRONG models of
``benchmark/controls_qwen3_next.py``, kept to show that the comparison
tells them from the right one.
"""

from __future__ import annotations

from benchmark.reference_hybrid import _f32, _gated, _rmsnorm, routed_experts


def _l2norm(x):
    import jax
    import jax.numpy as jnp

    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def _delta_mixer(h, lp, cfg, stop, beta_one=False):
    """The gated delta rule mixer over a whole sequence ``h [s, d]``, one
    token at a time: ``(out [s, d], S, tail)`` with ``S [value heads, key,
    value]`` the state after ``stop`` tokens and ``tail`` the ``conv - 1``
    rows of the convolution's input that end there."""
    import jax
    import jax.numpy as jnp

    hk, hv, dk, dv, w = (cfg.la_key_heads, cfg.la_value_heads,
                         cfg.la_key_dim, cfg.la_value_dim, cfg.la_conv)
    s = h.shape[0]
    qkv, z = jnp.split(h @ _f32(lp["la_in"]), [2 * hk * dk + hv * dv],
                       axis=-1)
    b, a = jnp.split(h @ _f32(lp["la_ba"]), 2, axis=-1)          # [s, hv]
    shifted = jnp.concatenate([jnp.zeros((w - 1, qkv.shape[1])), qkv])
    conv = jax.nn.silu(sum(shifted[i:i + s] * lp["conv_w"][i]
                           for i in range(w)))
    q, k, v = jnp.split(conv, [hk * dk, 2 * hk * dk], axis=-1)
    q = jnp.repeat(_l2norm(q.reshape(s, hk, dk)), hv // hk, axis=1) \
        * dk ** -0.5
    k = jnp.repeat(_l2norm(k.reshape(s, hk, dk)), hv // hk, axis=1)
    v = v.reshape(s, hv, dv)
    beta = jnp.ones_like(b) if beta_one else jax.nn.sigmoid(b)
    g = -jnp.exp(lp["A_log"]) * jax.nn.softplus(a + lp["dt_bias"])

    def token(carry, t):
        state, at_stop = carry
        i, q_t, k_t, v_t, g_t, b_t = t
        state = state * jnp.exp(g_t)[:, None, None]
        read = jnp.einsum("hkv,hk->hv", state, k_t)
        state = state + k_t[:, :, None] * (b_t[:, None]
                                           * (v_t - read))[:, None, :]
        at_stop = jnp.where(i == stop - 1, state, at_stop)
        return (state, at_stop), jnp.einsum("hkv,hk->hv", state, q_t)

    zero = jnp.zeros((hv, dk, dv))
    (_, at_stop), o = jax.lax.scan(token, (zero, zero),
                                   (jnp.arange(s), q, k, v, g, beta))
    y = _rmsnorm(o, lp["norm"], cfg.rms_eps) * jax.nn.silu(
        z.reshape(s, hv, dv))
    return (y.reshape(s, hv * dv) @ _f32(lp["la_out"]), at_stop,
            jax.lax.dynamic_slice_in_dim(shifted, stop, w - 1))


def rotate(x, rotary_dim: int, theta: float):
    """Rotary positions 0.. on the first ``rotary_dim`` dims of each head
    of ``x [s, heads, dim]``: dim ``i`` turns with dim ``i + rotary_dim /
    2`` by the angle ``position . theta ** (-2 i / rotary_dim)``."""
    import jax.numpy as jnp

    half = rotary_dim // 2
    angle = jnp.arange(x.shape[0])[:, None] \
        * theta ** (-jnp.arange(half) / half)[None, :]           # [s, half]
    cos, sin = jnp.cos(angle)[:, None], jnp.sin(angle)[:, None]
    x1, x2 = x[..., :half], x[..., half:rotary_dim]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            x[..., rotary_dim:]], axis=-1)


def _gated_attention(h, lp, cfg, full_rotary=False):
    """Causal softmax attention with a norm over each query and key head,
    partial rotary positions and a sigmoid gate on its output: query head
    ``i`` reads key-value head ``i // (heads / kv heads)``."""
    import jax
    import jax.numpy as jnp

    s = h.shape[0]
    q = jnp.einsum("sd,dhc->shc", h, _f32(lp["wq"]))
    gate = jnp.einsum("sd,dhc->shc", h, _f32(lp["wg"]))
    k = jnp.einsum("sd,dhc->shc", h, _f32(lp["wk"]))
    v = jnp.einsum("sd,dhc->shc", h, _f32(lp["wv"]))
    rotary = cfg.head_dim if full_rotary else cfg.rotary_dim
    q = rotate(_rmsnorm(q, lp["q_norm"], cfg.rms_eps), rotary, cfg.rope_theta)
    k = rotate(_rmsnorm(k, lp["k_norm"], cfg.rms_eps), rotary, cfg.rope_theta)
    group = cfg.n_heads // cfg.n_kv_heads
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    scores = jnp.einsum("qhc,shc->hqs", q, k) * cfg.attention_scale
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    probs = jax.nn.softmax(jnp.where(causal, scores, -1e30), axis=-1)
    a = jnp.einsum("hqs,shc->qhc", probs, v) * jax.nn.sigmoid(gate)
    return jnp.einsum("shc,hcd->sd", a, _f32(lp["wo"]))


def qwen3_next_hidden(params, tokens, cfg, stop=0, renormalise_held=False,
                      beta_one=False, full_rotary=False):
    """The residual stream after the last layer, ``[s, d]``, and what the
    delta-rule layers hold after ``stop`` tokens: ``{"ssm": [layers, value
    heads, key, value], "conv": [layers, conv - 1, channels]}``."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        x = _f32(params["embed"][tokens])
        ssm, conv = [], []
        for kind, lp in zip(cfg.layer_types, params["layers"]):
            h = _rmsnorm(x, lp["ln1"], cfg.rms_eps)
            if kind == "linear_attention":
                out, state, tail = _delta_mixer(h, lp, cfg, stop, beta_one)
                ssm.append(state)
                conv.append(tail)
            else:
                out = _gated_attention(h, lp, cfg, full_rotary)
            x = x + out
            h = _rmsnorm(x, lp["ln2"], cfg.rms_eps)
            shared = _gated(h, lp["shared_in"], lp["shared_out"]) \
                * jax.nn.sigmoid(h @ _f32(lp["shared_gate"]))[:, None]
            x = x + routed_experts(
                h, lp, cfg, renormalise_held=renormalise_held) + shared
        return x, {"ssm": jnp.stack(ssm), "conv": jnp.stack(conv)}


def qwen3_next_check(params, tokens, first, count: int, stop, cfg, **wrong):
    """``(logprobs [count, vocab], state)``: the log-probabilities of the
    token after each of the positions ``first .. first + count - 1`` of
    ``tokens`` (int32 ``[s]``; causal, so what follows a position does not
    matter to it), and the delta-rule layers' state after the first ``stop``
    tokens (``qwen3_next_hidden``). The head is its own leaf."""
    import jax

    with jax.default_matmul_precision("highest"):
        x, state = qwen3_next_hidden(params, tokens, cfg, stop, **wrong)
        x = jax.lax.dynamic_slice_in_dim(x, first, count)
        x = _rmsnorm(x, params["ln_f"], cfg.rms_eps)
        return jax.nn.log_softmax(x @ _f32(params["lm_head"]).T), state


def qwen3_next_logprobs(params, tokens, first, count: int, cfg, **wrong):
    """The log-probabilities of ``qwen3_next_check`` alone."""
    return qwen3_next_check(params, tokens, first, count, 0, cfg, **wrong)[0]
