"""Plain reference of the hybrid decoder (state-space and attention mixers,
routed experts of which a share is held, one shared gated MLP), independent
of the code under test.

The forward pass of ``granite-4.0-h-small``'s published description
(``model_type`` ``granitemoehybrid``), written out in straightforward
``jax.numpy``, float32 at the highest matmul precision: no kernel, no cache,
no batching, no chunking. The state-space recurrence is the SEQUENTIAL scan
over tokens, one state update a token, where the program under test
computes a prompt in chunks and decodes against a stored state. It is given
the same expert share as the program (``cfg.experts_held``): gates are the
softmax over the chosen experts, not renormalised over the held ones, and
what the absent experts would add is left out. Weights come in as the
program stores them and are widened to float32 here.

``cfg`` is anything with the configuration's numbers as attributes
(``nnstreamer_tpu.models.hybrid.HybridConfig`` has them all).

Departures from the published description: none in the equations. The
recurrent state is float32 (the published code keeps it in the activations'
precision unless told otherwise); the step ``softplus(dt + dt_bias)`` has no
upper or lower limit (the published limits are 0 and infinity, which limit
nothing).
"""

from __future__ import annotations


def _f32(x):
    import jax.numpy as jnp

    return x.astype(jnp.float32)


def _rmsnorm(x, scale, eps):
    import jax
    import jax.numpy as jnp

    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale


def _gated(h, w_in, w_out):
    import jax
    import jax.numpy as jnp

    a, b = jnp.split(h @ _f32(w_in), 2, axis=-1)
    return (jax.nn.silu(a) * b) @ _f32(w_out)


def routed_experts(h, lp, cfg, held=None, renormalise_held=False):
    """``sum_e gate_e . expert_e(h)`` over the chosen experts that are held
    (``held = (lo, hi)``, default ``cfg.experts_held``); ``h [s, d]``.
    ``renormalise_held`` is the WRONG layer (gates renormalised over the
    held experts), kept to show that the comparison tells the two apart."""
    import jax
    import jax.numpy as jnp

    lo, hi = cfg.experts_held if held is None else held
    logits = h @ _f32(lp["router"])                              # [s, E]
    top, choice = jax.lax.top_k(logits, cfg.experts_per_token)
    gates = jax.nn.softmax(top, axis=-1)                         # [s, k]
    if renormalise_held:
        mine = (choice >= lo) & (choice < hi)
        gates = jnp.where(mine, gates, 0.0)
        gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-30)
    first = cfg.experts_held[0]  # the weights hold experts first..

    def one(out, e):
        gate = jnp.sum(jnp.where(choice == e, gates, 0.0), axis=-1)
        y = _gated(h, lp["w_in"][e - first], lp["w_out"][e - first])
        return out + gate[:, None] * y, None

    out, _ = jax.lax.scan(one, jnp.zeros_like(h), jnp.arange(lo, hi))
    return out


def _mamba(h, lp, cfg, stop):
    """The Mamba-2 mixer over a whole sequence ``h [s, d]``, one token at
    a time: ``(out [s, d], S, tail)`` with ``S [heads, head_dim, state]``
    the recurrent state after ``stop`` tokens and ``tail`` the ``conv - 1``
    rows of the convolution's input that end there."""
    import jax
    import jax.numpy as jnp

    heads, p, n, w = (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state,
                      cfg.ssm_conv)
    inner = heads * p
    s = h.shape[0]
    z, xbc, dt = jnp.split(h @ _f32(lp["ssm_in"]),
                           [inner, 2 * inner + 2 * n], axis=-1)
    shifted = jnp.concatenate([jnp.zeros((w - 1, xbc.shape[1])), xbc])
    conv = sum(shifted[i:i + s] * lp["conv_w"][i] for i in range(w))
    xbc = jax.nn.silu(conv + lp["conv_b"])
    x, bm, cm = jnp.split(xbc, [inner, inner + n], axis=-1)
    x = x.reshape(s, heads, p)
    step = jax.nn.softplus(dt + lp["dt_bias"])                   # [s, h]
    a = -jnp.exp(lp["A_log"])

    def token(carry, t):
        state, at_stop = carry
        i, x_t, b_t, c_t, d_t = t
        state = state * jnp.exp(d_t * a)[:, None, None] \
            + (d_t[:, None] * x_t)[:, :, None] * b_t[None, None, :]
        at_stop = jnp.where(i == stop - 1, state, at_stop)
        return (state, at_stop), state @ c_t                     # [h, p]

    zero = jnp.zeros((heads, p, n))
    (_, at_stop), y = jax.lax.scan(token, (zero, zero),
                                   (jnp.arange(s), x, bm, cm, step))
    y = y + lp["D"][:, None] * x
    y = y.reshape(s, inner) * jax.nn.silu(z)
    out = _rmsnorm(y, lp["norm"], cfg.rms_eps) @ _f32(lp["ssm_out"])
    return out, at_stop, jax.lax.dynamic_slice_in_dim(shifted, stop, w - 1)


def _attention(h, lp, cfg):
    """Causal softmax attention, no positional encoding: query head ``i``
    reads key-value head ``i // (heads / kv heads)``."""
    import jax
    import jax.numpy as jnp

    s = h.shape[0]
    q = jnp.einsum("sd,dhc->shc", h, _f32(lp["wq"]))
    k = jnp.einsum("sd,dhc->shc", h, _f32(lp["wk"]))
    v = jnp.einsum("sd,dhc->shc", h, _f32(lp["wv"]))
    group = cfg.n_heads // cfg.n_kv_heads
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    scores = jnp.einsum("qhc,shc->hqs", q, k) * cfg.attention_scale
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    probs = jax.nn.softmax(jnp.where(causal, scores, -1e30), axis=-1)
    return jnp.einsum("shc,hcd->sd", jnp.einsum("hqs,shc->qhc", probs, v),
                      _f32(lp["wo"]))


def hybrid_hidden(params, tokens, cfg, renormalise_held=False, stop=0):
    """The residual stream after the last layer, ``[s, d]``, and what the
    state-space layers hold after ``stop`` tokens: ``{"ssm": [layers,
    heads, head_dim, state], "conv": [layers, conv - 1, conv_dim]}``."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        x = cfg.embedding_multiplier * _f32(params["embed"][tokens])
        ssm, conv = [], []
        for kind, lp in zip(cfg.layer_types, params["layers"]):
            h = _rmsnorm(x, lp["ln1"], cfg.rms_eps)
            if kind == "mamba":
                out, state, tail = _mamba(h, lp, cfg, stop)
                ssm.append(state)
                conv.append(tail)
            else:
                out = _attention(h, lp, cfg)
            x = x + cfg.residual_multiplier * out
            h = _rmsnorm(x, lp["ln2"], cfg.rms_eps)
            x = x + cfg.residual_multiplier * (
                routed_experts(h, lp, cfg, renormalise_held=renormalise_held)
                + _gated(h, lp["shared_in"], lp["shared_out"]))
        return x, {"ssm": jnp.stack(ssm), "conv": jnp.stack(conv)}


def hybrid_check(params, tokens, first, count: int, stop, cfg,
                 renormalise_held=False):
    """``(logprobs [count, vocab], state)``: the log-probabilities of the
    token after each of the positions ``first .. first + count - 1`` of
    ``tokens`` (int32 ``[s]``; causal, so what follows a position does not
    matter to it), and the state-space layers' state after the first
    ``stop`` tokens (``hybrid_hidden``)."""
    import jax

    with jax.default_matmul_precision("highest"):
        x, state = hybrid_hidden(params, tokens, cfg, renormalise_held, stop)
        x = jax.lax.dynamic_slice_in_dim(x, first, count)
        x = _rmsnorm(x, params["ln_f"], cfg.rms_eps)
        return jax.nn.log_softmax(
            x @ _f32(params["embed"]).T / cfg.logits_scaling), state


def hybrid_logprobs(params, tokens, first, count: int, cfg,
                    renormalise_held=False):
    """The log-probabilities of ``hybrid_check`` alone."""
    return hybrid_check(params, tokens, first, count, 0, cfg,
                        renormalise_held)[0]
