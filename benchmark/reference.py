"""Plain references, independent of the code under test.

``transformer_logprobs`` is the forward pass of the repo's transformer block
(pre-norm RMSNorm, fused qkv, full rotary, causal softmax attention, GELU
feed-forward, sequential residual, tied output head, no biases: the equations
of ``models/transformer.py``, written out again) in straightforward
``jax.numpy`` and float32 at the highest matmul precision, with no kernel, no
cache and no batching (one loop over the layers). It departs from the code under test only in
precision: that code computes in the configuration's dtype.
"""

from __future__ import annotations


def _rmsnorm(x, scale):
    import jax
    import jax.numpy as jnp

    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + 1e-6) * scale


def _rope(x, positions):
    import jax.numpy as jnp
    import numpy as np

    half = x.shape[-1] // 2
    freqs = jnp.exp(-np.log(10000.0) * jnp.arange(half) / half)
    angles = positions[:, None].astype(jnp.float32) * freqs       # [s, half]
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def transformer_logprobs(params, tokens, last, n_layers: int):
    """Log-probabilities of the token after position ``last`` of the
    sequence ``tokens`` (int32 [s]; causal, so what follows ``last`` does
    not matter)."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        s = tokens.shape[0]
        positions = jnp.arange(s)
        causal = positions[:, None] >= positions[None, :]
        x = params["embed"][tokens].astype(jnp.float32)           # [s, d]

        def layer(i, x):
            h = _rmsnorm(x, params["ln1"][i])
            qkv = jnp.einsum("sd,dthc->tshc", h, params["qkv"][i])
            q, k, v = _rope(qkv[0], positions), _rope(qkv[1], positions), qkv[2]
            scores = jnp.einsum("qhc,shc->hqs", q, k) * q.shape[-1] ** -0.5
            probs = jax.nn.softmax(jnp.where(causal, scores, -1e30), axis=-1)
            a = jnp.einsum("hqs,shc->qhc", probs, v)
            x = x + jnp.einsum("shc,hcd->sd", a, params["proj"][i])
            h = _rmsnorm(x, params["ln2"][i])
            h = jax.nn.gelu(jnp.einsum("sd,df->sf", h, params["w_in"][i]))
            return x + jnp.einsum("sf,fd->sd", h, params["w_out"][i])

        x = jax.lax.fori_loop(0, n_layers, layer, x)
        x = _rmsnorm(x[last], params["ln_f"])
        return jax.nn.log_softmax(jnp.einsum("d,vd->v", x, params["embed"]))
