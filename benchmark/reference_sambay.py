"""Plain reference of the SambaY decoder (``Phi-4-mini-flash-reasoning``,
``model_type`` ``phi4flash``; arXiv:2507.06607, differential attention
arXiv:2410.05258), independent of the code under test.

Every layer at every position, written out in straightforward ``jax.numpy``,
float32 at the highest matmul precision: no kernel, no cache, no two-stage
prefill, no page by pair, nothing of ``nnstreamer_tpu/models`` or
``nnstreamer_tpu/ops``. The selective scan is a sequential scan over time;
attention is two explicit softmaxes a pair, a block of queries at a time
against every key under the mask. The stored (bfloat16) leaves are widened
where they are used, layer by layer, so that 3.85 G parameters fit beside
the reference on the chip. The equations (``d`` the hidden size, ``N`` the
number of layers, ``l`` a layer's index):

- ``x = E[token]`` (no scale, no positions anywhere); every layer ``x = x +
  mixer_l(LN1(x))``, ``x = x + MLP(LN2(x))``, LN a LayerNorm with scale and
  bias; then the final LN and ``logits = x E^T`` (tied);
- MLP: ``(g, v) = split(h W1)``, ``(v * silu(g)) W2``;
- ``l`` even, ``l <= N/2``: Mamba-1. ``(xr, z) = split(h W_in)``; ``xc =
  silu(conv1d_causal(xr) + b)`` (depthwise, width 4); ``(dr, B, C) =
  split(xc W_x)``; ``dt = softplus(dr W_dt + b_dt)``; ``A = -exp(A_log)``;
  ``S_t = exp(dt_t[:, None] A) S_{t-1} + (dt_t xc_t)[:, None] B_t[None,
  :]``; ``y_t = S_t C_t + D xc_t``; ``out = (y silu(z)) W_out``. Layer
  ``N/2`` also publishes ``m_t = y_t`` (BEFORE the gate) as the token's
  memory;
- ``l`` odd, ``l < N/2``: differential attention over a window (query ``i``
  sees key ``j`` iff ``0 <= i - j < window``); ``l = N/2 + 1``: the same,
  causal with no window. ``q, k, v = split(h Wqkv + b)``, heads of
  ``head_dim``, scale ``head_dim ** -0.5``. Query pair ``p`` is heads ``(2p,
  2p + 1) = (q1, q2)`` and reads key-value pair ``c = p // (query pairs /
  key-value pairs)``: ``k1 = k[2c]``, ``k2 = k[2c + 1]``, ``V = [v[2c] |
  v[2c + 1]]``. ``A1 = softmax(q1 k1^T) V``, ``A2 = softmax(q2 k2^T) V``;
  ``lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda0(l)``, ``lambda0(l) =
  0.8 - 0.6 exp(-0.3 l)``; ``o_p = (1 - lambda0(l)) RMSNorm(A1 - lambda A2;
  g)``; ``out = concat_p(o_p) W_o + b_o``;
- ``l`` even, ``l > N/2``: gated memory unit, ``(silu(h W1) * m) W2`` with
  ``m`` layer ``N/2``'s memory of the same token;
- ``l`` odd, ``l > N/2 + 1``: cross attention: its own ``W_q`` + bias,
  ``W_o``, lambda vectors and pair norm, ``lambda0`` at its own ``l``; keys
  and values are layer ``N/2 + 1``'s.

``cfg`` is anything with the configuration's numbers as attributes
(``nnstreamer_tpu.models.sambay.SambaYConfig`` has them all). ``A_log`` is
stored state-major ``[state, inner]`` (the published ``[inner, state]``
transposed).

What the program under test does differently, each within the limits of the
cell's check (``workloads/phi4flash_reason_closed.json`` ``tolerances``):
activations, weights and cached keys and values in bfloat16; ``A1`` and
``A2`` each rounded to bfloat16 before they are subtracted; the scan in
chunks.

The keyword arguments of :func:`sambay_check` are the WRONG models of
``benchmark/controls_sambay.py``, kept to show that the comparison tells
them from the right one. Each may be a traced boolean: one compiled program
then serves every control.
"""

from __future__ import annotations

#: queries attended over at a time
QUERY_BLOCK = 256

#: the wrong models :func:`sambay_check` takes, each a keyword
WRONG = (
    "no_diff", "no_pair_norm", "no_lambda_scale", "wrong_lambda_layer",
    "no_window", "half_window", "window_off_by_one", "memory_after_gate",
    "stale_memory", "no_gmu_silu", "cross_reads_window", "no_D",
    "no_dt_bias")


def _f32(x):
    import jax.numpy as jnp

    return x.astype(jnp.float32)


def layernorm(x, scale, bias, eps):
    import jax.numpy as jnp

    mean = x.mean(-1, keepdims=True)
    var = jnp.square(x - mean).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * scale + bias


def layer_kind(i: int, n_layers: int) -> str:
    half = n_layers // 2
    if i % 2 == 0:
        return "mamba" if i <= half else "gmu"
    return "window" if i < half else "full" if i == half + 1 else "cross"


def mlp(h, lp):
    import jax
    import jax.numpy as jnp

    g, v = jnp.split(h @ _f32(lp["mlp_in"]), 2, axis=-1)
    return (v * jax.nn.silu(g)) @ _f32(lp["mlp_out"])


def mamba(h, lp, cfg, stop, wrong):
    """The Mamba-1 mixer over a whole sequence ``h [s, d]``, one token at a
    time: ``(out [s, d], memory [s, inner], S [inner, state], tail [conv -
    1, inner])``, the state after ``stop`` tokens and the rows of the
    convolution's input that end there."""
    import jax
    import jax.numpy as jnp

    n, w, rank = cfg.ssm_state, cfg.ssm_conv, cfg.dt_rank
    s = h.shape[0]
    xr, z = jnp.split(h @ _f32(lp["ssm_in"]), 2, axis=-1)
    shifted = jnp.concatenate([jnp.zeros((w - 1, xr.shape[1])), xr])
    xc = jax.nn.silu(sum(shifted[i:i + s] * lp["conv_w"][i]
                         for i in range(w)) + lp["conv_b"])
    low, bm, cm = jnp.split(xc @ _f32(lp["x_proj"]), [rank, rank + n],
                            axis=-1)
    dt = jax.nn.softplus(low @ _f32(lp["dt_proj"]) + jnp.where(
        wrong["no_dt_bias"], 0.0, lp["dt_bias"]))
    a = -jnp.exp(lp["A_log"]).T                               # [inner, n]

    def token(carry, t):
        state, at_stop = carry
        i, x_t, b_t, c_t, d_t = t
        state = jnp.exp(d_t[:, None] * a) * state \
            + (d_t * x_t)[:, None] * b_t[None, :]
        at_stop = jnp.where(i == stop - 1, state, at_stop)
        return (state, at_stop), state @ c_t

    zero = jnp.zeros(a.shape)
    (_, at_stop), y = jax.lax.scan(token, (zero, zero),
                                   (jnp.arange(s), xc, bm, cm, dt))
    y = y + jnp.where(wrong["no_D"], 0.0, lp["D"]) * xc
    gated = y * jax.nn.silu(z)
    return (gated @ _f32(lp["ssm_out"]),
            jnp.where(wrong["memory_after_gate"], gated, y), at_stop,
            jax.lax.dynamic_slice_in_dim(shifted, stop, w - 1))


def softmax_rows(q, k, v, window):
    """``softmax(q k^T) v`` head by head under the causal mask and, with a
    ``window`` (it may be traced), the band: ``q [s, heads, c]``, ``k [s,
    heads, c]``, ``v [s, heads, cv]``, a block of queries at a time (a
    loop, so that the program stays small)."""
    import jax
    import jax.numpy as jnp

    s = q.shape[0]
    blocks = -(-s // QUERY_BLOCK)
    qp = jnp.pad(q, ((0, blocks * QUERY_BLOCK - s), (0, 0), (0, 0)))
    kj = jnp.arange(s)[None, :]

    def one(lo):
        scores = jnp.einsum(
            "qhc,shc->hqs", jax.lax.dynamic_slice_in_dim(qp, lo,
                                                         QUERY_BLOCK), k)
        qi = (lo + jnp.arange(QUERY_BLOCK))[:, None]
        seen = (qi >= kj) & (qi - kj < window)
        probs = jax.nn.softmax(jnp.where(seen, scores, -1e30), axis=-1)
        return jnp.einsum("hqs,shc->qhc", probs, v)

    out = jax.lax.map(one, jnp.arange(blocks) * QUERY_BLOCK)
    return out.reshape((blocks * QUERY_BLOCK,) + out.shape[2:])[:s]


def differential(q, k, v, lp, layer, window, cfg, wrong):
    """Differential attention of one layer from its projections ``q [s,
    heads, c]``, ``k``/``v [s, kv heads, c]``: the output before ``W_o``,
    ``[s, heads c]``."""
    import jax.numpy as jnp

    s, heads, c = q.shape
    pairs, group = heads // 2, (heads // 2) // (k.shape[1] // 2)
    scale = c ** -0.5
    # pair p reads key-value pair p // group
    at = jnp.arange(pairs) // group
    k1, k2 = k[:, 0::2][:, at], k[:, 1::2][:, at]              # [s,pairs,c]
    values = jnp.concatenate([v[:, 0::2], v[:, 1::2]], axis=-1)[:, at]
    a1 = softmax_rows(q[:, 0::2] * scale, k1, values, window)
    a2 = softmax_rows(q[:, 1::2] * scale, k2, values, window)
    lam0 = 0.8 - 0.6 * jnp.exp(-0.3 * jnp.where(
        wrong["wrong_lambda_layer"], layer // 2, layer))
    lam = jnp.exp(jnp.sum(lp["lam_q1"] * lp["lam_k1"])) \
        - jnp.exp(jnp.sum(lp["lam_q2"] * lp["lam_k2"])) + lam0
    d = a1 - jnp.where(wrong["no_diff"], 0.0, lam) * a2
    normed = d / jnp.sqrt(jnp.square(d).mean(-1, keepdims=True)
                          + cfg.ln_eps) * lp["sub_norm"]
    d = jnp.where(wrong["no_pair_norm"], d, normed)
    d = jnp.where(wrong["no_lambda_scale"], 1.0, 1.0 - lam0) * d
    return d.reshape(s, pairs * 2 * c)


def sambay_hidden(params, tokens, cfg, stop=0, **wrong):
    """The residual stream after the last layer, ``[s, d]``, and what the
    layers leave behind: ``{"kv": [1, 2, s, kv heads, c]`` (the full
    layer's keys and values), ``"win": [window layers, 2, s, kv heads, c],
    "ssm": [mamba layers, inner, state]`` and ``"conv": [mamba layers, conv
    - 1, inner]`` after ``stop`` tokens``}``."""
    import jax
    import jax.numpy as jnp

    unknown = set(wrong) - set(WRONG)
    if unknown:
        raise TypeError(f"sambay_hidden: no wrong model {sorted(unknown)}")
    wrong = {name: jnp.asarray(wrong.get(name, False)) for name in WRONG}
    heads, hk, c = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    everything = 1 << 30
    window = jnp.where(
        wrong["no_window"], everything,
        jnp.where(wrong["half_window"], cfg.window // 2,
                  jnp.where(wrong["window_off_by_one"], cfg.window + 1,
                            cfg.window)))

    def split_heads(u, n):
        return u.reshape(u.shape[0], n, c)

    with jax.default_matmul_precision("highest"):
        x = _f32(params["embed"][tokens])
        s = x.shape[0]
        win, ssm, conv = [], [], []
        memory = full = last_window = None
        for i, lp in enumerate(params["layers"]):
            kind = layer_kind(i, cfg.n_layers)
            h = layernorm(x, lp["ln1"], lp["ln1_b"], cfg.ln_eps)
            if kind == "mamba":
                out, memory, state, tail = mamba(h, lp, cfg, stop, wrong)
                ssm.append(state)
                conv.append(tail)
            elif kind == "gmu":
                u = h @ _f32(lp["gmu_in"])
                m = jnp.where(wrong["stale_memory"], jnp.concatenate(
                    [jnp.zeros_like(memory[:1]), memory[:-1]]), memory)
                out = (jnp.where(wrong["no_gmu_silu"], u, jax.nn.silu(u))
                       * m) @ _f32(lp["gmu_out"])
            else:
                if kind == "cross":
                    q = split_heads(h @ _f32(lp["wq"]) + lp["bq"], heads)
                    # the wrong model: the LAST window layer's rows, of
                    # which the window's are there to read
                    k, v = (jnp.where(wrong["cross_reads_window"], w, f)
                            for w, f in zip(last_window, full))
                    seen = jnp.where(wrong["cross_reads_window"], window,
                                     everything)
                else:
                    q, k, v = jnp.split(
                        h @ _f32(lp["wqkv"]) + lp["bqkv"],
                        [heads * c, (heads + hk) * c], axis=-1)
                    q, k, v = (split_heads(q, heads), split_heads(k, hk),
                               split_heads(v, hk))
                    if kind == "window":
                        seen, last_window = window, (k, v)
                        win.append(jnp.stack([k, v]))
                    else:
                        seen, full = everything, (k, v)
                out = differential(q, k, v, lp, i, seen, cfg, wrong) \
                    @ _f32(lp["wo"]) + lp["bo"]
            x = x + out
            x = x + mlp(layernorm(x, lp["ln2"], lp["ln2_b"], cfg.ln_eps),
                        lp)
        return x, {"kv": jnp.stack(full)[None], "win": jnp.stack(win),
                   "ssm": jnp.stack(ssm), "conv": jnp.stack(conv)}


def sambay_check(params, tokens, first, count: int, stop, cfg, **wrong):
    """``(logprobs [count, vocab], left)``: the log-probabilities of the
    token after each of the positions ``first .. first + count - 1`` of
    ``tokens`` (int32 ``[s]``; causal, so what follows a position does not
    matter to it), and what the layers leave behind (``sambay_hidden``: the
    rows of every position, the states after the first ``stop`` tokens)."""
    import jax

    with jax.default_matmul_precision("highest"):
        x, left = sambay_hidden(params, tokens, cfg, stop, **wrong)
        x = jax.lax.dynamic_slice_in_dim(x, first, count)
        x = layernorm(x, params["ln_f"], params["ln_f_b"], cfg.ln_eps)
        return jax.nn.log_softmax(x @ _f32(params["embed"]).T), left


def sambay_logprobs(params, tokens, first, count: int, cfg, **wrong):
    """The log-probabilities of ``sambay_check`` alone."""
    return sambay_check(params, tokens, first, count, 0, cfg, **wrong)[0]
