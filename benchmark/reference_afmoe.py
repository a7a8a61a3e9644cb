"""Plain reference of the AFMoE decoder (gated grouped-query attention of
two kinds, window and full, under sandwich norms; leading dense layers;
sigmoid-scored routed experts of which a share is held, with a selection
bias; one shared expert), independent of the code under test.

The forward pass of ``Trinity-Large-Preview``'s published description
(``model_type`` ``afmoe``), written out in straightforward ``jax.numpy``,
float32 at the highest matmul precision: no kernel, no cache, no batching.
Attention is computed a block of queries at a time, against the keys the
block may see, and the MLPs a block of rows at a time, each as a loop, so
that a forward over twelve thousand tokens fits beside the served model and
its program stays small. The equations:

- ``x = E[token] * sqrt(d)``;
- every layer ``x = x + ln1_post(attn(ln1(x)))``, ``x = x + ln2_post(ffn(
  ln2(x)))``, all RMSNorm; then ``ln_f`` and the untied head;
- attention: ``q = h wq``, ``k = h wk``, ``v = h wv``, ``g = h wg``; RMSNorm
  over each query and key head BEFORE positions; a ``sliding_attention``
  layer turns queries and keys by rotary positions over the whole head
  (half-split, plain frequencies) and lets query ``i`` see key ``j`` iff
  ``0 <= i - j < window``; a ``full_attention`` layer has NO positions and
  the plain causal mask; scores ``q . k / sqrt(head_dim)``, query head ``i``
  reads key-value head ``i // group``; ``out = (softmax(scores) v *
  sigmoid(g)) wo``;
- leading layers: ``wdown(silu(wgate h) * wup h)``;
- expert layers: ``s = sigmoid(h wr)``; the chosen are ``top_k(s + b)``,
  ``b`` the selection bias, used for the choice ONLY; gates ``g_e =
  route_scale * s_e / (sum over the chosen of s + 1e-20)``; ``y = shared(h)
  + sum over the chosen of g_e expert_e(h)``. It is given the same expert
  share as the program (``cfg.experts_held``): the denominator is over ALL
  the chosen, held or not, and what the absent experts would add is left
  out.

``cfg`` is anything with the configuration's numbers as attributes
(``nnstreamer_tpu.models.afmoe.AfmoeConfig`` has them all).

Departures from the published code, none in the equations:
- rotary pairs are half-split, as published (no de-interleaving needed);
- norm scales are the effective scale (the published depth scaling of the
  sandwich norms is their initialisation);
- ``wq``/``wg`` are ``[d, heads, head_dim]``, ``wk``/``wv`` ``[d, kv heads,
  head_dim]``, ``wo`` ``[heads, head_dim, d]`` (the checkpoint's matrices,
  reshaped); ``dense_in``/``shared_in``/``w_in`` hold ``[gate | up]`` side
  by side.

What the program under test does differently, each within the limits of the
cell's check (``workloads/trinity_longctx_closed.json`` ``tolerances``):
activations, weights and cached keys and values in bfloat16; router operands
in bfloat16 with float32 sums (a near tie between the fourth and fifth
expert may fall the other way).

The keyword arguments of :func:`afmoe_check` are the WRONG models of
``benchmark/controls_afmoe.py``, kept to show that the comparison tells
them from the right one.
"""

from __future__ import annotations

from benchmark.reference_hybrid import _f32, _gated, _rmsnorm

#: queries attended over at a time, and rows an MLP takes at a time (its
#: hidden activations are ``rows x 2 x width`` float32)
QUERY_BLOCK = 256
ROW_BLOCK = 2048
SLIDING = "sliding_attention"


def rotate(x, theta: float):
    """Positions 0.. on all dims of ``x [s, heads, dim]``, half-split."""
    import jax.numpy as jnp

    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(angle)[:, None], jnp.sin(angle)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(h, lp, cfg, window, rotary: bool, no_gate=False):
    """``(out [s, d], k [s, kv heads, dim], v)``: gated grouped-query
    attention over a whole sequence; ``window`` None sees everything
    before a query, else the last ``window`` positions."""
    import jax
    import jax.numpy as jnp

    s = h.shape[0]
    q = _rmsnorm(jnp.einsum("sd,dhc->shc", h, _f32(lp["wq"])),
                 lp["q_norm"], cfg.rms_eps)
    k = _rmsnorm(jnp.einsum("sd,dhc->shc", h, _f32(lp["wk"])),
                 lp["k_norm"], cfg.rms_eps)
    v = jnp.einsum("sd,dhc->shc", h, _f32(lp["wv"]))
    if rotary:
        q, k = rotate(q, cfg.rope_theta), rotate(k, cfg.rope_theta)
    hk = k.shape[1]
    # a block of queries at a time (a loop, so that the program stays
    # small): against every key under the causal mask, or, with a window,
    # against the ``window + QUERY_BLOCK`` keys that end with the block
    blocks = -(-s // QUERY_BLOCK)
    front = 0 if window is None else window
    back = blocks * QUERY_BLOCK - s
    qp = jnp.pad(q.reshape(s, hk, cfg.n_heads // hk, cfg.head_dim),
                 ((0, back), (0, 0), (0, 0), (0, 0)))
    kp = jnp.pad(k, ((front, back), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((front, back), (0, 0), (0, 0)))
    span = kp.shape[0] if window is None else window + QUERY_BLOCK

    def one(lo):
        start = 0 if window is None else lo   # of the padded keys
        ks = jax.lax.dynamic_slice_in_dim(kp, start, span)
        vs = jax.lax.dynamic_slice_in_dim(vp, start, span)
        scores = jnp.einsum(
            "qkgc,skc->kgqs", jax.lax.dynamic_slice_in_dim(
                qp, lo, QUERY_BLOCK), ks) * cfg.head_dim ** -0.5
        qi = (lo + jnp.arange(QUERY_BLOCK))[:, None]
        kj = (start - front + jnp.arange(span))[None, :]
        seen = (kj >= 0) & (qi >= kj)
        if window is not None:
            seen = seen & (qi - kj < window)
        probs = jax.nn.softmax(jnp.where(seen, scores, -1e30), axis=-1)
        return jnp.einsum("kgqs,skc->qkgc", probs, vs)

    out = jax.lax.map(one, jnp.arange(blocks) * QUERY_BLOCK)
    a = out.reshape(blocks * QUERY_BLOCK, cfg.n_heads, cfg.head_dim)[:s]

    if not no_gate:
        a = a * jax.nn.sigmoid(jnp.einsum("sd,dhc->shc", h, _f32(lp["wg"])))
    return jnp.einsum("shc,hcd->sd", a, _f32(lp["wo"])), k, v


def by_rows(fn, h):
    """``fn(h)`` a block of rows at a time (a loop): the same numbers,
    less room; rows of padding compute nothing that is kept."""
    import jax
    import jax.numpy as jnp

    rows, d = h.shape
    blocks = -(-rows // ROW_BLOCK)
    padded = jnp.pad(h, ((0, blocks * ROW_BLOCK - rows), (0, 0)))
    return jax.lax.map(fn, padded.reshape(blocks, ROW_BLOCK, d)).reshape(
        blocks * ROW_BLOCK, d)[:rows]


def routed_experts(h, lp, cfg, held=None, softmax_scores=False,
                   no_route_norm=False, no_route_scale=False,
                   renormalise_held=False, no_bias=False):
    """``sum_e gate_e . expert_e(h)`` over the chosen experts that are held
    (``held = (lo, hi)``, default ``cfg.experts_held``); ``h [s, d]``. The
    keywords are WRONG layers: softmax for sigmoid scores, gates not
    divided by their sum, ``route_scale`` left out, gates renormalised over
    the HELD experts, the selection bias left out."""
    import jax
    import jax.numpy as jnp

    lo, hi = cfg.experts_held if held is None else held
    logits = h @ _f32(lp["router"])                              # [s, E]
    scores = jax.nn.softmax(logits, axis=-1) if softmax_scores \
        else jax.nn.sigmoid(logits)
    _, choice = jax.lax.top_k(
        scores if no_bias else scores + lp["expert_bias"],
        cfg.experts_per_token)
    gates = jnp.take_along_axis(scores, choice, axis=-1)         # [s, k]
    if renormalise_held:
        gates = jnp.where((choice >= lo) & (choice < hi), gates, 0.0)
    if not no_route_norm:
        gates = gates / (gates.sum(-1, keepdims=True) + 1e-20)
    if not no_route_scale:
        gates = gates * cfg.routed_scaling_factor
    first = cfg.experts_held[0]  # the weights hold experts first..

    def one(out, e):
        gate = jnp.sum(jnp.where(choice == e, gates, 0.0), axis=-1)
        y = _gated(h, lp["w_in"][e - first], lp["w_out"][e - first])
        return out + gate[:, None] * y, None

    out, _ = jax.lax.scan(one, jnp.zeros_like(h), jnp.arange(lo, hi))
    return out


def afmoe_hidden(params, tokens, cfg, held=None, no_window=False,
                 half_window=False, window_off_by_one=False,
                 rotary_on_full=False, no_rotary_on_window=False,
                 no_gate=False, no_post_norms=False, no_embed_scale=False,
                 **routing):
    """The residual stream after the last layer, ``[s, d]``, and the keys
    and values of each kind of layer, ``{"kv": [full layers, 2, s, kv
    heads, dim], "win": [window layers, ...]}``."""
    import jax
    import jax.numpy as jnp

    window = cfg.window // 2 if half_window \
        else cfg.window + 1 if window_off_by_one else cfg.window

    def post(y, scale):
        return y if no_post_norms else _rmsnorm(y, scale, cfg.rms_eps)

    with jax.default_matmul_precision("highest"):
        x = _f32(params["embed"][tokens])
        if not no_embed_scale:
            x = x * cfg.d_model ** 0.5
        rows = {"kv": [], "win": []}
        for kind, lp in zip(cfg.layer_types, params["layers"]):
            sliding = kind == SLIDING
            out, k, v = attention(
                _rmsnorm(x, lp["ln1"], cfg.rms_eps), lp, cfg,
                window if sliding and not no_window else None,
                rotary=(not no_rotary_on_window) if sliding
                else rotary_on_full, no_gate=no_gate)
            rows["win" if sliding else "kv"].append(jnp.stack([k, v]))
            x = x + post(out, lp["ln1_post"])
            h = _rmsnorm(x, lp["ln2"], cfg.rms_eps)
            if "dense_in" in lp:
                y = by_rows(lambda r, lp=lp: _gated(
                    r, lp["dense_in"], lp["dense_out"]), h)
            else:
                y = by_rows(lambda r, lp=lp: routed_experts(
                    r, lp, cfg, held, **routing) + _gated(
                        r, lp["shared_in"], lp["shared_out"]), h)
            x = x + post(y, lp["ln2_post"])
        return x, {name: jnp.stack(r) for name, r in rows.items()}


def afmoe_check(params, tokens, first, count: int, stop, cfg, **wrong):
    """``(logprobs [count, vocab], {"kv": .., "win": ..})``: the
    log-probabilities of the token after each of the positions ``first ..
    first + count - 1`` of ``tokens`` (int32 ``[s]``; causal, so what
    follows a position does not matter to it), and every position's keys
    and values of every layer, by kind (``stop`` is not needed: a row is
    its own position's)."""
    import jax

    del stop
    with jax.default_matmul_precision("highest"):
        x, rows = afmoe_hidden(params, tokens, cfg, **wrong)
        x = jax.lax.dynamic_slice_in_dim(x, first, count)
        x = _rmsnorm(x, params["ln_f"], cfg.rms_eps)
        return jax.nn.log_softmax(x @ _f32(params["lm_head"]).T), rows


def afmoe_logprobs(params, tokens, first, count: int, cfg, **wrong):
    """The log-probabilities of ``afmoe_check`` alone."""
    return afmoe_check(params, tokens, first, count, 0, cfg, **wrong)[0]
