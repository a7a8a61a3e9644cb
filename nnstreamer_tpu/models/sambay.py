"""Decoder-hybrid-decoder LM (SambaY): a self-decoder of Mamba-1 and
window-attention layers, ONE full-attention layer whose cache every later
attention layer reads, and a cross-decoder of gated memory units and cross
attention — the fifth member of the serving engine's model family
(``models/family.py``).

What is different from the other four, and why it is a module of its own:

- **Three kinds of lane memory at once.** A Mamba-1 layer keeps a recurrent
  state ``[state, channels]`` and a convolution tail a lane
  (``BlockPool``'s state arena, updated in place by ``ops/lane_state.py``
  rule ``"mamba1"``); a window layer keeps keys and values of the last
  ``window`` positions (``BlockPool.win``, given back behind the window);
  the one full layer keeps every block of a stream (``kv``). The family
  states ``kv_window`` AND ``lane_state``; the decode step takes ``{"kv",
  "win", "state"}``.
- **Layers that own no cache.** ``kv_entry`` says ONE layer. The cross
  layers project queries only and read the full layer's blocks through the
  same arena index: eight ``attend`` calls a step over index 0, one of them
  after a ``kv_write``.
- **Mamba-1.** The decay is ``exp(dt[c] A[c, n])``, a matrix and not a
  scalar a head, and ``dt`` comes through a low-rank projection. Prefill is
  a chunked selective scan (``selective_scan_chunked``) that hands over the
  state after each row's LAST REAL token.
- **Gated memory units.** A layer whose mixer is ``silu(h W1) * m``, ``m``
  the scan output (before its gate) of the last Mamba layer at the SAME
  token: the step and the prefill carry ``m`` beside the residual.
- **Differential attention** (arXiv:2410.05258): query heads in pairs, two
  softmaxes over the same values, ``A1 - lambda A2``, an RMSNorm over the
  pair. Heads are 64 wide; the cache stores a block by PAIR, key row ``[k1
  | k2]`` and value row ``[v1 | v2]`` of 128 columns, and a side-1 query
  goes in as ``[q1 | 0]``, a side-2 query as ``[0 | q2]``
  (``_pair_queries``): the paged decode kernels and the flash prefill then
  see ``n_heads`` query rows over ``n_kv_heads / 2`` key-value heads of
  128, each stored row is read once a layer, and the pair's arithmetic is a
  few vector operations after the call (``_diff_out``).
- **A prefill of two stages.** The cross-decoder writes no state, so its
  rows at positions before the last feed nothing that is served: stage 1
  runs the self-decoder and the full layer's keys and values over the whole
  prompt, stage 2 the full layer's attention and everything after it for
  ONE row a prompt (``_upper_rows``, the decode step's own second half).
- LayerNorm with bias, projection biases, no positions anywhere, a tied
  head.

The family brings none of ``prefix_cache``, ``speculate``,
``prefill_chunk``, ``kv_quant`` and ``mesh`` (``refusal``).

Parameters are a plain pytree: ``embed``, ``ln_f`` / ``ln_f_b`` and
``layers``, one dict a layer: ``ln1``/``ln1_b``/``ln2``/``ln2_b``,
``mlp_in [d, 2 ff]`` (``[gate | up]``), ``mlp_out``, and by kind — Mamba:
``ssm_in [d, 2 inner]`` (``[x | z]``), ``conv_w [conv, inner]``,
``conv_b``, ``x_proj [inner, rank + 2 state]``, ``dt_proj [rank, inner]``,
``dt_bias``, ``A_log [state, inner]`` (state-major, as the lane's tile),
``D``, ``ssm_out``; attention: ``wqkv [d, (heads + 2 kv heads) head_dim]``
/ ``bqkv`` (a cross layer: ``wq``/``bq``), ``wo``/``bo``, ``lam_q1``,
``lam_k1``, ``lam_q2``, ``lam_k2 [head_dim]``, ``sub_norm [2 head_dim]``;
GMU: ``gmu_in [d, inner]``, ``gmu_out``.
"""

from __future__ import annotations

import dataclasses
import functools
import itertools
import math
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from nnstreamer_tpu.models import hybrid
from nnstreamer_tpu.models.family import ModelFamily
from nnstreamer_tpu.models.hybrid import _conv_decode, _conv_prefill, _gated
from nnstreamer_tpu.models.transformer import _attend_cache, _kv_codec
from nnstreamer_tpu.ops import lane_state as lane_ops

MAMBA, WINDOW, FULL, GMU, CROSS = (
    "mamba", "sliding_attention", "full_attention", "gmu", "cross_attention")

#: what the decode step counts of itself: blocks of the full arena read by
#: layers that own no cache, every lane, summed over the cross layers
COUNTERS = ("kv_shared_reads",)


@dataclasses.dataclass(frozen=True)
class SambaYConfig:
    vocab: int = 200064
    d_model: int = 2560
    #: a multiple of 4: even layers are of the Mamba class (Mamba-1 up to
    #: ``n_layers / 2``, gated memory units after), odd ones attention
    #: (window below ``n_layers / 2``, the full layer at ``n_layers / 2 +
    #: 1``, cross attention after)
    n_layers: int = 32
    n_heads: int = 40
    n_kv_heads: int = 20
    head_dim: int = 64
    #: a window layer's query ``i`` sees key ``j`` iff ``0 <= i - j < window``
    window: int = 512
    d_ff: int = 10240
    ssm_inner: int = 5120
    ssm_state: int = 16
    ssm_conv: int = 4
    dt_rank: int = 160
    #: channel blocks of a lane's state tile ``[blocks, state, inner /
    #: blocks]`` (``ops/lane_state.py``: the tile's columns are whole lanes)
    ssm_blocks: int = 1
    #: positions a step of the prefill's chunked scan holds at once
    ssm_chunk: int = 64
    ln_eps: float = 1e-5
    max_seq: int = 6400
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16
    ssm_state_dtype: Any = jnp.float32

    def __post_init__(self):
        if self.n_layers % 4 or self.n_layers < 8:
            raise ValueError(
                f"SambaYConfig: n_layers ({self.n_layers}) must be a "
                f"multiple of 4, at least 8")
        if self.n_heads % 2 or self.n_kv_heads % 2 \
                or (self.n_heads // 2) % (self.n_kv_heads // 2) \
                or self.window <= 0 or self.ssm_inner % self.ssm_blocks:
            raise ValueError(
                f"SambaYConfig: heads ({self.n_heads}, {self.n_kv_heads}) "
                f"pair by neighbours and query pairs share key-value pairs "
                f"evenly, window ({self.window}) is positive and "
                f"ssm_blocks ({self.ssm_blocks}) divides ssm_inner "
                f"({self.ssm_inner})")

    @property
    def memory_layer(self) -> int:
        return self.n_layers // 2

    @property
    def layer_types(self) -> Tuple[str, ...]:
        half = self.memory_layer
        return tuple(
            (MAMBA if i <= half else GMU) if i % 2 == 0 else
            WINDOW if i < half else FULL if i == half + 1 else CROSS
            for i in range(self.n_layers))

    @property
    def ssm_layers(self) -> int:
        return self.layer_types.count(MAMBA)

    @property
    def window_layers(self) -> int:
        return self.layer_types.count(WINDOW)

    @property
    def cross_layers(self) -> int:
        return self.layer_types.count(CROSS)

    @property
    def pair_width(self) -> int:
        return 2 * self.head_dim

    @property
    def kv_pairs(self) -> int:
        return self.n_kv_heads // 2

    @property
    def attention_scale(self) -> float:
        return float(self.head_dim ** -0.5)

    @property
    def family(self) -> ModelFamily:
        return SAMBAY


def lambda_init(layer: int) -> float:
    """``lambda_0`` of the differential attention of layer ``layer``."""
    return 0.8 - 0.6 * math.exp(-0.3 * layer)


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _scaled_normal(key, shape, dtype, scale):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def init_params(cfg: SambaYConfig, seed: int = 0) -> Dict[str, Any]:
    """Seeded weights as ``hybrid.init_params`` makes them, each leaf on the
    default device by one small program: normal x 0.02 for every matrix
    and every bias (zero would leave those paths untested); the depthwise
    convolution normal x ``conv ** -0.5`` (its fan-in is its 4 taps: at
    0.02 the scan's input, the memory and every Mamba layer's output are a
    hundredth of the residual and no check could tell a wrong gated memory
    unit: PERF.md, PR 39); ones for the norm scales; ``A_log = log(1..state)`` down every channel,
    ``D = 1``, ``dt_bias`` the inverse softplus of a step drawn
    log-uniformly from [0.001, 0.1] a channel; the four lambda vectors
    normal x 0.1."""
    D, F, E = cfg.d_model, cfg.d_ff, cfg.ssm_inner
    H, Hk, C = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    keys = map(functools.partial(jax.random.fold_in,
                                 jax.random.PRNGKey(seed % (2 ** 31 - 1))),
               itertools.count())

    def mat(*shape, dtype=cfg.param_dtype):
        return hybrid._normal(next(keys), shape, dtype)

    def vec(*shape):
        return mat(*shape, dtype=jnp.float32)

    def ones(*shape):
        return jnp.ones(shape, jnp.float32)

    layers = []
    for kind in cfg.layer_types:
        if kind == MAMBA:
            dt = jnp.exp(jax.random.uniform(
                next(keys), (E,), jnp.float32, np.log(1e-3), np.log(0.1)))
            p = {"ssm_in": mat(D, 2 * E),
                 "conv_w": _scaled_normal(next(keys), (cfg.ssm_conv, E),
                                          jnp.float32, cfg.ssm_conv ** -0.5),
                 "conv_b": vec(E),
                 "x_proj": mat(E, cfg.dt_rank + 2 * cfg.ssm_state),
                 "dt_proj": mat(cfg.dt_rank, E),
                 "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                 "A_log": jnp.broadcast_to(jnp.log(jnp.arange(
                     1, cfg.ssm_state + 1, dtype=jnp.float32))[:, None],
                     (cfg.ssm_state, E)),
                 "D": ones(E), "ssm_out": mat(E, D)}
        elif kind == GMU:
            p = {"gmu_in": mat(D, E), "gmu_out": mat(E, D)}
        else:
            p = {"wq": mat(D, H * C), "bq": vec(H * C)} if kind == CROSS \
                else {"wqkv": mat(D, (H + 2 * Hk) * C),
                      "bqkv": vec((H + 2 * Hk) * C)}
            p.update(wo=mat(H * C, D), bo=vec(D), sub_norm=ones(2 * C),
                     **{name: _scaled_normal(next(keys), (C,), jnp.float32,
                                             0.1)
                        for name in ("lam_q1", "lam_k1", "lam_q2",
                                     "lam_k2")})
        p.update(ln1=ones(D), ln1_b=vec(D), ln2=ones(D), ln2_b=vec(D),
                 mlp_in=mat(D, 2 * F), mlp_out=mat(F, D))
        layers.append(p)
    return {"embed": mat(cfg.vocab, D), "ln_f": ones(D), "ln_f_b": vec(D),
            "layers": layers}


# -- the pieces every program shares ------------------------------------------

def _layernorm(x, scale, bias, eps):
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mean), axis=-1, keepdims=True)
    return ((x32 - mean) * lax.rsqrt(var + eps) * scale + bias) \
        .astype(x.dtype)


def _dot(x, w, dtype):
    return jnp.dot(x.astype(dtype), w.astype(dtype),
                   preferred_element_type=jnp.float32)


def _embed(params, tokens, cfg: SambaYConfig):
    return params["embed"][tokens].astype(cfg.dtype)


def _logits(x, params, cfg: SambaYConfig):
    x = _layernorm(x, params["ln_f"], params["ln_f_b"], cfg.ln_eps)
    return jnp.einsum("...d,vd->...v", x, params["embed"].astype(cfg.dtype),
                      preferred_element_type=jnp.float32)


def _mlp(x, lp, cfg: SambaYConfig):
    """``x + (up * silu(gate)) W2`` of ``LN2(x)``, any leading axes."""
    h = _layernorm(x, lp["ln2"], lp["ln2_b"], cfg.ln_eps)
    with jax.named_scope("dense_ffn"):
        y = _gated(h.reshape(-1, h.shape[-1]), lp["mlp_in"], lp["mlp_out"],
                   cfg.dtype)
    return x + y.astype(cfg.dtype).reshape(x.shape)


def _gmu(h, memory, lp, cfg: SambaYConfig):
    """The gated memory unit: ``(silu(h W1) * m) W2``, ``m`` float32."""
    with jax.named_scope("gmu"):
        gate = jax.nn.silu(_dot(h, lp["gmu_in"], cfg.dtype))
        return _dot(gate * memory, lp["gmu_out"], cfg.dtype) \
            .astype(cfg.dtype)


def _heads(x, heads: int):
    return x.reshape(x.shape[:-1] + (heads, x.shape[-1] // heads))


def _pair_queries(q, cfg: SambaYConfig):
    """``q [.., heads, head_dim]`` as the rows the pair entry is read by:
    ``[.., heads, 2 head_dim]``, an even head (side 1) ``[q | 0]``, an odd
    head (side 2) ``[0 | q]``. Query row ``i`` then reads key-value pair
    ``i // (heads / kv pairs)``, which is its pair's."""
    first = (jnp.arange(cfg.n_heads) % 2 == 0)[:, None]
    zero = jnp.zeros_like(q)
    return jnp.concatenate([jnp.where(first, q, zero),
                            jnp.where(first, zero, q)], axis=-1)


def _qkv(h, lp, cfg: SambaYConfig, queries: bool = True):
    """The fused projection of a layer that owns a cache: ``(q [.., heads,
    2 head_dim]`` as ``_pair_queries`` makes them, ``kv [2, .., kv pairs,
    2 head_dim])``, the key row ``[k1 | k2]`` and the value row ``[v1 |
    v2]`` of each pair. Without ``queries`` the keys' and values' columns
    alone are projected, and ``q`` is None."""
    n = cfg.n_heads * cfg.head_dim
    w, b = (lp["wqkv"], lp["bqkv"]) if queries \
        else (lp["wqkv"][:, n:], lp["bqkv"][n:])
    u = (_dot(h, w, cfg.dtype) + b).astype(cfg.dtype)
    q = None
    if queries:
        q, u = _pair_queries(_heads(u[..., :n], cfg.n_heads), cfg), u[..., n:]
    return q, jnp.stack([_heads(part, cfg.kv_pairs)
                         for part in jnp.split(u, 2, axis=-1)])


def _q_only(h, w, b, cfg: SambaYConfig):
    q = (_dot(h, w, cfg.dtype) + b).astype(cfg.dtype)
    return _pair_queries(_heads(q, cfg.n_heads), cfg)


def _diff_out(a, lp, layer: int, cfg: SambaYConfig):
    """What the two softmaxes of each pair become: ``a [.., heads, 2
    head_dim]`` (row ``2p`` is ``A1`` of pair ``p``, row ``2p + 1`` its
    ``A2``) → ``(1 - lambda_0) RMSNorm(A1 - lambda A2)`` a pair, the pairs
    side by side ``[.., heads head_dim]``, then the output projection."""
    with jax.named_scope("diff_out"):
        lam0 = lambda_init(layer)
        lam = jnp.exp(jnp.sum(lp["lam_q1"] * lp["lam_k1"])) \
            - jnp.exp(jnp.sum(lp["lam_q2"] * lp["lam_k2"])) + lam0
        a = a.astype(jnp.float32)
        a = a.reshape(a.shape[:-2] + (cfg.n_heads // 2, 2, cfg.pair_width))
        d = a[..., 0, :] - lam * a[..., 1, :]
        d = d * lax.rsqrt(jnp.mean(d * d, axis=-1, keepdims=True)
                          + cfg.ln_eps) * lp["sub_norm"]
        d = ((1.0 - lam0) * d).reshape(d.shape[:-2] + (-1,))
    with jax.named_scope("attn_out"):
        return (_dot(d, lp["wo"], cfg.dtype) + lp["bo"]).astype(cfg.dtype)


# -- the Mamba-1 mixer --------------------------------------------------------

def _mamba_project(h, lp, cfg: SambaYConfig):
    with jax.named_scope("ssm_in"):
        xr, z = jnp.split(_dot(h, lp["ssm_in"], cfg.dtype), 2, axis=-1)
    return xr.astype(cfg.dtype), z


def _mamba_dt(xc, lp, cfg: SambaYConfig):
    """The two low-rank products and the softplus: ``(dt, B, C)`` float32
    from the activated convolution ``xc`` float32."""
    with jax.named_scope("ssm_dt"):
        low, bm, cm = jnp.split(
            _dot(xc, lp["x_proj"], cfg.dtype),
            [cfg.dt_rank, cfg.dt_rank + cfg.ssm_state], axis=-1)
        dt = jax.nn.softplus(_dot(low, lp["dt_proj"], cfg.dtype)
                             + lp["dt_bias"])
    return dt, bm, cm


def _mamba_finish(y, xc, z, lp, cfg: SambaYConfig):
    """``(out, memory)``: the skip, then the memory is taken, BEFORE the
    gate; then the gate and the output projection."""
    with jax.named_scope("ssm_out"):
        memory = y + lp["D"] * xc
        out = _dot(memory * jax.nn.silu(z), lp["ssm_out"], cfg.dtype)
    return out.astype(cfg.dtype), memory


def selective_scan_chunked(x, dt, a, bm, cm, chunk: int):
    """The recurrence ``S_t = exp(dt_t[c] a[n, c]) S_{t-1} + B_t[n] (dt_t
    x_t)[c]``, ``y_t[c] = sum_n S_t[n, c] C_t[n]`` from a zero state,
    ``chunk`` positions at a time: inside a chunk an associative scan over
    ``(decay, write)`` pairs, between chunks the carried state, so that
    never more than one chunk's ``[chunk, n, c]`` is held. ``x``/``dt [b,
    s, c]``, ``a [n, c]`` (negative), ``bm``/``cm [b, s, n]``, all float32.
    Returns ``(y [b, s, c], S [b, n, c])``. A position whose ``dt`` is 0
    passes the state through unchanged, which is how padding is kept out
    of it."""
    b, s_in, c = x.shape
    q = min(chunk, s_in)
    if s_in % q:  # whole chunks: the rows added have dt 0
        pad = [(0, 0), (0, q - s_in % q), (0, 0)]
        x, dt, bm, cm = (jnp.pad(v, pad) for v in (x, dt, bm, cm))
    steps = x.shape[1] // q

    def chunks(v):
        return jnp.moveaxis(v.reshape(b, steps, q, v.shape[-1]), 1, 0)

    def combine(left, right):
        return left[0] * right[0], left[1] * right[0] + right[1]

    def body(state, xs):
        x, dt, bm, cm = xs
        decay = jnp.exp(dt[:, :, None, :] * a)               # [b,q,n,c]
        write = bm[..., None] * (x * dt)[:, :, None, :]
        kept, added = lax.associative_scan(combine, (decay, write), axis=1)
        states = kept * state[:, None] + added
        return states[:, -1], jnp.sum(states * cm[..., None], axis=2)

    state, y = lax.scan(body, jnp.zeros((b,) + a.shape, jnp.float32),
                        tuple(chunks(v) for v in (x, dt, bm, cm)))
    return jnp.moveaxis(y, 0, 1).reshape(b, steps * q, c)[:, :s_in], state


def _tile(a, cfg: SambaYConfig):
    """``[.., state, inner]`` as the lane's tile ``[.., blocks, state,
    inner / blocks]``."""
    blocks = cfg.ssm_blocks
    return jnp.swapaxes(a.reshape(
        a.shape[:-1] + (blocks, cfg.ssm_inner // blocks)), -2, -3)


def _mamba_prefill(h, lp, lengths, cfg: SambaYConfig):
    """The mixer over whole right-padded prompts ``h [b, s, d]``: ``(out
    [b, s, d], memory [b, s, inner] float32, state [b, blocks, n, inner /
    blocks], tail [b, conv - 1, inner])``: the state after each row's last
    real token and the last ``conv - 1`` real rows of the convolution's
    input."""
    s = h.shape[1]
    xr, z = _mamba_project(h, lp, cfg)
    with jax.named_scope("ssm_conv"):
        conv, tail = _conv_prefill(xr, lp["conv_w"], lengths, cfg.ssm_conv)
        xc = jax.nn.silu(conv + lp["conv_b"])
    dt, bm, cm = _mamba_dt(xc, lp, cfg)
    with jax.named_scope("ssm_scan"):
        real = jnp.arange(s)[None, :] < lengths[:, None]
        y, state = selective_scan_chunked(
            xc, jnp.where(real[..., None], dt, 0.0), -jnp.exp(lp["A_log"]),
            bm, cm, cfg.ssm_chunk)
    out, memory = _mamba_finish(y, xc, z, lp, cfg)
    return out, memory, _tile(state, cfg).astype(cfg.ssm_state_dtype), tail


def _mamba_decode(h, lp, slot, tail, live, cfg: SambaYConfig):
    """One token for every lane: ``h [b, d]``, ``slot`` the layer's
    :class:`lane_ops.LaneSlot` of the state arena, ``tail [b, conv - 1,
    inner]`` → ``(out [b, d], memory [b, inner], slot, tail)``. An empty
    lane reads zeros and keeps what its slots hold."""
    b, blocks = h.shape[0], cfg.ssm_blocks
    xr, z = _mamba_project(h, lp, cfg)
    with jax.named_scope("ssm_conv"):
        conv, tail = _conv_decode(xr, lp["conv_w"], tail, live)
        xc = jax.nn.silu(conv + lp["conv_b"])
    dt, bm, cm = _mamba_dt(xc, lp, cfg)
    with jax.named_scope("ssm_update"):
        y, slot = lane_ops.update(
            lane_ops.MAMBA1, slot, live,
            (xc.reshape(b, blocks, -1), dt.reshape(b, blocks, -1),
             _tile(-jnp.exp(lp["A_log"]), cfg), bm, cm))
    out, memory = _mamba_finish(y.reshape(b, -1), xc, z, lp, cfg)
    return out, memory, slot, tail


# -- the layers ----------------------------------------------------------------

def _lower_layers(params, tokens, lengths, cfg: SambaYConfig, attn):
    """The self-decoder over whole right-padded prompts, and the full
    layer's keys and values: ``(x [b, s, d]`` as it enters the full layer,
    ``h [b, s, d]`` the full layer's normed input, ``memory [b, s, inner]``,
    ``cache)`` with ``cache = {"kv": [1, 2, b, s, kv pairs, 2 head_dim],
    "win": [window layers, ...], "state": {"ssm", "conv"}}``."""
    x = _embed(params, tokens, cfg)
    win, ssm, conv, memory = [], [], [], None
    for i in range(cfg.memory_layer + 1):
        kind, lp = cfg.layer_types[i], params["layers"][i]
        h = _layernorm(x, lp["ln1"], lp["ln1_b"], cfg.ln_eps)
        if kind == MAMBA:
            out, memory, state, tail = _mamba_prefill(h, lp, lengths, cfg)
            ssm.append(state)
            conv.append(tail)
        else:
            with jax.named_scope("qkv"):
                q, kv = _qkv(h, lp, cfg)
            with jax.named_scope("attend_window"):
                a = attn(q, kv[0], kv[1], scale=cfg.attention_scale,
                         window=cfg.window)
            out = _diff_out(a, lp, i, cfg)
            win.append(kv)
        x = _mlp(x + out, lp, cfg)
    lp = params["layers"][cfg.memory_layer + 1]
    h = _layernorm(x, lp["ln1"], lp["ln1_b"], cfg.ln_eps)
    with jax.named_scope("qkv"):
        _, kv = _qkv(h, lp, cfg, queries=False)
    return x, h, memory, {"kv": kv[None], "win": jnp.stack(win), "state": {
        "ssm": jnp.stack(ssm), "conv": jnp.stack(conv)}}


def _upper_rows(x, q_full, memory, params, cfg: SambaYConfig, attend):
    """The full layer from its attention on and the cross-decoder, for the
    rows ``x [.., d]`` (the full layer's input, un-normed), ``q_full`` the
    full layer's queries of those rows and ``memory [.., inner]`` the last
    Mamba layer's memory of the SAME rows. ``attend(q, scope) -> a`` reads
    the full layer's keys and values, wherever they are: the decode step's
    blocks, a prompt's rows."""
    half = cfg.memory_layer
    for i in range(half + 1, cfg.n_layers):
        kind, lp = cfg.layer_types[i], params["layers"][i]
        if kind == FULL:
            out = _diff_out(attend(q_full, "attend"), lp, i, cfg)
        else:
            h = _layernorm(x, lp["ln1"], lp["ln1_b"], cfg.ln_eps)
            if kind == GMU:
                out = _gmu(h, memory, lp, cfg)
            else:
                with jax.named_scope("qkv"):
                    q = _q_only(h, lp["wq"], lp["bq"], cfg)
                out = _diff_out(attend(q, "attend_cross"), lp, i, cfg)
        x = _mlp(x + out, lp, cfg)
    return x


def _full_queries(h, params, cfg: SambaYConfig):
    lp = params["layers"][cfg.memory_layer + 1]
    n = cfg.n_heads * cfg.head_dim
    with jax.named_scope("qkv"):
        return _q_only(h, lp["wqkv"][:, :n], lp["bqkv"][:n], cfg)


def build_prefill(cfg: SambaYConfig, max_seq: Optional[int] = None,
                  attention_fn: Optional[Callable] = None,
                  kv_codec: Optional[str] = None) -> Callable:
    """``prefill(params, tokens[int32 b, s], lengths[int32 b]) -> (logits[b,
    vocab], cache)`` over right-padded prompts, in two stages: the
    self-decoder and the full layer's keys and values over the whole bucket
    (``_lower_layers``), then the full layer's attention and the
    cross-decoder for each row's LAST REAL token alone (``_upper_rows``):
    the cross-decoder leaves nothing behind, so its rows before the last
    feed nothing. ``cache`` is what the pool scatters into its three
    arenas. ``attention_fn`` is ``ops.flash_attention`` (it takes
    ``window=``) or None."""
    from nnstreamer_tpu.ops.flash_attention import attention_reference

    del max_seq
    _no_codec(kv_codec)
    attn = attention_fn or attention_reference

    @jax.named_scope("nns.prefill")
    def prefill(params, tokens, lengths=None):
        b, s = tokens.shape
        lengths = jnp.full((b,), s, jnp.int32) if lengths is None \
            else jnp.asarray(lengths, jnp.int32)
        x, h, memory, cache = _lower_layers(params, tokens, lengths, cfg,
                                            attn)
        at = (lengths - 1)[:, None, None]

        def last(v):
            return jnp.take_along_axis(v, at, axis=1)         # [b,1,..]

        keys, values = cache["kv"][0]
        mask = (jnp.arange(s)[None, :] < lengths[:, None])[:, None, None, :]

        def attend(q, scope):
            with jax.named_scope(scope):
                return _attend_cache(q, keys, values, mask, cfg.pair_width,
                                     cfg.dtype, scale=cfg.attention_scale)

        x = _upper_rows(last(x), _full_queries(last(h), params, cfg),
                        last(memory), params, cfg, attend)
        with jax.named_scope("logits"):
            logits = _logits(x[:, 0], params, cfg)
        return logits, cache

    return prefill


def _no_codec(kv_codec) -> None:
    if kv_codec not in (None, "raw"):
        raise ValueError(f"sambay: no codec {kv_codec!r} over three arenas")


def build_paged_decode_step(cfg: SambaYConfig, block_tokens: int,
                            max_seq: Optional[int] = None,
                            kv_codec: Optional[str] = None,
                            paged_attention_fn: Optional[Callable] = None
                            ) -> Callable:
    """One token for every decode lane against the pool's three arenas:
    ``step(params, token[int32 b], arenas, bt, pos[int32 b]) -> (logits[b,
    vocab], arenas, counts)`` with ``arenas = {"kv": pages, "win": pages_w,
    "state": {"ssm", "conv"}}`` and ``bt = {"kv": [b, MB], "win": [b,
    MB]}``.

    A window layer writes its row at ``(its index among the window layers,
    block, slot)`` of the window arena and attends over ``max(0, pos -
    window + 1)..pos`` there, as ``afmoe``'s do. The ONE full layer writes
    at index 0 of the full arena and attends over ``0..pos``; every cross
    layer attends over the same index and writes nothing. A Mamba layer
    updates its slot of the state arena in place (``ops/lane_state.py``,
    the layer a static index) and the last of them hands its memory to the
    gated memory units of the same step. A lane whose full table is all
    sentinel is empty: it writes nowhere and keeps its state.
    ``paged_attention_fn`` is ``ops.paged_attention`` or None (the gather
    form)."""
    from nnstreamer_tpu.ops.paged_attention import paged_attention_reference

    s_max = max_seq or cfg.max_seq
    T = int(block_tokens)
    if T <= 0 or s_max % T:
        raise ValueError(
            f"build_paged_decode_step: max_seq ({s_max}) must be a "
            f"positive multiple of block_tokens ({block_tokens})")
    _no_codec(kv_codec)
    codec = _kv_codec(cfg, kv_codec)
    paged = paged_attention_fn or paged_attention_reference

    @jax.named_scope("nns.decode")
    def step(params, token, arenas, bt, pos):
        pos = jnp.asarray(pos, jnp.int32)
        pos_c = jnp.minimum(pos, s_max - 1)
        pages = {name: arenas[name] for name in ("kv", "win")}
        state = dict(arenas["state"])
        live = bt["kv"][:, 0] < pages["kv"].shape[1]
        at = (pos_c // T)[:, None]
        blk = {name: jnp.take_along_axis(bt[name], at, axis=1)
               for name in pages}
        off = (pos_c % T)[:, None]

        def attend(q, name, index, scope, window=None):
            return paged(q, pages[name], index, bt[name], pos_c,
                         scale=cfg.attention_scale,
                         heads_major=codec.heads_major, window=window,
                         scope=scope)

        x = _embed(params, token, cfg)                          # [b,d]
        i_ssm = i_win = 0
        for i in range(cfg.memory_layer + 1):
            kind, lp = cfg.layer_types[i], params["layers"][i]
            h = _layernorm(x, lp["ln1"], lp["ln1_b"], cfg.ln_eps)
            if kind == MAMBA:
                out, memory, slot, tail = _mamba_decode(
                    h, lp, lane_ops.LaneSlot(state["ssm"], i_ssm),
                    state["conv"][i_ssm], live, cfg)
                state["ssm"] = slot.arena
                with jax.named_scope("ssm_conv"):
                    state["conv"] = state["conv"].at[i_ssm].set(tail)
                i_ssm += 1
            else:
                with jax.named_scope("qkv"):
                    q, kv = _qkv(h[:, None], lp, cfg)
                with jax.named_scope("kv_write"):
                    pages["win"] = codec.paged_write(pages["win"], i_win,
                                                     kv, blk["win"], off)
                a = attend(q, "win", i_win, "attend_window", cfg.window)
                out = _diff_out(a, lp, i, cfg)[:, 0]
                i_win += 1
            x = _mlp(x + out, lp, cfg)
        # the full layer: its row into the full arena, then everything
        # that reads that arena, this layer first
        lp = params["layers"][cfg.memory_layer + 1]
        h = _layernorm(x, lp["ln1"], lp["ln1_b"], cfg.ln_eps)
        with jax.named_scope("qkv"):
            q, kv = _qkv(h[:, None], lp, cfg)
        with jax.named_scope("kv_write"):
            pages["kv"] = codec.paged_write(pages["kv"], 0, kv, blk["kv"],
                                            off)
        x = _upper_rows(x[:, None], q, memory[:, None], params, cfg,
                        lambda q, scope: attend(q, "kv", 0, scope))
        with jax.named_scope("logits"):
            logits = _logits(x[:, 0], params, cfg)
        counts = {"kv_shared_reads": jnp.sum(pos_c // T + 1, dtype=jnp.int32)
                  * cfg.cross_layers}
        return logits, {**pages, "state": state}, counts

    return step


def build_forward(cfg: SambaYConfig) -> Callable:
    """``forward(params, tokens[int32 b, s]) -> logits[b, s, vocab]``: every
    layer at every position (tests; the served path is the two-stage
    prefill + decode)."""
    from nnstreamer_tpu.ops.flash_attention import attention_reference

    def forward(params, tokens):
        b, s = tokens.shape
        x, h, memory, cache = _lower_layers(
            params, tokens, jnp.full((b,), s, jnp.int32), cfg,
            attention_reference)
        keys, values = cache["kv"][0]
        x = _upper_rows(
            x, _full_queries(h, params, cfg), memory, params, cfg,
            lambda q, scope: attention_reference(
                q, keys, values, scale=cfg.attention_scale))
        return _logits(x, params, cfg)

    return forward


def lane_state(cfg: SambaYConfig) -> Dict[str, Any]:
    """What a decode lane holds beside its blocks: the Mamba layers'
    recurrent state, state-major in channel blocks, and their convolution
    tails."""
    return {"layers": cfg.ssm_layers,
            "ssm": ((cfg.ssm_blocks, cfg.ssm_state,
                     cfg.ssm_inner // cfg.ssm_blocks), cfg.ssm_state_dtype),
            "conv": ((cfg.ssm_conv - 1, cfg.ssm_inner), cfg.dtype)}


def state_update(cfg: SambaYConfig, lanes: int) -> str:
    """The form the decode step updates ``lanes`` lanes' recurrent state in
    here: ``"lane_kernel"`` or ``"reference"`` (``ops/lane_state.py``)."""
    spec = lane_state(cfg)
    shape, dtype = spec["ssm"]
    return lane_ops.state_update_form(
        lane_ops.MAMBA1,
        jax.ShapeDtypeStruct((spec["layers"], lanes) + tuple(shape), dtype))


def prefill_counters(cfg: SambaYConfig, rows: int) -> dict:
    """What a prefill over a bucket of ``rows`` positions a prompt computed:
    rows the self-decoder ran (stage 1) and rows the cross-decoder ran
    (stage 2: one a prompt)."""
    return {"prefill_rows_self": rows,
            "prefill_rows_cross": min(rows, 1)}


SAMBAY = ModelFamily(
    name="sambay", init_params=init_params, build_prefill=build_prefill,
    build_paged_decode_step=build_paged_decode_step,
    kv_entry=lambda cfg: (1, 2, (cfg.kv_pairs, cfg.pair_width)),
    kv_window=lambda cfg: (cfg.window_layers, cfg.window),
    lane_state=lane_state, counters=COUNTERS, state_update=state_update,
    prefill_counters=prefill_counters,
    refusal="keeps recurrent state per decode lane beside a window arena "
            "and ONE full layer's blocks that its cross layers read: its "
            "chunked scan enters with no state, sharing a prefix's blocks "
            "needs the states at the block boundary, and nothing narrows "
            "or shards three kinds of lane memory yet (ROADMAP.md R3, R4; "
            "mesh: R2)",
    read_in_dtype=("embed", "ssm_in", "x_proj", "dt_proj", "ssm_out",
                   "wqkv", "wq", "wo", "gmu_in", "gmu_out", "mlp_in",
                   "mlp_out"))
