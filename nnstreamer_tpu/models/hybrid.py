"""Hybrid decoder LM: recurrent mixers (Mamba-2, or the gated delta rule of
``models/gated_delta.py``) and attention mixers under one layer pattern,
every layer ending in a routed expert layer plus one shared gated MLP — the
second member of the serving engine's model family (``models/family.py``;
the dense block of ``models/transformer.py`` is the first).

What is different from the dense block, and why it is here and not a flag
on it:

- **The layer pattern is data** (``HybridConfig.layer_types``), and the
  layers are laid out one after another in the program, not scanned: each
  kind has its own weights, its own state and its own index into that
  state, and a static index is what lets the compiler update a state
  arena in place.
- **Two kinds of state.** An attention layer keeps keys and values in the
  paged block arena (``serving/kvpool.py``), as the dense block does, with
  fewer key-value heads than query heads and a stated score scale; what
  else it does is the configuration's (``rotary_dim``: rotary positions on
  the first dims of each head, none at 0; ``qk_norm``: a norm over each
  query and key head; ``attn_gate``: the query projection yields an output
  gate beside the query). A recurrent layer keeps, per decode lane, one
  recurrent state (``ssm``: ``[heads, head_dim, state]`` for Mamba-2,
  ``[value heads, key, value]`` for the delta rule; float32 unless the
  configuration says otherwise) and the last ``conv - 1`` rows of its
  convolution's input (``conv``). Prefill computes the recurrence in
  chunks (the state-space-duality form, or the delta rule's triangular
  solve; PAPERS.md) and hands over the state after the prompt's last real
  token, whatever the bucket's padding; decode is the recurrence for one
  token, written into the arena in place (``ops/lane_state.py``: one
  Pallas pass over a layer's slots on a TPU). A pattern holds attention and
  ONE recurrent kind: the lanes' state arena has one shape. (A third
  recurrent kind, Mamba-1, whose decay is a matrix and whose state lies
  state-major, is ``models/sambay.py``'s, under the same kernel's third
  rule.)
- **The expert layer holds a share** (``experts_held``): the router keeps
  its published width and its experts per token, gates are the softmax
  over the chosen experts and are not renormalised over the held ones,
  and what the absent experts would add is left out. The tokens routed to
  held experts are sorted by expert, each expert's rows padded to whole
  tiles, and the tiles go through their experts as one grouped matmul
  (``moe_ffn``, ``ops/grouped_matmul.py``: a Pallas grid over the sorted
  tiles on a TPU, a loop tile by tile elsewhere): no expert computes a
  token that did not choose it.

Parameters are a plain pytree: ``embed``, ``ln_f``, ``layers`` (a list
with one dict per layer) and, where the head is not the embedding
(``tie_embeddings`` false), ``lm_head``. Large matrices are stored in
``param_dtype``; norm scales, the convolution and the per-head ``A_log`` / ``dt_bias`` /
``D`` in float32.
"""

from __future__ import annotations

import dataclasses
import functools
import itertools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from nnstreamer_tpu.models.family import ModelFamily
from nnstreamer_tpu.models.gated_delta import gated_delta_chunked, l2norm
from nnstreamer_tpu.models.transformer import _attend_cache, _kv_codec
from nnstreamer_tpu.ops import grouped_matmul
from nnstreamer_tpu.ops import lane_state as lane_ops

MAMBA, ATTENTION, DELTA = "mamba", "attention", "linear_attention"
#: the published pattern's period: five state-space layers, one attention
#: layer, four state-space layers
PERIOD = (MAMBA,) * 5 + (ATTENTION,) + (MAMBA,) * 4
#: what the decode step counts of its expert layers, summed over layers
#: and steps (``engine.stats``; live lanes only). The last two: the tiles
#: of the sorted buffer that hold a row, and the tiles the buffer has (the
#: grouped matmul's grid: their quotient is the share of its steps that
#: move weights)
COUNTERS = ("moe_tokens_held", "moe_tokens_absent", "moe_expert_load_max",
            "moe_experts_hit", "moe_layer_steps", "moe_tiles_live",
            "moe_tiles_grid")
_HI = lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class HybridConfig:
    vocab: int = 100352
    d_model: int = 4096
    layer_types: Tuple[str, ...] = PERIOD
    # attention mixer: grouped queries; no positions, norms or gate
    # unless said
    n_heads: int = 32
    n_kv_heads: int = 8
    head_dim: int = 128
    attention_scale: float = 0.0078125
    #: rotary positions (half-split) on the first ``rotary_dim`` dims of
    #: every query and key head; 0: none
    rotary_dim: int = 0
    rope_theta: float = 10000.0
    #: an RMSNorm over each query and each key head, before the positions
    qk_norm: bool = False
    #: the query projection yields ``[q | gate]``; the attention's output
    #: is multiplied by ``sigmoid(gate)`` before the output projection
    attn_gate: bool = False
    # state-space mixer (Mamba-2, one group of B and C shared by all heads)
    ssm_heads: int = 128
    ssm_head_dim: int = 64
    ssm_state: int = 128
    ssm_conv: int = 4
    ssm_chunk: int = 256
    # gated delta rule mixer: key heads are repeated over the value heads
    la_key_heads: int = 16
    la_value_heads: int = 32
    la_key_dim: int = 128
    la_value_dim: int = 128
    la_conv: int = 4
    la_chunk: int = 64
    # expert layer
    num_experts: int = 72
    experts_per_token: int = 10
    expert_width: int = 768
    shared_width: int = 1536
    #: the shared MLP's output is multiplied by ``sigmoid(h . shared_gate)``
    shared_gate: bool = False
    #: ``[lo, hi)``: the expert ids whose weights are held here
    experts_held: Tuple[int, int] = (0, 72)
    #: gates are the softmax over the chosen experts' logits; false: the
    #: softmax over ALL router outputs, taken at the chosen and not
    #: renormalised, times ``routed_scaling_factor``
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    #: what a router output becomes before the choice (``moe_ffn``)
    score_func: str = "softmax"
    #: the output head is the embedding (else a leaf of its own, ``lm_head``)
    tie_embeddings: bool = True
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    logits_scaling: float = 16.0
    rms_eps: float = 1e-5
    max_seq: int = 1024
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16
    ssm_state_dtype: Any = jnp.float32

    def __post_init__(self):
        lo, hi = self.experts_held
        if set(self.layer_types) not in ({MAMBA, ATTENTION},
                                         {DELTA, ATTENTION}):
            raise ValueError(f"HybridConfig: layer_types must hold "
                             f"{ATTENTION!r} and one of {MAMBA!r} and "
                             f"{DELTA!r}, and nothing else, got "
                             f"{self.layer_types!r}")
        if self.la_value_heads % self.la_key_heads:
            raise ValueError(
                f"HybridConfig: la_value_heads ({self.la_value_heads}) must "
                f"be a multiple of la_key_heads ({self.la_key_heads})")
        if self.rotary_dim % 2 or self.rotary_dim > self.head_dim:
            raise ValueError(
                f"HybridConfig: rotary_dim ({self.rotary_dim}) must be even "
                f"and at most head_dim ({self.head_dim})")
        if self.n_heads % self.n_kv_heads:
            raise ValueError(
                f"HybridConfig: n_heads ({self.n_heads}) must be a multiple "
                f"of n_kv_heads ({self.n_kv_heads})")
        if not 0 <= lo < hi <= self.num_experts:
            raise ValueError(
                f"HybridConfig: experts_held {self.experts_held!r} must be "
                f"a non-empty range inside [0, {self.num_experts})")

    @property
    def n_layers(self) -> int:
        return len(self.layer_types)

    @property
    def ssm_layers(self) -> int:
        return self.layer_types.count(MAMBA)

    @property
    def la_layers(self) -> int:
        return self.layer_types.count(DELTA)

    @property
    def attn_layers(self) -> int:
        return self.layer_types.count(ATTENTION)

    @property
    def d_inner(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.ssm_state

    @property
    def la_key_width(self) -> int:
        return self.la_key_heads * self.la_key_dim

    @property
    def la_value_width(self) -> int:
        return self.la_value_heads * self.la_value_dim

    @property
    def la_conv_dim(self) -> int:
        """q, k and v side by side: the channels the convolution runs over."""
        return 2 * self.la_key_width + self.la_value_width

    @property
    def n_held(self) -> int:
        return self.experts_held[1] - self.experts_held[0]

    @property
    def family(self) -> ModelFamily:
        return HYBRID


@functools.partial(jax.jit, static_argnums=(1, 2))
def _normal(key, shape, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * 0.02).astype(dtype)


def init_params(cfg: HybridConfig, seed: int = 0) -> Dict[str, Any]:
    """Seeded weights, each leaf made on the default device by one small
    program (nothing the size of the model passes through the host):
    normal x 0.02 for every matrix, the convolution and its bias; ones for
    the norm scales (the EFFECTIVE scale, where a published checkpoint
    stores ``scale - 1``); per head of a recurrent mixer ``A_log =
    log(1..heads)``, ``D = 1`` and ``dt_bias`` the inverse softplus of a
    step drawn log-uniformly from [0.001, 0.1] (the families' usual
    initialisation: heads from slow to fast decay)."""
    D, F, Fs = cfg.d_model, cfg.expert_width, cfg.shared_width
    keys = map(functools.partial(jax.random.fold_in,
                                 jax.random.PRNGKey(seed % (2 ** 31 - 1))),
               itertools.count())

    def mat(*shape, dtype=cfg.param_dtype):
        return _normal(next(keys), shape, dtype)

    def ones(*shape):
        return jnp.ones(shape, jnp.float32)

    def per_head(heads):
        dt = jnp.exp(jax.random.uniform(
            next(keys), (heads,), jnp.float32, np.log(1e-3), np.log(0.1)))
        return {"dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                "A_log": jnp.log(jnp.arange(1, heads + 1, dtype=jnp.float32))}

    layers = []
    for kind in cfg.layer_types:
        if kind == MAMBA:
            H = cfg.ssm_heads
            p = {**per_head(H),
                 "ssm_in": mat(D, 2 * cfg.d_inner + 2 * cfg.ssm_state + H),
                 "conv_w": mat(cfg.ssm_conv, cfg.conv_dim,
                               dtype=jnp.float32),
                 "conv_b": mat(cfg.conv_dim, dtype=jnp.float32),
                 "D": ones(H), "norm": ones(cfg.d_inner),
                 "ssm_out": mat(cfg.d_inner, D)}
        elif kind == DELTA:
            p = {**per_head(cfg.la_value_heads),
                 "la_in": mat(D, cfg.la_conv_dim + cfg.la_value_width),
                 "la_ba": mat(D, 2 * cfg.la_value_heads),
                 "conv_w": mat(cfg.la_conv, cfg.la_conv_dim,
                               dtype=jnp.float32),
                 "norm": ones(cfg.la_value_dim),
                 "la_out": mat(cfg.la_value_width, D)}
        else:
            p = {"wq": mat(D, cfg.n_heads, cfg.head_dim),
                 "wk": mat(D, cfg.n_kv_heads, cfg.head_dim),
                 "wv": mat(D, cfg.n_kv_heads, cfg.head_dim),
                 "wo": mat(cfg.n_heads, cfg.head_dim, D)}
            if cfg.attn_gate:
                p["wg"] = mat(D, cfg.n_heads, cfg.head_dim)
            if cfg.qk_norm:
                p.update(q_norm=ones(cfg.head_dim), k_norm=ones(cfg.head_dim))
        p.update(ln1=ones(D), ln2=ones(D),
                 router=mat(D, cfg.num_experts),
                 w_in=mat(cfg.n_held, D, 2 * F),
                 w_out=mat(cfg.n_held, F, D),
                 shared_in=mat(D, 2 * Fs), shared_out=mat(Fs, D))
        if cfg.shared_gate:
            p["shared_gate"] = mat(D)
        layers.append(p)
    params = {"embed": mat(cfg.vocab, D), "ln_f": ones(D), "layers": layers}
    if not cfg.tie_embeddings:
        params["lm_head"] = mat(cfg.vocab, D)
    return params


def _rmsnorm(x, scale, eps):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * lax.rsqrt(var + eps) * scale).astype(x.dtype)


_gated = grouped_matmul.gated_mlp


def expert_tile(cfg: HybridConfig, t: int) -> Tuple[int, int]:
    """``(tile, rows)`` of the expert-sorted buffer for ``t`` tokens. An
    expert gets ``t * k / num_experts`` rows on average and the fullest
    about twice that: a tile of the next power of two (8 to 128 rows)
    takes an expert in one pass, so its weights are read once; ``rows``
    holds every pair with each held expert padded to whole tiles."""
    pairs = t * cfg.experts_per_token
    most = -(-2 * pairs // cfg.num_experts)
    tile = min(128, max(8, 1 << (most - 1).bit_length()))
    return tile, -(-(pairs + cfg.n_held * (tile - 1)) // tile) * tile


def expert_matmul(cfg: HybridConfig, t: int) -> str:
    """The form :func:`moe_ffn` runs ``t`` tokens' tiles in here:
    ``"grouped_kernel"`` or ``"tile_loop"`` (``ops/grouped_matmul.py``)."""
    tile, rows = expert_tile(cfg, t)
    d, f = cfg.d_model, cfg.expert_width
    return grouped_matmul.expert_matmul_form(
        jax.ShapeDtypeStruct((rows, d), cfg.dtype),
        jax.ShapeDtypeStruct((cfg.n_held, d, 2 * f), cfg.dtype),
        jax.ShapeDtypeStruct((cfg.n_held, f, d), cfg.dtype), tile)


def routed_share(cfg) -> Dict[str, float]:
    """The share of the held experts' leaves one token's matmuls read:
    ``experts_per_token`` of ``num_experts`` router outputs
    (``ModelFamily.token_share``)."""
    return dict.fromkeys(("w_in", "w_out"),
                         cfg.experts_per_token / cfg.num_experts)


def moe_ffn(h, lp, cfg: HybridConfig, live=None):
    """The routed experts' part of the layer that is held here, for tokens
    ``h [t, d]`` (already normed): ``(out [t, d] float32, counts)``.

    Routing is over all ``num_experts`` router outputs; a token's gates
    are the softmax over its ``experts_per_token`` largest logits (or,
    where the configuration says ``norm_topk_prob`` false, the softmax
    over all of them at the chosen, times ``routed_scaling_factor``).
    Under ``score_func`` ``"sigmoid"`` a score is ``sigmoid(logit)``; the
    chosen are the largest of ``score + expert_bias`` (``lp["expert_bias"]
    [num_experts]`` float32: for the CHOICE only), and the gates the
    chosen's scores, divided by their sum over all the chosen where
    ``norm_topk_prob``, times ``routed_scaling_factor``. The
    (token, choice) pairs that fell on a held expert are sorted by expert,
    each expert's rows start on a tile boundary of a padded buffer, and
    the tiles that hold a row go one by one through their tile's expert
    (``grouped_matmul.expert_tiles``): the work follows the routing, and
    an expert nobody chose is never read. ``live [t]`` masks tokens out of
    the routing (empty decode lanes). ``counts``: int32 scalars named as
    :data:`COUNTERS`."""
    t, d = h.shape
    k, (lo, hi), n_held = cfg.experts_per_token, cfg.experts_held, cfg.n_held
    dtype = cfg.dtype
    with jax.named_scope("router"):
        logits = jnp.dot(h, lp["router"].astype(dtype),
                         preferred_element_type=jnp.float32)
        if cfg.score_func == "sigmoid":
            scores = jax.nn.sigmoid(logits)
            _, choice = lax.top_k(scores + lp["expert_bias"], k)
            gates = jnp.take_along_axis(scores, choice, axis=-1)
            if cfg.norm_topk_prob:
                gates = gates / (jnp.sum(gates, -1, keepdims=True) + 1e-20)
            gates = gates * cfg.routed_scaling_factor
        elif cfg.norm_topk_prob:
            top, choice = lax.top_k(logits, k)                   # [t, k]
            gates = jax.nn.softmax(top, axis=-1)
        else:
            _, choice = lax.top_k(logits, k)
            gates = jnp.take_along_axis(jax.nn.softmax(logits, axis=-1),
                                        choice, axis=-1) \
                * cfg.routed_scaling_factor
        held = (choice >= lo) & (choice < hi)
        absent = ~held
        if live is not None:
            held, absent = held & live[:, None], absent & live[:, None]
    with jax.named_scope("experts"):
        pairs = t * k
        tile, rows = expert_tile(cfg, t)
        # sort the pairs by held expert; n_held stands for "not held here"
        expert = jnp.where(held, choice - lo, n_held).reshape(pairs)
        order = jnp.argsort(expert, stable=True)
        expert_s = expert[order]
        counts = jnp.sum(expert[:, None] == jnp.arange(n_held)[None, :],
                         axis=0, dtype=jnp.int32)                # [n_held]
        padded = -(-counts // tile) * tile
        ends = jnp.cumsum(padded)
        first = jnp.cumsum(counts) - counts      # in the sorted order
        e = jnp.minimum(expert_s, n_held - 1)
        dest_s = jnp.where(expert_s < n_held, (ends - padded)[e]
                           + jnp.arange(pairs) - first[e], rows)
        token_s = order // k
        x = jnp.zeros((rows, d), dtype).at[dest_s].set(h[token_s],
                                                       mode="drop")
        # a tile's expert: how many experts end at or before its first row
        tile_expert = jnp.minimum(jnp.sum(
            ends[None, :] <= (jnp.arange(rows // tile) * tile)[:, None],
            axis=1), n_held - 1)
        tiles_live = ends[-1] // tile
        out = grouped_matmul.expert_tiles(x, tile_expert, tiles_live,
                                          lp["w_in"], lp["w_out"], tile)
        # back to the tokens: each pair's row, weighted by its gate
        dest = jnp.zeros(pairs, jnp.int32).at[order].set(dest_s)
        picked = out[jnp.minimum(dest, rows - 1)].reshape(t, k, d)
        y = jnp.einsum("tk,tkd->td", jnp.where(held, gates, 0.0), picked)
    return y, {
        "moe_tokens_held": jnp.sum(held, dtype=jnp.int32),
        "moe_tokens_absent": jnp.sum(absent, dtype=jnp.int32),
        "moe_expert_load_max": jnp.max(counts),
        "moe_experts_hit": jnp.sum(counts > 0, dtype=jnp.int32),
        "moe_layer_steps": jnp.int32(1),
        "moe_tiles_live": tiles_live.astype(jnp.int32),
        "moe_tiles_grid": jnp.int32(rows // tile),
    }


def _expert_layer(x, lp, cfg: HybridConfig, live=None):
    """``x + r . (moe(h) + shared(h))`` with one norm for both branches;
    ``x [b, s, d]``. ``live``: the rows that are routed, ``[b]`` (a lane's
    every token) or ``[b, s]`` (token by token: a prompt's padding)."""
    b, s, d = x.shape
    h = _rmsnorm(x, lp["ln2"], cfg.rms_eps).reshape(b * s, d)
    if live is not None:
        live = jnp.repeat(live, s) if live.ndim == 1 else live.reshape(b * s)
    routed, counts = moe_ffn(h, lp, cfg, live)
    with jax.named_scope("shared_ffn"):
        shared = _gated(h, lp["shared_in"], lp["shared_out"], cfg.dtype)
        if cfg.shared_gate:
            shared = shared * jax.nn.sigmoid(jnp.dot(
                h, lp["shared_gate"].astype(cfg.dtype),
                preferred_element_type=jnp.float32))[:, None]
    y = (routed + shared).astype(cfg.dtype).reshape(b, s, d)
    return x + cfg.residual_multiplier * y, counts


# -- the state-space mixer ---------------------------------------------------

def _ssm_project(h, lp, cfg: HybridConfig):
    """``[z | xBC | dt] = h . w_in``: the gate, the convolution's input
    (x, B and C side by side) and the per-head step."""
    zxd = jnp.dot(h, lp["ssm_in"].astype(cfg.dtype),
                  preferred_element_type=jnp.float32)
    z, xbc, dt = jnp.split(
        zxd, [cfg.d_inner, cfg.d_inner + cfg.conv_dim], axis=-1)
    return z, xbc.astype(cfg.dtype), dt


def _ssm_split(xbc, dt, lp, cfg: HybridConfig):
    """The activated convolution's output as ``x [.., heads, head_dim]``,
    ``B`` and ``C [.., state]`` (float32), with the step ``softplus(dt +
    dt_bias)`` and ``A = -exp(A_log)``."""
    x, bm, cm = jnp.split(xbc.astype(jnp.float32),
                          [cfg.d_inner, cfg.d_inner + cfg.ssm_state],
                          axis=-1)
    x = x.reshape(x.shape[:-1] + (cfg.ssm_heads, cfg.ssm_head_dim))
    return (x, bm, cm, jax.nn.softplus(dt + lp["dt_bias"]),
            -jnp.exp(lp["A_log"]))


def _ssm_finish(y, x, z, lp, cfg: HybridConfig):
    """``D`` skip, gate, then the norm over all channels (one group), then
    the output projection."""
    y = y + lp["D"][:, None] * x
    y = y.reshape(y.shape[:-2] + (cfg.d_inner,)) * jax.nn.silu(z)
    y = _rmsnorm(y, lp["norm"], cfg.rms_eps).astype(cfg.dtype)
    return jnp.dot(y, lp["ssm_out"].astype(cfg.dtype),
                   preferred_element_type=jnp.float32).astype(cfg.dtype)


def ssd_chunked(x, dt, a, bm, cm, chunk: int):
    """The recurrence ``S_t = exp(dt_t a) S_{t-1} + dt_t x_t (x) B_t``,
    ``y_t = S_t . C_t`` from a zero state, computed chunk by chunk: inside
    a chunk as one masked matrix product, between chunks as the recurrence
    on whole-chunk states. ``x [b, s, h, p]``, ``dt [b, s, h]``, ``a [h]``
    (negative), ``bm``/``cm [b, s, n]``, all float32. Returns ``(y [b, s,
    h, p], S [b, h, p, n])``. A position whose ``dt`` is 0 passes the state through
    unchanged, which is how padding is kept out of it."""
    b, s_in, h, p = x.shape
    q = min(chunk, s_in)
    if s_in % q:  # whole chunks: the rows added have dt 0
        pad = [(0, 0), (0, q - s_in % q)]
        x, dt = jnp.pad(x, pad + [(0, 0)] * 2), jnp.pad(dt, pad + [(0, 0)])
        bm, cm = jnp.pad(bm, pad + [(0, 0)]), jnp.pad(cm, pad + [(0, 0)])
    s = x.shape[1]
    c = s // q
    xdt = (x * dt[..., None]).reshape(b, c, q, h, p)
    bm, cm = bm.reshape(b, c, q, -1), cm.reshape(b, c, q, -1)
    acs = jnp.cumsum((dt * a).reshape(b, c, q, h), axis=2)  # <= 0, falling
    # inside a chunk: y_i = sum_{j<=i} exp(acs_i - acs_j) (C_i.B_j) xdt_j
    lower = jnp.tril(jnp.ones((q, q), bool))[None, None, :, :, None]
    seg = acs[:, :, :, None, :] - acs[:, :, None, :, :]     # [b,c,i,j,h]
    decay = jnp.where(lower, jnp.exp(jnp.where(lower, seg, 0.0)), 0.0)
    cb = jnp.einsum("bcin,bcjn->bcij", cm, bm, precision=_HI)
    y = jnp.einsum("bcijh,bcjhp->bcihp", cb[..., None] * decay, xdt,
                   precision=_HI)
    # each chunk's own contribution to the state at its end
    to_end = jnp.exp(acs[:, :, -1:, :] - acs)               # [b,c,q,h]
    states = jnp.einsum("bcjh,bcjhp,bcjn->bchpn", to_end, xdt, bm,
                        precision=_HI)
    chunk_decay = jnp.exp(acs[:, :, -1, :])                 # [b,c,h]
    state = jnp.zeros((b, h, p, bm.shape[-1]), jnp.float32)
    entering = []
    for i in range(c):
        entering.append(state)
        state = state * chunk_decay[:, i, :, None, None] + states[:, i]
    # what the state entering a chunk adds to the chunk's outputs
    y = y + jnp.einsum("bcin,bchpn,bcih->bcihp", cm,
                       jnp.stack(entering, axis=1), jnp.exp(acs),
                       precision=_HI)
    return y.reshape(b, s, h, p)[:, :s_in], state


def _conv_prefill(x, conv_w, lengths, w: int):
    """The causal depthwise convolution of a recurrent mixer over
    right-padded rows ``x [b, s, c]`` (``conv_w [w, c]``, no bias): ``(conv
    [b, s, c] float32, tail [b, w-1, c])``, the tail being the last ``w -
    1`` REAL rows of the input, zeros where the row is shorter."""
    s = x.shape[1]
    shifted = jnp.pad(x, ((0, 0), (w - 1, 0), (0, 0)))
    conv = sum(shifted[:, i:i + s].astype(jnp.float32) * conv_w[i]
               for i in range(w))
    idx = lengths[:, None] - (w - 1) + jnp.arange(w - 1)[None, :]
    tail = jnp.take_along_axis(x, jnp.maximum(idx, 0)[..., None], axis=1)
    return conv, jnp.where((idx >= 0)[..., None], tail, 0)


def _conv_decode(x, conv_w, tail, live):
    """The same convolution for one new row a lane, over the lane's stored
    tail: ``(conv [b, c] float32, tail)``. An empty lane reads zeros and
    keeps what its slot holds."""
    rows = jnp.concatenate([jnp.where(live[:, None, None], tail, 0),
                            x[:, None]], axis=1)                # [b,w,c]
    conv = jnp.einsum("bwc,wc->bc", rows.astype(jnp.float32), conv_w)
    return conv, jnp.where(live[:, None, None], rows[:, 1:], tail)


def _ssm_prefill(h, lp, lengths, cfg: HybridConfig):
    """The mixer over a whole right-padded prompt ``h [b, s, d]``:
    ``(out [b, s, d], state [b, heads, head_dim, n], tail [b, conv-1,
    conv_dim])`` — the state after each row's last real token and the
    last ``conv - 1`` real rows of the convolution's input."""
    b, s, _ = h.shape
    w = cfg.ssm_conv
    with jax.named_scope("ssm_in"):
        z, xbc, dt = _ssm_project(h, lp, cfg)
    with jax.named_scope("ssm_conv"):
        conv, tail = _conv_prefill(xbc, lp["conv_w"], lengths, w)
        x, bm, cm, step, a = _ssm_split(jax.nn.silu(conv + lp["conv_b"]),
                                        dt, lp, cfg)
    with jax.named_scope("ssm_scan"):
        real = jnp.arange(s)[None, :] < lengths[:, None]
        y, state = ssd_chunked(x, jnp.where(real[..., None], step, 0.0), a,
                               bm, cm, cfg.ssm_chunk)
    with jax.named_scope("ssm_out"):
        out = _ssm_finish(y, x, z, lp, cfg)
    return out, state.astype(cfg.ssm_state_dtype), tail


def _state_step(rule: str, state, live, operands):
    """One token of every lane's recurrent state through
    ``ops/lane_state.py``: ``(out, state)``. ``state`` is a
    :class:`lane_ops.LaneSlot` (the arena and the layer: updated in place
    and handed back as one) or one layer's ``[b, heads, rows, cols]``
    alone, which is an arena of one layer."""
    if isinstance(state, lane_ops.LaneSlot):
        return lane_ops.update(rule, state, live, operands)
    out, (states, _) = lane_ops.update(
        rule, lane_ops.LaneSlot(state[None], 0), live, operands)
    return out, states[0]


def _ssm_decode(h, lp, state, tail, live, cfg: HybridConfig):
    """One token for every lane: ``h [b, d]``, ``state`` the layer's slot
    of the state arena (:func:`_state_step`: ``[b, heads, head_dim, n]``
    a lane), ``tail [b, conv-1, conv_dim]`` → ``(out [b, d], state,
    tail)``. An empty lane (``live`` false) reads zeros and keeps what its
    slot holds."""
    with jax.named_scope("ssm_in"):
        z, xbc, dt = _ssm_project(h, lp, cfg)
    with jax.named_scope("ssm_conv"):
        conv, new_tail = _conv_decode(xbc, lp["conv_w"], tail, live)
        x, bm, cm, step, a = _ssm_split(jax.nn.silu(conv + lp["conv_b"]),
                                        dt, lp, cfg)
    with jax.named_scope("ssm_update"):
        y, new_state = _state_step(lane_ops.MAMBA2, state, live,
                                   (x, step, a, bm, cm))
    with jax.named_scope("ssm_out"):
        out = _ssm_finish(y, x, z, lp, cfg)
    return out, new_state, new_tail


# -- the gated delta rule mixer ---------------------------------------------

def _la_project(h, lp, cfg: HybridConfig):
    """``[q | k | v | z] = h . la_in`` and ``[b | a] = h . la_ba``: the
    convolution's input (q, k and v side by side), the output gate, and
    per value head the write strength ``sigmoid(b)`` and the log decay
    ``-exp(A_log) softplus(a + dt_bias)``."""
    qkvz = jnp.dot(h, lp["la_in"].astype(cfg.dtype),
                   preferred_element_type=jnp.float32)
    qkv, z = jnp.split(qkvz, [cfg.la_conv_dim], axis=-1)
    b, a = jnp.split(jnp.dot(h, lp["la_ba"].astype(cfg.dtype),
                             preferred_element_type=jnp.float32), 2, axis=-1)
    g = -jnp.exp(lp["A_log"]) * jax.nn.softplus(a + lp["dt_bias"])
    return qkv.astype(cfg.dtype), z, g, jax.nn.sigmoid(b)


def _la_split(qkv, cfg: HybridConfig):
    """The activated convolution's output as float32 ``q``, ``k [.., value
    heads, key]`` (each key head repeated over its value heads, both of
    length one, q scaled by ``key ** -0.5``) and ``v [.., value heads,
    value]``."""
    q, k, v = jnp.split(qkv.astype(jnp.float32),
                        [cfg.la_key_width, 2 * cfg.la_key_width], axis=-1)
    heads = q.shape[:-1] + (cfg.la_key_heads, cfg.la_key_dim)
    group = cfg.la_value_heads // cfg.la_key_heads
    q, k = (jnp.repeat(l2norm(x.reshape(heads)), group, axis=-2)
            for x in (q, k))
    return (q * cfg.la_key_dim ** -0.5, k,
            v.reshape(v.shape[:-1] + (cfg.la_value_heads, cfg.la_value_dim)))


def _la_finish(o, z, lp, cfg: HybridConfig):
    """The norm over each head's values (one scale for all heads), the
    gate, then the output projection."""
    z = z.reshape(o.shape)
    y = _rmsnorm(o, lp["norm"], cfg.rms_eps) * jax.nn.silu(z)
    y = y.reshape(y.shape[:-2] + (cfg.la_value_width,)).astype(cfg.dtype)
    return jnp.dot(y, lp["la_out"].astype(cfg.dtype),
                   preferred_element_type=jnp.float32).astype(cfg.dtype)


def _la_prefill(h, lp, lengths, cfg: HybridConfig):
    """The mixer over a whole right-padded prompt ``h [b, s, d]``: ``(out
    [b, s, d], state [b, value heads, key, value], tail [b, conv-1,
    channels])``, as :func:`_ssm_prefill` hands them over."""
    with jax.named_scope("la_in"):
        qkv, z, g, beta = _la_project(h, lp, cfg)
    with jax.named_scope("la_conv"):
        conv, tail = _conv_prefill(qkv, lp["conv_w"], lengths, cfg.la_conv)
        q, k, v = _la_split(jax.nn.silu(conv), cfg)
    with jax.named_scope("la_scan"):
        real = (jnp.arange(h.shape[1])[None, :] < lengths[:, None])[..., None]
        o, state = gated_delta_chunked(
            q, k, v, jnp.where(real, g, 0.0), jnp.where(real, beta, 0.0),
            cfg.la_chunk)
    with jax.named_scope("la_out"):
        out = _la_finish(o, z, lp, cfg)
    return out, state.astype(cfg.ssm_state_dtype), tail


def _la_decode(h, lp, state, tail, live, cfg: HybridConfig):
    """One token for every lane, as :func:`_ssm_decode`: ``h [b, d]``,
    ``state`` the layer's slot (``[b, value heads, key, value]`` a lane),
    ``tail [b, conv-1, channels]`` → ``(out [b, d], state, tail)``."""
    with jax.named_scope("la_in"):
        qkv, z, g, beta = _la_project(h, lp, cfg)
    with jax.named_scope("la_conv"):
        conv, new_tail = _conv_decode(qkv, lp["conv_w"], tail, live)
        q, k, v = _la_split(jax.nn.silu(conv), cfg)
    with jax.named_scope("la_update"):
        o, new_state = _state_step(lane_ops.GATED_DELTA, state, live,
                                   (q, k, v, g, beta))
    with jax.named_scope("la_out"):
        out = _la_finish(o, z, lp, cfg)
    return out, new_state, new_tail


# -- the attention mixer -----------------------------------------------------

def _rope(x, positions, rotary_dim: int, theta: float, freqs=None):
    """Rotary positions on the first ``rotary_dim`` dims of each head,
    half-split (dim ``i`` turns with dim ``i + rotary_dim / 2``); the rest
    pass. ``x [b, s, h, c]``, ``positions [b, s]``. ``freqs
    [rotary_dim / 2]``: the angle a position turns each pair by, where it
    is not ``theta ** (-2 i / rotary_dim)`` (scaled frequencies)."""
    half = rotary_dim // 2
    if freqs is None:
        freqs = jnp.exp(-np.log(theta) * jnp.arange(half) / half)
    angles = positions[..., None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(angles)[:, :, None], jnp.sin(angles)[:, :, None]
    x32 = x.astype(jnp.float32)
    x1, x2 = x32[..., :half], x32[..., half:rotary_dim]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos,
                            x32[..., rotary_dim:]], axis=-1).astype(x.dtype)


def _qkv(h, lp, positions, cfg: HybridConfig, rotary_dim=None):
    """``(q, k, v, gate)`` of ``h [b, s, d]`` at ``positions [b, s]``:
    the projections, then what the configuration asks of queries and keys
    (a norm over each head, rotary positions); ``gate`` is None without
    ``attn_gate``. ``rotary_dim``: this layer's, where the kinds of
    attention layer differ in it (default: the configuration's)."""
    dtype = cfg.dtype
    rotary_dim = cfg.rotary_dim if rotary_dim is None else rotary_dim
    q = jnp.einsum("bsd,dhc->bshc", h, lp["wq"].astype(dtype))
    k = jnp.einsum("bsd,dhc->bshc", h, lp["wk"].astype(dtype))
    v = jnp.einsum("bsd,dhc->bshc", h, lp["wv"].astype(dtype))
    gate = jnp.einsum("bsd,dhc->bshc", h, lp["wg"].astype(dtype)) \
        if cfg.attn_gate else None
    if cfg.qk_norm:
        q = _rmsnorm(q, lp["q_norm"], cfg.rms_eps)
        k = _rmsnorm(k, lp["k_norm"], cfg.rms_eps)
    if rotary_dim:
        q = _rope(q, positions, rotary_dim, cfg.rope_theta)
        k = _rope(k, positions, rotary_dim, cfg.rope_theta)
    return q, k, v, gate


def _attn_out(a, gate, lp, dtype):
    if gate is not None:
        a = (a * jax.nn.sigmoid(gate.astype(jnp.float32))).astype(dtype)
    return jnp.einsum("bshc,hcd->bsd", a, lp["wo"].astype(dtype))


def _logits(x, params, cfg: HybridConfig):
    x = _rmsnorm(x, params["ln_f"], cfg.rms_eps)
    head = params["embed" if cfg.tie_embeddings else "lm_head"]
    return jnp.einsum("bd,vd->bv", x, head.astype(cfg.dtype),
                      preferred_element_type=jnp.float32) \
        / cfg.logits_scaling


def _embed(params, tokens, cfg: HybridConfig):
    return (params["embed"][tokens].astype(jnp.float32)
            * cfg.embedding_multiplier).astype(cfg.dtype)


def lane_state(cfg: HybridConfig) -> Dict[str, tuple]:
    """What a decode lane holds beside its blocks: leaf -> (shape after
    the ``[layers, lanes]`` axes, dtype), and the number of layers. The
    recurrent state is ``ssm`` and the convolution's tail ``conv``,
    whichever recurrence the pattern holds."""
    if cfg.la_layers:
        layers, w, channels = cfg.la_layers, cfg.la_conv, cfg.la_conv_dim
        state = (cfg.la_value_heads, cfg.la_key_dim, cfg.la_value_dim)
    else:
        layers, w, channels = cfg.ssm_layers, cfg.ssm_conv, cfg.conv_dim
        state = (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state)
    return {"layers": layers, "ssm": (state, cfg.ssm_state_dtype),
            "conv": ((w - 1, channels), cfg.dtype)}


def state_update(cfg: HybridConfig, lanes: int) -> str:
    """The form the decode step updates ``lanes`` lanes' recurrent state
    in here: ``"lane_kernel"`` or ``"reference"`` (``ops/lane_state.py``)."""
    spec = lane_state(cfg)
    shape, dtype = spec["ssm"]
    return lane_ops.state_update_form(
        lane_ops.GATED_DELTA if cfg.la_layers else lane_ops.MAMBA2,
        jax.ShapeDtypeStruct((spec["layers"], lanes) + tuple(shape), dtype))


def build_prefill(cfg: HybridConfig, max_seq: Optional[int] = None,
                  attention_fn: Optional[Callable] = None,
                  kv_codec: Optional[str] = None) -> Callable:
    """``prefill(params, tokens[int32 b, s], lengths[int32 b]) -> (logits[b,
    vocab], cache)`` over right-padded prompts. ``cache`` is what the pool
    scatters: ``kv [attention layers, 2, b, s, kv heads, head_dim]`` (at
    the bucket's length; slots past a row's length hold padding that
    decode overwrites before it reads, as in the dense prefill) and, per
    state-space layer, the recurrent state after the row's LAST REAL token
    and the convolution's last real input rows."""
    del max_seq, kv_codec  # the cache is the bucket's length, stored raw

    @jax.named_scope("nns.prefill")
    def prefill(params, tokens, lengths=None):
        b, s = tokens.shape
        lengths = jnp.full((b,), s, jnp.int32) if lengths is None \
            else jnp.asarray(lengths, jnp.int32)
        x, cache = _prompt_layers(params, tokens, lengths, cfg, attention_fn)
        with jax.named_scope("logits"):
            last = jnp.take_along_axis(
                x, (lengths - 1)[:, None, None], axis=1)[:, 0]
            logits = _logits(last, params, cfg)
        return logits, cache

    return prefill


def _prompt_layers(params, tokens, lengths, cfg: HybridConfig,
                   attention_fn: Optional[Callable] = None):
    """Every layer over whole right-padded prompts: the residual stream
    ``[b, s, d]`` after the last layer, and what the layers leave behind
    for decoding (``build_prefill``'s cache)."""
    from nnstreamer_tpu.ops.flash_attention import attention_reference

    attn = attention_fn or attention_reference
    dtype, r = cfg.dtype, cfg.residual_multiplier
    x = _embed(params, tokens, cfg)
    positions = jnp.broadcast_to(jnp.arange(tokens.shape[1]), tokens.shape)
    # a prompt's padding, and a row that holds no prompt, are left out of
    # the routing
    real = positions < lengths[:, None]
    kv, ssm, conv = [], [], []
    for kind, lp in zip(cfg.layer_types, params["layers"]):
        h = _rmsnorm(x, lp["ln1"], cfg.rms_eps)
        if kind != ATTENTION:
            mixer = _ssm_prefill if kind == MAMBA else _la_prefill
            out, state, tail = mixer(h, lp, lengths, cfg)
            ssm.append(state)
            conv.append(tail)
        else:
            with jax.named_scope("qkv"):
                q, k, v, gate = _qkv(h, lp, positions, cfg)
            with jax.named_scope("attend"):
                a = attn(q, k, v, scale=cfg.attention_scale)
                out = _attn_out(a, gate, lp, dtype)
            kv.append(jnp.stack([k, v]))
        x = x + r * out
        x, _ = _expert_layer(x, lp, cfg, real)
    return x, {"kv": jnp.stack(kv), "state": {
        "ssm": jnp.stack(ssm), "conv": jnp.stack(conv)}}


def build_paged_decode_step(cfg: HybridConfig, block_tokens: int,
                            max_seq: Optional[int] = None,
                            kv_codec: Optional[str] = None,
                            paged_attention_fn: Optional[Callable] = None
                            ) -> Callable:
    """One token for every decode lane against the pool's two arenas:
    ``step(params, token[int32 b], arenas, bt[int32 b, MB], pos[int32 b])
    -> (logits[b, vocab], arenas, counts)``.

    ``arenas["kv"]`` is the block arena of the attention layers, addressed
    as ``build_paged_decode_step`` of the dense block addresses it
    (scatter at ``(layer, block, slot)``, gather by table, the sentinel
    rules). ``arenas["state"]`` holds one slot per LANE and state-space
    layer: lane ``i`` reads and writes slot ``i``, in place — the layer is
    a static index, the arena a carry of the K-step scan, never a scan's
    ``xs``/``ys``. A lane whose table is all sentinel is empty: it reads
    zeros, writes nowhere and is left out of the routing.
    ``paged_attention_fn`` as in the dense block's builder."""
    dtype, r = cfg.dtype, cfg.residual_multiplier
    s_max = max_seq or cfg.max_seq
    T = int(block_tokens)
    if T <= 0 or s_max % T:
        raise ValueError(
            f"build_paged_decode_step: max_seq ({s_max}) must be a "
            f"positive multiple of block_tokens ({block_tokens})")
    codec = _kv_codec(cfg, kv_codec)

    @jax.named_scope("nns.decode")
    def step(params, token, arenas, bt, pos):
        pos = jnp.asarray(pos, jnp.int32)
        pos_c = jnp.minimum(pos, s_max - 1)
        pages, state = arenas["kv"], dict(arenas["state"])
        live = bt[:, 0] < pages.shape[1]
        blk = jnp.take_along_axis(bt, (pos_c // T)[:, None], axis=1)
        off = (pos_c % T)[:, None]
        x = _embed(params, token, cfg)[:, None]                 # [b,1,d]
        counts = {name: jnp.int32(0) for name in COUNTERS}
        i_ssm = i_attn = 0
        for kind, lp in zip(cfg.layer_types, params["layers"]):
            h = _rmsnorm(x, lp["ln1"], cfg.rms_eps)
            if kind != ATTENTION:
                mixer, scope = (_ssm_decode, "ssm") if kind == MAMBA \
                    else (_la_decode, "la")
                out, slot, tail = mixer(
                    h[:, 0], lp, lane_ops.LaneSlot(state["ssm"], i_ssm),
                    state["conv"][i_ssm], live, cfg)
                state["ssm"] = slot.arena
                with jax.named_scope(scope + "_conv"):
                    state["conv"] = state["conv"].at[i_ssm].set(tail)
                out = out[:, None]
                i_ssm += 1
            else:
                with jax.named_scope("qkv"):
                    q, k, v, gate = _qkv(h, lp, pos_c[:, None], cfg)
                with jax.named_scope("kv_write"):
                    pages = codec.paged_write(pages, i_attn,
                                              jnp.stack([k, v]), blk, off)
                if paged_attention_fn is not None:
                    a = paged_attention_fn(q, pages, i_attn, bt, pos_c,
                                           scale=cfg.attention_scale,
                                           heads_major=codec.heads_major)
                else:
                    with jax.named_scope("kv_gather"):
                        mask = jnp.arange(s_max)[None, None, None, :] \
                            <= pos_c[:, None, None, None]
                        ck, cv = codec.paged_read(pages, i_attn, bt)
                    with jax.named_scope("attend"):
                        a = _attend_cache(q, ck, cv, mask, cfg.head_dim,
                                          dtype, scale=cfg.attention_scale)
                with jax.named_scope("attend"):
                    out = _attn_out(a, gate, lp, dtype)
                i_attn += 1
            x = x + r * out
            x, c = _expert_layer(x, lp, cfg, live)
            counts = {name: counts[name] + c[name] for name in COUNTERS}
        with jax.named_scope("logits"):
            logits = _logits(x[:, 0], params, cfg)
        return logits, {"kv": pages, "state": state}, counts

    return step


def build_forward(cfg: HybridConfig) -> Callable:
    """``forward(params, tokens[int32 b, s]) -> logits[b, s, vocab]``: the
    prefill's layers with every position's logits (tests, smoke checks;
    the served path is prefill + decode)."""

    def forward(params, tokens):
        b, s = tokens.shape
        x, _ = _prompt_layers(params, tokens, jnp.full((b,), s, jnp.int32),
                              cfg)
        return _logits(x.reshape(b * s, -1), params, cfg).reshape(b, s, -1)

    return forward


HYBRID = ModelFamily(
    name="hybrid", init_params=init_params, build_prefill=build_prefill,
    build_paged_decode_step=build_paged_decode_step,
    kv_entry=lambda cfg: (cfg.attn_layers, 2,
                          (cfg.n_kv_heads, cfg.head_dim)),
    lane_state=lane_state, counters=COUNTERS, expert_matmul=expert_matmul,
    state_update=state_update, token_share=routed_share,
    refusal="keeps recurrent state per decode lane, which nothing can copy, "
            "share, shard or narrow yet (ROADMAP.md R3; mesh: R2)",
    # every matrix but the embedding, whose lookup (``_embed``) widens the
    # STORED rows to float32; ``init_params`` stores all of them in
    # ``param_dtype``, which is ``dtype`` unless a caller says otherwise
    read_in_dtype=("ssm_in", "ssm_out", "la_in", "la_ba", "la_out", "wq",
                   "wk", "wv", "wo", "wg", "router", "w_in", "w_out",
                   "shared_in", "shared_out", "shared_gate", "lm_head"))
