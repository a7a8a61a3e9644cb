"""Window-and-full attention decoder LM with sandwich norms: the layers'
mixers are gated grouped-query attention of TWO kinds under one layer
pattern, the first layers end in a dense gated MLP and the rest in the
routed expert layer of ``models/hybrid.py`` with sigmoid scores — the
fourth member of the serving engine's model family (``models/family.py``).

What is different from the other three, and why it is a module of its own:

- **Two kinds of key-value state.** A ``sliding_attention`` layer's query
  ``i`` sees key ``j`` iff ``0 <= i - j < window``; a ``full_attention``
  layer sees everything before it. Both keep keys and values per head in a
  paged block arena of the same entry form, but NOT the same arena: the
  full layers' arena keeps every block of a stream for life, the window
  layers' arena (``BlockPool.win``) gets a lane's blocks back once they lie
  wholly behind the window. The family states the second kind
  (``kv_window``), the decode step takes both arenas and both tables
  (``{"kv": .., "win": ..}``), and prefill hands over the whole prompt's
  rows for both (the pool scatters the window layers' LAST blocks only).
- **Positions on the window layers only**: rotary over the whole head
  there, none at all on the full layers (``hybrid._qkv`` with the layer's
  own ``rotary_dim``). Every layer norms each query and key head first and
  gates the attention's output (``wg``), as ``hybrid.py`` does it.
- **Sandwich norms**: four norms a layer. ``x = x + ln1_post(attn(ln1(x)))``
  and ``x = x + ln2_post(ffn(ln2(x)))``.
- **Sigmoid scores with a selection bias** (``hybrid.moe_ffn``
  ``score_func``): the chosen are the largest of ``sigmoid(logit) +
  expert_bias``, the gates the chosen's own scores over their sum, times
  ``routed_scaling_factor``; the bias moves the choice and never a gate.
- The embedding is scaled by ``sqrt(d_model)``; the head is untied.

No lane state. The family brings none of ``prefix_cache``, ``speculate``,
``prefill_chunk``, ``kv_quant`` and ``mesh`` (``refusal``).

Parameters are a plain pytree: ``embed``, ``ln_f``, ``lm_head`` and
``layers`` (one dict a layer: ``ln1``, ``ln1_post``, ``ln2``, ``ln2_post``,
``wq``/``wg [d, heads, head_dim]``, ``wk``/``wv [d, kv heads, head_dim]``,
``q_norm``/``k_norm [head_dim]``, ``wo [heads, head_dim, d]`` and either
``dense_in`` / ``dense_out`` or the expert layer's leaves as ``hybrid.py``
names them plus ``expert_bias [num_experts]`` float32).
"""

from __future__ import annotations

import dataclasses
import functools
import itertools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from nnstreamer_tpu.models import hybrid
from nnstreamer_tpu.models.family import ModelFamily
from nnstreamer_tpu.models.hybrid import (
    COUNTERS,
    _attn_out,
    _embed,
    _gated,
    _logits,
    _qkv,
    _rmsnorm,
    moe_ffn,
)
from nnstreamer_tpu.models.transformer import _kv_codec

SLIDING, FULL = "sliding_attention", "full_attention"


@dataclasses.dataclass(frozen=True)
class AfmoeConfig:
    vocab: int = 200192
    d_model: int = 3072
    #: the layer pattern is data: ``SLIDING`` or ``FULL`` a layer
    layer_types: Tuple[str, ...] = (SLIDING,) * 3 + (FULL,)
    #: the first layers end in a dense gated MLP of ``dense_width``
    num_dense_layers: int = 0
    n_heads: int = 48
    n_kv_heads: int = 8
    head_dim: int = 128
    #: a window layer's query ``i`` sees key ``j`` iff ``0 <= i - j < window``
    window: int = 4096
    rope_theta: float = 10000.0
    dense_width: int = 12288
    # expert layer (``hybrid.moe_ffn`` reads these)
    num_experts: int = 256
    experts_per_token: int = 4
    expert_width: int = 3072
    shared_width: int = 3072
    experts_held: Tuple[int, int] = (0, 256)
    score_func: str = "sigmoid"
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.448
    rms_eps: float = 1e-5
    max_seq: int = 4096
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16
    # what the routines shared with ``hybrid.py`` ask of a configuration
    # and this family has one value for
    qk_norm = True
    attn_gate = True
    tie_embeddings = False
    logits_scaling = 1.0

    def __post_init__(self):
        lo, hi = self.experts_held
        if not self.layer_types or \
                set(self.layer_types) - {SLIDING, FULL}:
            raise ValueError(
                f"AfmoeConfig: layer_types must hold {SLIDING!r} and "
                f"{FULL!r} and nothing else, got {self.layer_types!r}")
        if not 0 <= lo < hi <= self.num_experts:
            raise ValueError(
                f"AfmoeConfig: experts_held {self.experts_held!r} must be a "
                f"non-empty range inside [0, {self.num_experts})")
        if self.head_dim % 2 or self.n_heads % self.n_kv_heads \
                or self.window <= 0 \
                or not 0 <= self.num_dense_layers <= self.n_layers:
            raise ValueError(
                f"AfmoeConfig: head_dim ({self.head_dim}) must be even, "
                f"n_heads ({self.n_heads}) a multiple of n_kv_heads "
                f"({self.n_kv_heads}), window ({self.window}) positive and "
                f"num_dense_layers ({self.num_dense_layers}) at most "
                f"n_layers ({self.n_layers})")

    @property
    def n_layers(self) -> int:
        return len(self.layer_types)

    @property
    def full_layers(self) -> int:
        return self.layer_types.count(FULL)

    @property
    def window_layers(self) -> int:
        return self.layer_types.count(SLIDING)

    @property
    def n_held(self) -> int:
        return self.experts_held[1] - self.experts_held[0]

    @property
    def attention_scale(self) -> float:
        return float(self.head_dim ** -0.5)

    @property
    def embedding_multiplier(self) -> float:
        return float(self.d_model ** 0.5)

    @property
    def family(self) -> ModelFamily:
        return AFMOE


def init_params(cfg: AfmoeConfig, seed: int = 0) -> Dict[str, Any]:
    """Seeded weights as ``hybrid.init_params`` makes them: each leaf on
    the default device by one small program, normal x 0.02 for every matrix
    and, float32, for the selection bias (zero would leave that path
    untested); ones for the norm scales."""
    D, H, Hk, C = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    keys = map(functools.partial(jax.random.fold_in,
                                 jax.random.PRNGKey(seed % (2 ** 31 - 1))),
               itertools.count())

    def mat(*shape, dtype=cfg.param_dtype):
        return hybrid._normal(next(keys), shape, dtype)

    def ones(*shape):
        return jnp.ones(shape, jnp.float32)

    layers = []
    for i in range(cfg.n_layers):
        p = {"ln1": ones(D), "ln1_post": ones(D), "ln2": ones(D),
             "ln2_post": ones(D), "q_norm": ones(C), "k_norm": ones(C),
             "wq": mat(D, H, C), "wk": mat(D, Hk, C), "wv": mat(D, Hk, C),
             "wg": mat(D, H, C), "wo": mat(H, C, D)}
        if i < cfg.num_dense_layers:
            p.update(dense_in=mat(D, 2 * cfg.dense_width),
                     dense_out=mat(cfg.dense_width, D))
        else:
            F, Fs = cfg.expert_width, cfg.shared_width
            p.update(router=mat(D, cfg.num_experts),
                     expert_bias=mat(cfg.num_experts, dtype=jnp.float32),
                     w_in=mat(cfg.n_held, D, 2 * F),
                     w_out=mat(cfg.n_held, F, D),
                     shared_in=mat(D, 2 * Fs), shared_out=mat(Fs, D))
        layers.append(p)
    return {"embed": mat(cfg.vocab, D), "ln_f": ones(D), "layers": layers,
            "lm_head": mat(cfg.vocab, D)}


def _layer_qkv(h, lp, positions, kind: str, cfg: AfmoeConfig):
    """``hybrid._qkv`` with the layer's own positions: rotary over the
    whole head on a window layer, none on a full one."""
    return _qkv(h, lp, positions, cfg,
                rotary_dim=cfg.head_dim if kind == SLIDING else 0)


def _mixer_out(x, a, gate, lp, cfg: AfmoeConfig):
    """``x + ln1_post((a * sigmoid(gate)) . wo)``."""
    with jax.named_scope("attn_out"):
        out = _attn_out(a, gate, lp, cfg.dtype)
    return x + _rmsnorm(out, lp["ln1_post"], cfg.rms_eps)


def _ffn(x, lp, cfg: AfmoeConfig, live=None):
    """The layer's second half between its two norms: a dense gated MLP
    where the layer has one, else the routed experts plus the shared MLP
    (ungated, unscaled); ``(x, counts or None)``. ``live [b]`` or ``[b,
    s]``: the rows that are routed."""
    b, s, d = x.shape
    h = _rmsnorm(x, lp["ln2"], cfg.rms_eps).reshape(b * s, d)
    if "dense_in" in lp:
        counts = None
        with jax.named_scope("dense_ffn"):
            y = _gated(h, lp["dense_in"], lp["dense_out"], cfg.dtype)
    else:
        if live is not None:
            live = jnp.repeat(live, s) if live.ndim == 1 \
                else live.reshape(b * s)
        routed, counts = moe_ffn(h, lp, cfg, live)
        with jax.named_scope("shared_ffn"):
            y = routed + _gated(h, lp["shared_in"], lp["shared_out"],
                                cfg.dtype)
    y = y.astype(cfg.dtype).reshape(b, s, d)
    return x + _rmsnorm(y, lp["ln2_post"], cfg.rms_eps), counts


def _prompt_layers(params, tokens, lengths, cfg: AfmoeConfig,
                   attention_fn: Optional[Callable] = None):
    """Every layer over whole right-padded prompts: the residual stream
    ``[b, s, d]`` after the last layer and the keys and values of both
    kinds, ``{"kv": [full layers, 2, b, s, kv heads, head_dim], "win":
    [window layers, ...]}``. A prompt's padding is left out of the
    routing, as in ``mla._prompt_layers``."""
    from nnstreamer_tpu.ops.flash_attention import attention_reference

    attn = attention_fn or attention_reference
    x = _embed(params, tokens, cfg)
    positions = jnp.broadcast_to(jnp.arange(tokens.shape[1]), tokens.shape)
    real = positions < lengths[:, None]
    rows = {FULL: [], SLIDING: []}
    for kind, lp in zip(cfg.layer_types, params["layers"]):
        h = _rmsnorm(x, lp["ln1"], cfg.rms_eps)
        with jax.named_scope("qkv"):
            q, k, v, gate = _layer_qkv(h, lp, positions, kind, cfg)
        if kind == SLIDING:
            with jax.named_scope("attend_window"):
                a = attn(q, k, v, scale=cfg.attention_scale,
                         window=cfg.window)
        else:
            with jax.named_scope("attend"):
                a = attn(q, k, v, scale=cfg.attention_scale)
        rows[kind].append(jnp.stack([k, v]))
        x, _ = _ffn(_mixer_out(x, a, gate, lp, cfg), lp, cfg, real)
    return x, {"kv": jnp.stack(rows[FULL]), "win": jnp.stack(rows[SLIDING])}


def build_prefill(cfg: AfmoeConfig, max_seq: Optional[int] = None,
                  attention_fn: Optional[Callable] = None,
                  kv_codec: Optional[str] = None) -> Callable:
    """``prefill(params, tokens[int32 b, s], lengths[int32 b]) -> (logits[b,
    vocab], cache)`` over right-padded prompts: the logits after each row's
    LAST REAL token, and what the pool scatters into its two arenas
    (``_prompt_layers``; slots past a row's length hold padding that decode
    overwrites before it reads). ``attention_fn`` is
    ``ops.flash_attention`` (it takes ``window=``) or None."""
    del max_seq
    _no_codec(kv_codec)

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


def _no_codec(kv_codec) -> None:
    if kv_codec not in (None, "raw"):
        raise ValueError(f"afmoe: no codec {kv_codec!r} over two arenas")


def build_paged_decode_step(cfg: AfmoeConfig, block_tokens: int,
                            max_seq: Optional[int] = None,
                            kv_codec: Optional[str] = None,
                            paged_attention_fn: Optional[Callable] = None
                            ) -> Callable:
    """One token for every decode lane against the pool's two block arenas:
    ``step(params, token[int32 b], arenas, bt, pos[int32 b]) -> (logits[b,
    vocab], arenas, counts)`` with ``arenas = {"kv": pages, "win":
    pages_w}`` and ``bt = {"kv": [b, MB], "win": [b, MB]}``.

    Both tables are indexed by a position's block (``pos // T``); an
    arena's own sentinel stands for a block that is not held: not yet
    allocated in either, and in the window arena also given back behind
    the window, where nothing reads any more. Each layer writes its row at
    ``(its index among its kind, block, slot)`` of its kind's arena and
    attends there: a full layer over slots ``0..pos``, a window layer over
    ``max(0, pos - window + 1)..pos``. A lane whose full table is all
    sentinel is empty: it writes nowhere and is left out of the routing.
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
    attend = paged_attention_fn or paged_attention_reference

    @jax.named_scope("nns.decode")
    def step(params, token, arenas, bt, pos):
        pos = jnp.asarray(pos, jnp.int32)
        pos_c = jnp.minimum(pos, s_max - 1)
        pages = dict(arenas)
        live = bt["kv"][:, 0] < pages["kv"].shape[1]
        at = (pos_c // T)[:, None]
        blk = {name: jnp.take_along_axis(bt[name], at, axis=1)
               for name in pages}
        off = (pos_c % T)[:, None]
        x = _embed(params, token, cfg)[:, None]                 # [b,1,d]
        counts = {name: jnp.int32(0) for name in COUNTERS}
        index = {"kv": 0, "win": 0}
        for kind, lp in zip(cfg.layer_types, params["layers"]):
            name, window = ("win", cfg.window) if kind == SLIDING \
                else ("kv", None)
            i = index[name]
            index[name] += 1
            h = _rmsnorm(x, lp["ln1"], cfg.rms_eps)
            with jax.named_scope("qkv"):
                q, k, v, gate = _layer_qkv(h, lp, pos_c[:, None], kind, cfg)
            with jax.named_scope("kv_write"):
                pages[name] = codec.paged_write(
                    pages[name], i, jnp.stack([k, v]), blk[name], off)
            # under the scope ``attend``, with a window ``attend_window``
            a = attend(q, pages[name], i, bt[name], pos_c,
                       scale=cfg.attention_scale,
                       heads_major=codec.heads_major, window=window)
            x, c = _ffn(_mixer_out(x, a, gate, lp, cfg), lp, cfg, live)
            if c is not None:
                counts = {n: counts[n] + c[n] for n in COUNTERS}
        with jax.named_scope("logits"):
            logits = _logits(x[:, 0], params, cfg)
        return logits, pages, counts

    return step


def build_forward(cfg: AfmoeConfig) -> Callable:
    """``forward(params, tokens[int32 b, s]) -> logits[b, s, vocab]``: the
    prefill's layers with every position's logits (tests; the served path
    is prefill + decode)."""

    def forward(params, tokens):
        b, s = tokens.shape
        x, _ = _prompt_layers(params, tokens, jnp.full((b,), s, jnp.int32),
                              cfg)
        return _logits(x.reshape(b * s, -1), params, cfg).reshape(b, s, -1)

    return forward


AFMOE = ModelFamily(
    name="afmoe", init_params=init_params, build_prefill=build_prefill,
    build_paged_decode_step=build_paged_decode_step,
    kv_entry=lambda cfg: (cfg.full_layers, 2,
                          (cfg.n_kv_heads, cfg.head_dim)),
    kv_window=lambda cfg: (cfg.window_layers, cfg.window),
    counters=COUNTERS, expert_matmul=hybrid.expert_matmul,
    refusal="keeps the window layers' keys and values in a block arena of "
            "their own whose blocks go back behind the window: nothing "
            "shares, chunks, narrows or shards two arenas yet (ROADMAP.md "
            "R4; mesh: R2)",
    read_in_dtype=("wq", "wk", "wv", "wg", "wo", "dense_in", "dense_out",
                   "router", "w_in", "w_out", "shared_in", "shared_out",
                   "lm_head"))
