"""Latent-attention decoder LM: every layer's mixer is multi-head latent
attention (one compressed key-value row a token, shared by all heads), the
first layers end in a dense gated MLP and the rest in the routed expert
layer of ``models/hybrid.py`` with its shared MLP — the third member of the
serving engine's model family (``models/family.py``).

What is different from the other two, and why it is a module of its own:

- **The cache is one row a token a layer**, ``[c | k_r]``: the latent ``c``
  (``kv_lora_rank`` wide, after its norm) and ONE rotary key ``k_r``
  (``qk_rope_dim`` wide, after the rotation) that every head shares. The
  family states that entry (``kv_entry``: one part of ``[row_store]``) and
  the pool's arena holds it as ``[layers, blocks, 1, T, row_store]``.
  ``row_store`` is ``rank + rope`` rounded up to whole vectors of 128
  lanes, the columns past ``rank + rope`` zeros: the chip tiles a leaf's
  last dimension so in any case (576 columns take the room of 640), and a
  kernel's DMA moves whole tiles only.
- **Two attention paths that must agree.** Prefill EXPANDS: keys and
  values per head come out of the latent through ``wkv_b``
  (``[k_nope | v]``), queries and keys are ``nope + rope`` wide, values
  ``v_head_dim``, and the flash kernel attends over them without ever
  holding the scores. Decode ABSORBS: ``wkv_b``'s key half is folded into
  the query (``q_lat = q_nope . W_kb^T``, one ``rank``-wide query a head),
  the scores are ``[q_lat | q_rope] . [c | k_r]`` against the arena's rows
  in place (``ops.paged_attention`` with ``v_width``), the probabilities
  weigh the rows' latent part, and ``wkv_b``'s value half is applied after
  the attention: the same numbers in another order of sums.
- **Scaled rotary frequencies** (YaRN: each pair's frequency blended
  between the plain one and the plain one over ``rope_factor`` by where its
  wavelength lies against the original window) and the softmax scale's
  ``mscale ** 2``.
- **Leading dense layers**, and gates that are the softmax over ALL router
  outputs at the chosen experts, not renormalised (``norm_topk_prob``
  false: ``hybrid.moe_ffn`` reads it from the configuration).

No lane state: a lane holds its blocks and nothing else. The family brings
none of ``prefix_cache``, ``speculate``, ``prefill_chunk``, ``kv_quant``
and ``mesh`` (``refusal``).

Parameters are a plain pytree: ``embed``, ``ln_f``, ``lm_head`` and
``layers`` (a list with one dict a layer: ``ln1``, ``wq [d, heads, nope +
rope]``, ``wkv_a [d, rank + rope]``, ``kv_norm [rank]``, ``wkv_b [rank,
heads, nope + v]``, ``wo [heads, v, d]``, ``ln2`` and either ``dense_in`` /
``dense_out`` or the expert layer's leaves as ``hybrid.py`` names them).
Rotary pairs are half-split as in ``hybrid._rope`` (a published checkpoint
interleaves them: storage).
"""

from __future__ import annotations

import dataclasses
import functools
import itertools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from nnstreamer_tpu.models import hybrid
from nnstreamer_tpu.models.family import ModelFamily
from nnstreamer_tpu.models.hybrid import (
    COUNTERS,
    _embed,
    _expert_layer,
    _gated,
    _logits,
    _rmsnorm,
    _rope,
)
from nnstreamer_tpu.models.transformer import _paged_scatter


@dataclasses.dataclass(frozen=True)
class MLAConfig:
    vocab: int = 102400
    d_model: int = 2048
    n_layers: int = 27
    n_heads: int = 16
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64
    v_head_dim: int = 128
    kv_lora_rank: int = 512
    rope_theta: float = 10000.0
    #: YaRN: positions beyond ``rope_original_max`` by this factor; 1: the
    #: plain frequencies and no ``mscale``
    rope_factor: float = 40.0
    rope_original_max: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 0.707
    rope_mscale_all_dim: float = 0.707
    #: the first layers end in a dense gated MLP of ``dense_width``
    first_dense_layers: int = 1
    dense_width: int = 10944
    # expert layer (``hybrid.moe_ffn`` and ``_expert_layer`` read these)
    num_experts: int = 64
    experts_per_token: int = 6
    expert_width: int = 1408
    shared_width: int = 2816
    experts_held: Tuple[int, int] = (0, 64)
    norm_topk_prob: bool = False
    routed_scaling_factor: float = 1.0
    rms_eps: float = 1e-6
    max_seq: int = 4096
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16
    # what the routines shared with ``hybrid.py`` ask of a configuration
    # and this family has one value for
    shared_gate = False
    score_func = "softmax"
    tie_embeddings = False
    embedding_multiplier = 1.0
    residual_multiplier = 1.0
    logits_scaling = 1.0

    def __post_init__(self):
        lo, hi = self.experts_held
        if not 0 <= lo < hi <= self.num_experts:
            raise ValueError(
                f"MLAConfig: experts_held {self.experts_held!r} must be a "
                f"non-empty range inside [0, {self.num_experts})")
        if self.qk_rope_dim % 2 or not \
                0 <= self.first_dense_layers <= self.n_layers:
            raise ValueError(
                f"MLAConfig: qk_rope_dim ({self.qk_rope_dim}) must be even "
                f"and first_dense_layers ({self.first_dense_layers}) at "
                f"most n_layers ({self.n_layers})")

    @property
    def n_held(self) -> int:
        return self.experts_held[1] - self.experts_held[0]

    @property
    def row_width(self) -> int:
        """The cache's entry for a token of a layer: ``[c | k_r]``."""
        return self.kv_lora_rank + self.qk_rope_dim

    @property
    def row_store(self) -> int:
        """The columns a row is held at: whole vectors of 128 lanes."""
        return -(-self.row_width // 128) * 128

    @property
    def family(self) -> ModelFamily:
        return MLA


def yarn_mscale(factor: float, mscale: float) -> float:
    return 0.1 * mscale * np.log(factor) + 1.0 if factor > 1 else 1.0


def softmax_scale(cfg: MLAConfig) -> float:
    """``(nope + rope) ** -0.5`` times ``mscale(all dims) ** 2``."""
    return float((cfg.qk_nope_dim + cfg.qk_rope_dim) ** -0.5
                 * yarn_mscale(cfg.rope_factor, cfg.rope_mscale_all_dim) ** 2)


def rotary_frequencies(cfg: MLAConfig) -> np.ndarray:
    """The angle a position turns each of the ``qk_rope_dim / 2`` pairs
    by, float32. Pair ``i`` of the plain table turns by ``theta ** (-2 i /
    dim)``; YaRN divides that by ``rope_factor`` for the pairs that turn
    fewer than ``beta_slow`` times over the original window, leaves those
    that turn more than ``beta_fast`` times, and blends linearly between
    (``low`` and ``high``: the pair indexes of those two)."""
    dim, half = cfg.qk_rope_dim, cfg.qk_rope_dim // 2
    plain = cfg.rope_theta ** (-np.arange(half, dtype=np.float64) / half)
    if cfg.rope_factor == 1:
        return plain.astype(np.float32)

    def pair_of(turns):
        return dim * np.log(cfg.rope_original_max / (2 * np.pi * turns)) \
            / (2 * np.log(cfg.rope_theta))

    low = max(int(np.floor(pair_of(cfg.rope_beta_fast))), 0)
    high = min(int(np.ceil(pair_of(cfg.rope_beta_slow))), dim - 1)
    ramp = np.clip((np.arange(half) - low) / max(high - low, 0.001), 0, 1)
    return (plain * ((1 - ramp) + ramp / cfg.rope_factor)).astype(np.float32)


def init_params(cfg: MLAConfig, seed: int = 0) -> Dict[str, Any]:
    """Seeded weights as ``hybrid.init_params`` makes them: each leaf on
    the default device by one small program, normal x 0.02 for every
    matrix, ones for the norm scales."""
    D, H, R = cfg.d_model, cfg.n_heads, cfg.kv_lora_rank
    keys = map(functools.partial(jax.random.fold_in,
                                 jax.random.PRNGKey(seed % (2 ** 31 - 1))),
               itertools.count())

    def mat(*shape):
        return hybrid._normal(next(keys), shape, cfg.param_dtype)

    def ones(*shape):
        return jnp.ones(shape, jnp.float32)

    layers = []
    for i in range(cfg.n_layers):
        p = {"ln1": ones(D), "ln2": ones(D), "kv_norm": ones(R),
             "wq": mat(D, H, cfg.qk_nope_dim + cfg.qk_rope_dim),
             "wkv_a": mat(D, cfg.row_width),
             "wkv_b": mat(R, H, cfg.qk_nope_dim + cfg.v_head_dim),
             "wo": mat(H, cfg.v_head_dim, D)}
        if i < cfg.first_dense_layers:
            p.update(dense_in=mat(D, 2 * cfg.dense_width),
                     dense_out=mat(cfg.dense_width, D))
        else:
            F, Fs = cfg.expert_width, cfg.shared_width
            p.update(router=mat(D, cfg.num_experts),
                     w_in=mat(cfg.n_held, D, 2 * F),
                     w_out=mat(cfg.n_held, F, D),
                     shared_in=mat(D, 2 * Fs), shared_out=mat(Fs, D))
        layers.append(p)
    return {"embed": mat(cfg.vocab, D), "ln_f": ones(D), "layers": layers,
            "lm_head": mat(cfg.vocab, D)}


def _rotate(x, positions, cfg: MLAConfig):
    """The scaled rotary positions on all ``qk_rope_dim`` dims of ``x [b,
    s, h, rope]``, with YaRN's factor on cosine and sine (1 where both
    ``mscale`` are the same)."""
    x = _rope(x, positions, cfg.qk_rope_dim, cfg.rope_theta,
              freqs=jnp.asarray(rotary_frequencies(cfg)))
    m = yarn_mscale(cfg.rope_factor, cfg.rope_mscale) \
        / yarn_mscale(cfg.rope_factor, cfg.rope_mscale_all_dim)
    return x if m == 1.0 else (x.astype(jnp.float32) * m).astype(x.dtype)


def _queries(h, lp, positions, cfg: MLAConfig):
    """``(q_nope [b, s, h, nope], q_rope [b, s, h, rope])`` of ``h [b, s,
    d]``, the rotary part turned to ``positions [b, s]``."""
    q = jnp.einsum("bsd,dhc->bshc", h, lp["wq"].astype(cfg.dtype))
    return q[..., :cfg.qk_nope_dim], \
        _rotate(q[..., cfg.qk_nope_dim:], positions, cfg)


def _latent_rows(h, lp, positions, cfg: MLAConfig):
    """The cache rows ``[c | k_r | 0] [b, s, row_store]`` of ``h [b, s,
    d]``: the latent after its norm, the shared rotary key after the
    rotation, in ``cfg.dtype``, zeros up to the width a row is held at."""
    ckr = jnp.dot(h, lp["wkv_a"].astype(cfg.dtype),
                  preferred_element_type=jnp.float32).astype(cfg.dtype)
    c = _rmsnorm(ckr[..., :cfg.kv_lora_rank], lp["kv_norm"], cfg.rms_eps)
    k_r = _rotate(ckr[..., None, cfg.kv_lora_rank:], positions, cfg)
    pad = jnp.zeros(c.shape[:-1] + (cfg.row_store - cfg.row_width,),
                    c.dtype)
    return jnp.concatenate([c, k_r[..., 0, :], pad], axis=-1)


def _ffn(x, lp, cfg: MLAConfig, live=None):
    """The layer's second half: a dense gated MLP where the layer has one,
    else the expert layer; ``(x, counts or None)``."""
    if "dense_in" not in lp:
        return _expert_layer(x, lp, cfg, live)
    b, s, d = x.shape
    h = _rmsnorm(x, lp["ln2"], cfg.rms_eps).reshape(b * s, d)
    with jax.named_scope("dense_ffn"):
        y = _gated(h, lp["dense_in"], lp["dense_out"], cfg.dtype)
    return x + y.astype(cfg.dtype).reshape(b, s, d), None


def _prompt_layers(params, tokens, lengths, cfg: MLAConfig,
                   attention_fn: Optional[Callable] = None):
    """Every layer over whole right-padded prompts, attention in the
    EXPANDED form: the residual stream ``[b, s, d]`` after the last layer
    and the latent rows ``[layers, 1, b, s, row_store]``. The padding past
    a row's length is left out of the routing: no expert computes it (a
    bucket is up to twice its prompt, and the experts are most of a
    prompt's arithmetic)."""
    from nnstreamer_tpu.ops.flash_attention import attention_reference

    attn = attention_fn or attention_reference
    dtype, scale = cfg.dtype, softmax_scale(cfg)
    x = _embed(params, tokens, cfg)
    positions = jnp.broadcast_to(jnp.arange(tokens.shape[1]), tokens.shape)
    real = positions < lengths[:, None]
    rows = []
    for lp in params["layers"]:
        h = _rmsnorm(x, lp["ln1"], cfg.rms_eps)
        with jax.named_scope("mla_q"):
            q_nope, q_rope = _queries(h, lp, positions, cfg)
            q = jnp.concatenate([q_nope, q_rope], axis=-1)
        with jax.named_scope("mla_kv"):
            row = _latent_rows(h, lp, positions, cfg)
            kv = jnp.einsum("bsr,rhc->bshc", row[..., :cfg.kv_lora_rank],
                            lp["wkv_b"].astype(dtype))
            k_r = jnp.broadcast_to(
                row[..., None, cfg.kv_lora_rank:cfg.row_width],
                kv.shape[:3] + (cfg.qk_rope_dim,))
            k = jnp.concatenate([kv[..., :cfg.qk_nope_dim], k_r], axis=-1)
        with jax.named_scope("attend"):
            a = attn(q, k, kv[..., cfg.qk_nope_dim:], scale=scale)
        with jax.named_scope("mla_out"):
            out = jnp.einsum("bshc,hcd->bsd", a, lp["wo"].astype(dtype))
        rows.append(row[None])
        x, _ = _ffn(x + out, lp, cfg, real)
    return x, jnp.stack(rows)


def build_prefill(cfg: MLAConfig, max_seq: Optional[int] = None,
                  attention_fn: Optional[Callable] = None,
                  kv_codec: Optional[str] = None) -> Callable:
    """``prefill(params, tokens[int32 b, s], lengths[int32 b]) -> (logits[b,
    vocab], rows)`` over right-padded prompts: the logits after each row's
    LAST REAL token, and the latent rows ``[layers, 1, b, s, row_store]`` the
    pool scatters (slots past a row's length hold padding that decode
    overwrites before it reads)."""
    del max_seq
    _no_codec(kv_codec)

    @jax.named_scope("nns.prefill")
    def prefill(params, tokens, lengths=None):
        b, s = tokens.shape
        lengths = jnp.full((b,), s, jnp.int32) if lengths is None \
            else jnp.asarray(lengths, jnp.int32)
        x, rows = _prompt_layers(params, tokens, lengths, cfg, attention_fn)
        with jax.named_scope("logits"):
            last = jnp.take_along_axis(
                x, (lengths - 1)[:, None, None], axis=1)[:, 0]
            logits = _logits(last, params, cfg)
        return logits, rows

    return prefill


def _no_codec(kv_codec) -> None:
    if kv_codec not in (None, "raw"):
        raise ValueError(f"mla: no codec {kv_codec!r} for a latent row")


def build_paged_decode_step(cfg: MLAConfig, block_tokens: int,
                            max_seq: Optional[int] = None,
                            kv_codec: Optional[str] = None,
                            paged_attention_fn: Optional[Callable] = None
                            ) -> Callable:
    """One token for every decode lane against the latent arena, attention
    in the ABSORBED form: ``step(params, token[int32 b], pages, bt[int32 b,
    MB], pos[int32 b]) -> (logits[b, vocab], pages, counts)``.

    ``pages [layers, blocks, 1, T, row_store]`` is addressed as the dense
    block's arena is (scatter at ``(layer, block, slot)``, the sentinel
    rules); a lane whose table is all sentinel is empty: it writes nowhere
    and is left out of the routing. ``paged_attention_fn`` is
    ``ops.paged_attention`` or None (the gather form)."""
    from nnstreamer_tpu.ops.paged_attention import paged_attention_reference

    dtype, scale = cfg.dtype, softmax_scale(cfg)
    rank, nope = cfg.kv_lora_rank, cfg.qk_nope_dim
    s_max = max_seq or cfg.max_seq
    T = int(block_tokens)
    if T <= 0 or s_max % T:
        raise ValueError(
            f"build_paged_decode_step: max_seq ({s_max}) must be a "
            f"positive multiple of block_tokens ({block_tokens})")
    _no_codec(kv_codec)
    attend = paged_attention_fn or paged_attention_reference

    @jax.named_scope("nns.decode")
    def step(params, token, pages, bt, pos):
        pos = jnp.asarray(pos, jnp.int32)
        pos_c = jnp.minimum(pos, s_max - 1)
        live = bt[:, 0] < pages.shape[1]
        blk = jnp.take_along_axis(bt, (pos_c // T)[:, None], axis=1)
        off = (pos_c % T)[:, None]
        x = _embed(params, token, cfg)[:, None]                 # [b,1,d]
        counts = {name: jnp.int32(0) for name in COUNTERS}
        for i, lp in enumerate(params["layers"]):
            h = _rmsnorm(x, lp["ln1"], cfg.rms_eps)
            wkv_b = lp["wkv_b"].astype(dtype)
            with jax.named_scope("mla_q"):
                q_nope, q_rope = _queries(h, lp, pos_c[:, None], cfg)
                q_lat = jnp.einsum("bqhc,rhc->bqhr", q_nope,
                                   wkv_b[..., :nope])
                q = jnp.concatenate([q_lat, q_rope, jnp.zeros(
                    q_lat.shape[:-1] + (cfg.row_store - cfg.row_width,),
                    dtype)], axis=-1)
            with jax.named_scope("mla_kv"):
                row = _latent_rows(h, lp, pos_c[:, None], cfg)
            with jax.named_scope("kv_write"):
                pages = _paged_scatter(pages, i, row[:, :, None], blk, off)
            o_lat = attend(q, pages, i, bt, pos_c, scale=scale,
                           v_width=rank)
            with jax.named_scope("mla_out"):
                o = jnp.einsum("bqhr,rhc->bqhc", o_lat, wkv_b[..., nope:])
                out = jnp.einsum("bqhc,hcd->bqd", o, lp["wo"].astype(dtype))
            x, c = _ffn(x + out, lp, cfg, live)
            if c is not None:
                counts = {name: counts[name] + c[name] for name in COUNTERS}
        with jax.named_scope("logits"):
            logits = _logits(x[:, 0], params, cfg)
        return logits, pages, counts

    return step


def build_forward(cfg: MLAConfig) -> Callable:
    """``forward(params, tokens[int32 b, s]) -> logits[b, s, vocab]``: the
    prefill's layers with every position's logits (tests; the served path
    is prefill + decode)."""

    def forward(params, tokens):
        b, s = tokens.shape
        x, _ = _prompt_layers(params, tokens, jnp.full((b,), s, jnp.int32),
                              cfg)
        return _logits(x.reshape(b * s, -1), params, cfg).reshape(b, s, -1)

    return forward


MLA = ModelFamily(
    name="mla", init_params=init_params, build_prefill=build_prefill,
    build_paged_decode_step=build_paged_decode_step,
    kv_entry=lambda cfg: (cfg.n_layers, 1, (cfg.row_store,)),
    latent_value_width=lambda cfg: cfg.kv_lora_rank,
    counters=COUNTERS, expert_matmul=hybrid.expert_matmul,
    token_share=hybrid.routed_share,
    refusal="keeps one latent row a token that every head shares: no paged "
            "chunk of several query positions a lane reads such rows and "
            "no codec narrows one (ROADMAP.md R5; mesh: R2, R11)",
    read_in_dtype=("wq", "wkv_a", "wkv_b", "wo", "dense_in", "dense_out",
                   "router", "w_in", "w_out", "shared_in", "shared_out",
                   "lm_head"))
