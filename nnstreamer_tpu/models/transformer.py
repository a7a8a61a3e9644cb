"""Decoder-only transformer LM — the long-context / multi-chip flagship.

New capability beyond the reference (which has no attention/sequence models
in-framework, SURVEY §5): a GPT-style LM whose parameters are laid out for
SPMD sharding (see ``parallel.sharded`` for the axis rules) and whose
attention can run as **ring attention** over a sequence-parallel mesh axis
(``parallel.ring``). TPU-first choices:

- layers are **stacked** (one leading L axis per param) and applied with
  ``lax.scan`` — one compiled layer body instead of L inlined copies;
- bfloat16 activations, fp32 layernorm/softmax accumulations;
- rotary position embeddings (no learned positional table to shard);
- optional top-1 MoE FFN whose expert dim maps to the ``ep`` mesh axis.

Params are a plain pytree dict, so sharding rules are transparent
name-based PartitionSpecs rather than framework metadata.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from nnstreamer_tpu.tensors.types import TensorsInfo


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab: int = 32000
    d_model: int = 512
    n_heads: int = 8
    n_layers: int = 4
    d_ff: int = 2048
    max_seq: int = 2048
    dtype: Any = jnp.bfloat16
    num_experts: int = 0  # 0 → dense FFN; >0 → top-1 MoE

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    @property
    def family(self):
        """What the serving engine takes from this model
        (``models/family.py``)."""
        return DENSE


def init_params(cfg: TransformerConfig, seed: int = 0) -> Dict[str, Any]:
    rng = np.random.default_rng(seed)

    def norm(*shape):
        return jnp.asarray(
            rng.standard_normal(shape).astype(np.float32) * 0.02
        )

    L, D, H, Dh, F = (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.head_dim,
                      cfg.d_ff)
    p = {
        "embed": norm(cfg.vocab, D),
        "ln1": jnp.ones((L, D), jnp.float32),
        "qkv": norm(L, D, 3, H, Dh),
        "proj": norm(L, H, Dh, D),
        "ln2": jnp.ones((L, D), jnp.float32),
        "ln_f": jnp.ones((D,), jnp.float32),
    }
    if cfg.num_experts:
        p["router"] = norm(L, D, cfg.num_experts)
        p["w_in"] = norm(L, cfg.num_experts, D, F)
        p["w_out"] = norm(L, cfg.num_experts, F, D)
    else:
        p["w_in"] = norm(L, D, F)
        p["w_out"] = norm(L, F, D)
    return p


def _rmsnorm(x, scale):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + 1e-6) * scale).astype(x.dtype)


def _rope(x, positions):
    """Rotary embeddings; x [b, s, h, d], positions [b, s]."""
    d = x.shape[-1]
    half = d // 2
    freqs = jnp.exp(-np.log(10000.0) * jnp.arange(half) / half)
    angles = positions[..., None].astype(jnp.float32) * freqs  # [b,s,half]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1
    ).astype(x.dtype)


def _dense_ffn(x, w_in, w_out, dtype):
    h = jnp.einsum("bsd,df->bsf", x, w_in.astype(dtype))
    h = jax.nn.gelu(h)
    return jnp.einsum("bsf,fd->bsd", h, w_out.astype(dtype))


def _block_qkv(x, lp, positions, dtype):
    """Pre-norm + qkv projection + rope — shared by the full forward's
    layer body and the KV-cached decode body."""
    h = _rmsnorm(x, lp["ln1"])
    qkv = jnp.einsum("bsd,dthc->btshc", h, lp["qkv"].astype(dtype))
    q, k, v = qkv[:, 0], qkv[:, 1], qkv[:, 2]            # [b,s,h,dh]
    return _rope(q, positions), _rope(k, positions), v


def _block_tail(x, a, lp, cfg):
    """Attention-output projection + residual + FFN block — shared by the
    full forward's layer body and the KV-cached decode body."""
    dtype = cfg.dtype
    x = x + jnp.einsum("bshc,hcd->bsd", a, lp["proj"].astype(dtype))
    h2 = _rmsnorm(x, lp["ln2"])
    if cfg.num_experts:
        return x + _moe_ffn(h2, lp["router"], lp["w_in"], lp["w_out"],
                            dtype)
    return x + _dense_ffn(h2, lp["w_in"], lp["w_out"], dtype)


def _attend_cache(q, ck, cv, mask, head_dim, dtype, scale=None):
    """The ONE cached-attention numeric core shared by single-token decode
    and chunk decode: fp32 scores (same scale FORM as attention_reference,
    flash_attention.py:45), fp32 softmax AND fp32 probs×values, rounding
    only the final output — bit-matches the full forward so greedy
    decode/forward parity holds in bfloat16 configs too.

    ``scale`` defaults to ``head_dim ** -0.5``. A cache with fewer heads
    than ``q`` is grouped-query attention: query head ``i`` reads
    key-value head ``i // (query heads / key-value heads)``; values may
    be narrower or wider than keys."""
    scale = head_dim ** -0.5 if scale is None else scale
    if q.shape[2] != ck.shape[2]:
        b, nq, hq, c = q.shape
        hk = ck.shape[2]
        scores = jnp.einsum(
            "bqkgc,bskc->bkgqs",
            q.astype(jnp.float32).reshape(b, nq, hk, hq // hk, c),
            ck.astype(jnp.float32)) * scale
        probs = jax.nn.softmax(
            jnp.where(mask[:, :, None], scores, -1e30), axis=-1)
        return jnp.einsum("bkgqs,bskc->bqkgc", probs,
                          cv.astype(jnp.float32)).reshape(
                              b, nq, hq, cv.shape[-1]).astype(dtype)
    scores = jnp.einsum("bqhc,bshc->bhqs", q.astype(jnp.float32),
                        ck.astype(jnp.float32))
    scores = scores * scale
    scores = jnp.where(mask, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqs,bshc->bqhc", probs,
                      cv.astype(jnp.float32)).astype(dtype)


def _final_logits(x, params):
    """Final rmsnorm + tied-embedding projection, shared by every forward
    variant so logit math can never diverge between them."""
    x = _rmsnorm(x, params["ln_f"])
    return jnp.einsum("bsd,vd->bsv", x.astype(jnp.float32),
                      params["embed"])


def _moe_ffn(x, router, w_in, w_out, dtype):
    """Top-1 routed MoE: expert axis shards over mesh axis ``ep`` (the
    one-hot dispatch einsum lets GSPMD all-to-all tokens to experts)."""
    logits = jnp.einsum("bsd,de->bse", x.astype(jnp.float32),
                        router.astype(jnp.float32))
    gate = jax.nn.softmax(logits, axis=-1)
    top = jnp.argmax(gate, axis=-1)                      # [b,s]
    onehot = jax.nn.one_hot(top, router.shape[-1], dtype=dtype)  # [b,s,e]
    weight = jnp.take_along_axis(gate, top[..., None], -1)[..., 0].astype(
        dtype)                                                   # [b,s]
    h = jnp.einsum("bsd,bse,edf->bsef", x, onehot, w_in.astype(dtype))
    h = jax.nn.gelu(h)
    out = jnp.einsum("bsef,efd->bsed", h, w_out.astype(dtype))
    return jnp.sum(out * onehot[..., None], axis=2) * weight[..., None]


def make_layer_body(cfg: TransformerConfig,
                    attention_fn: Optional[Callable] = None,
                    capture_kv: bool = False) -> Callable:
    """One transformer block as a ``lax.scan`` body over stacked layer
    params: ``layer_body((x, positions), layer_params) -> ((x, positions),
    ys)``. Shared by the plain forward (scan over all L layers), the
    pipeline-parallel forward (each stage scans its local L/pp layers),
    and prefill (``capture_kv=True`` → ys is the layer's rope'd
    ``stack([k, v])`` for decode-cache seeding)."""
    from nnstreamer_tpu.parallel.ring import attention_reference

    attn = attention_fn or attention_reference
    dtype = cfg.dtype

    def layer_body(x_and_pos, lp):
        x, positions = x_and_pos
        with jax.named_scope("qkv"):
            q, k, v = _block_qkv(x, lp, positions, dtype)
        with jax.named_scope("attend"):
            a = attn(q, k, v)                            # [b,s,h,dh]
        with jax.named_scope("ffn"):
            x = _block_tail(x, a, lp, cfg)
        return (x, positions), (jnp.stack([k, v]) if capture_kv else None)

    return layer_body


def build_forward(cfg: TransformerConfig,
                  attention_fn: Optional[Callable] = None) -> Callable:
    """Returns apply_fn(params, tokens[int32 b,s]) -> logits[b,s,vocab].

    ``attention_fn(q, k, v)`` defaults to single-device causal attention;
    pass a ring-attention closure (inside shard_map) for sequence
    parallelism. ``positions`` are offset by the sp shard index when the
    attention_fn provides ``.position_offset`` (set by the sharded step
    builder) so rotary phases stay globally correct.
    """
    dtype = cfg.dtype
    layer_body = make_layer_body(cfg, attention_fn)

    def apply_fn(params, tokens, position_offset=0):
        b, s = tokens.shape
        positions = position_offset + jnp.arange(s)[None, :].astype(
            jnp.int32
        ) * jnp.ones((b, 1), jnp.int32)
        x = params["embed"][tokens].astype(dtype)
        layer_params = {k: v for k, v in params.items()
                        if k not in ("embed", "ln_f")}
        (x, _), _ = lax.scan(layer_body, (x, positions), layer_params)
        return _final_logits(x, params)

    return apply_fn


def _slot_write(layer_cache, upd, pos, per_stream):
    """Write ``upd`` into a layer cache leaf at sequence slot(s) ``pos``.

    Leaf layout is ``[2, b, S, ...]`` (slot axis 2, any trailing rank —
    values have dh, scales don't). ``per_stream`` scatters per batch row
    with that row's own pos."""
    if per_stream:
        return jax.vmap(
            lambda cch, u, p: jax.lax.dynamic_update_slice(
                cch, u, (0, p) + (0,) * (cch.ndim - 2)),
            in_axes=(1, 1, 0), out_axes=1)(layer_cache, upd, pos)
    return jax.lax.dynamic_update_slice(
        layer_cache, upd, (0, 0, pos) + (0,) * (layer_cache.ndim - 3))


#: rows of one ``(8, 128)`` tile of the chip's memory
_TILE_ROWS = 8


def kv_heads_major(entry) -> bool:
    """THE rule for the order of the rows inside a block, from the shape of
    a token's entry (``ModelFamily.kv_entry``) and nothing else: an entry
    ``(heads, head_dim)`` whose heads are NOT whole tiles of 8 rows (fewer
    than 8: 2; or 10, between two tiles) is stored HEADS-MAJOR, ``[..,
    heads, T, head_dim]``, so that a head's ``T`` tokens are whole tiles; 8
    heads, 16, and a latent row ``(width,)`` stay token-major, ``[.., T,
    *entry]``, where a token's entry is whole tiles already and the decode
    write is one contiguous entry. With 2 heads token-major the chip tiles
    the arena two rows to a tile and every flat view or scatter of it is a
    copy of the WHOLE arena (PERF.md, PR 31 and PR 34); with 10 it pads
    each token's ten rows to sixteen and copies both arenas into ``{5,2,4,3,
    1,0:T(2,128)}`` and back every step (3.3 GB of temporaries: compiled
    for a described v5e, PERF.md PR 39). The order cannot be read back off a
    shape (``[2, 16, dh]`` is 2 heads of 16 tokens and 2 tokens of 16
    heads): the codec that made the arena states it (``heads_major``) and
    hands it on."""
    return len(entry) == 2 and entry[0] % _TILE_ROWS != 0


def _paged_gather(pages, layer, bt, heads_major: bool = False):
    """Block gather out of the whole arena leaf: ``pages [L, NTOT, 2, T,
    ...]`` (``heads_major``: ``[L, NTOT, 2, h, T, ...]``) + layer number +
    block table ``bt [b, MB]`` → that layer's contiguous ``[b, 2, MB*T,
    ...]`` k/v in global-slot order, heads inside tokens in either order
    of the arena. Table
    entries ≥ NTOT-1 (the pool's unallocated sentinel) clamp onto the
    pool's permanent ZERO block at index NTOT-1, so unallocated slots read
    exact zeros — finite, and masked out anyway."""
    ntot = pages.shape[1]
    g = pages[layer, jnp.minimum(bt, ntot - 1)]          # [b,MB,2,T,...]
    if heads_major:                             # from [b,MB,2,h,T,...]
        g = jnp.transpose(g, (0, 2, 1, 4, 3) + tuple(range(5, g.ndim)))
    else:
        g = jnp.moveaxis(g, 2, 1)                        # [b,2,MB,T,...]
    b, two, mb, t = g.shape[:4]
    return g.reshape((b, two, mb * t) + g.shape[4:])


def _paged_scatter(pages, layer, upd, blk, off, heads_major: bool = False):
    """Block scatter into the whole arena leaf, in place when the leaf is
    a donated loop carry: ``upd [b, c, 2, ...]`` into ``pages[layer, blk,
    :, off]`` (``blk``/``off`` are ``[b, c]``; ``heads_major``: into
    ``pages[layer, blk, :, :, off]``, the same ``upd``). Out-of-range
    block ids (the sentinel) DROP — a masked write, not a clamped one, so
    the zero block is never corrupted.

    Heads-major the part and the head are INDICES too, so that an update's
    window is one row ``[dh]`` (one number of a scale leaf): a window ``[2,
    h, 1, dh]`` astride the token axis makes XLA:TPU relay the whole arena
    out token-major for the scatter and back, every step (compiled for a
    described v5e, PR 34); rows it scatters in place."""
    if heads_major:
        part = jnp.arange(pages.shape[2])[:, None]
        head = jnp.arange(pages.shape[3])[None, :]
        return pages.at[layer, blk[..., None, None], part, head,
                        off[..., None, None]].set(upd, mode="drop")
    return pages.at[layer, blk, :, off].set(upd, mode="drop")


def _paged_shape(L, ntot, T, entry, parts, heads_major):
    if heads_major:
        return (L, ntot, parts, entry[0], T) + tuple(entry[1:])
    return (L, ntot, parts, T) + tuple(entry)


class _RawKVCodec:
    """Cache = one array [L, 2, b, S, h, dh] in the model dtype.

    ``heads_major`` is the order of the rows inside a block of the PAGED
    arena this codec makes (:func:`kv_heads_major`; the contiguous cache
    is token-major always): ``paged_init`` shapes the arena by it,
    ``paged_write`` and ``paged_read`` address it by it, and whoever reads
    the arena otherwise (``serving/kvpool.py``, ``ops/paged_attention.py``)
    is told it, never infers it."""

    def __init__(self, dtype, heads_major: bool = False):
        self.dtype = dtype
        self.heads_major = bool(heads_major)

    def init(self, L, b, S, h, dh):
        return jnp.zeros((L, 2, b, S, h, dh), self.dtype)

    def write(self, layer_cache, kv, pos, per_stream=False):
        """kv [2, b, c, h, dh] → slots [pos, pos+c) (per-row pos when
        ``per_stream``)."""
        return _slot_write(layer_cache, kv.astype(self.dtype), pos,
                           per_stream)

    def read(self, layer_cache):
        return layer_cache[0], layer_cache[1]

    def place_prefix(self, cache, kv):
        """kv [L, 2, b, s, h, dh] → cache slots [0, s)."""
        return jax.lax.dynamic_update_slice(
            cache, kv.astype(self.dtype), (0, 0, 0, 0, 0, 0))

    def paged_init(self, L, ntot, T, *entry, parts=2):
        """Paged arena [L, NTOT, parts, T, *entry] (keys and values per
        head: parts 2, entry ``h, dh``), or ``heads_major`` [L, NTOT,
        parts, h, T, dh]: ONE buffer that the paged
        builders address whole, the layer one more index beside the block
        (serving/kvpool.py owns allocation; index NTOT-1 of every layer
        is the permanent zero block)."""
        return jnp.zeros(_paged_shape(L, ntot, T, entry, parts,
                                      self.heads_major), self.dtype)

    def paged_write(self, pages, layer, kv, blk, off):
        """kv [2, b, c, h, dh] → pages[layer, blk[b,c], :, off[b,c]]
        (``heads_major``: pages[layer, blk[b,c], :, :, off[b,c]], row by
        row: ``_paged_scatter``)."""
        upd = jnp.transpose(kv.astype(self.dtype), (1, 2, 0, 3, 4))
        return _paged_scatter(pages, layer, upd, blk, off, self.heads_major)

    def paged_read(self, pages, layer, bt):
        g = _paged_gather(pages, layer, bt, self.heads_major)
        return g[:, 0], g[:, 1]


class _Int8KVCodec:
    """int8 KV cache: values [L, 2, b, S, h, dh] int8 + per-vector absmax
    scales [L, 2, b, S, h] fp32 — ~2× context (or batch slots) per HBM
    byte vs bf16, and the attend path reads half the bytes. Dequantize
    happens in fp32 right before the score/pv einsums, so the attention
    numeric core (_attend_cache) is unchanged. ``heads_major`` as
    ``_RawKVCodec``'s: both leaves of the paged arena in the same order
    (the scale leaf ``[L, NTOT, 2, h, T]``)."""

    def __init__(self, heads_major: bool = False):
        self.heads_major = bool(heads_major)

    def _q(self, kv):
        kf = kv.astype(jnp.float32)
        amax = jnp.max(jnp.abs(kf), axis=-1, keepdims=True)
        scale = jnp.maximum(amax / 127.0, 1e-30)
        q = jnp.clip(jnp.round(kf / scale), -127, 127).astype(jnp.int8)
        return q, scale[..., 0]

    def init(self, L, b, S, h, dh):
        return {"q": jnp.zeros((L, 2, b, S, h, dh), jnp.int8),
                "scale": jnp.zeros((L, 2, b, S, h), jnp.float32)}

    def write(self, layer_cache, kv, pos, per_stream=False):
        q, s = self._q(kv)                 # [2,b,c,h,dh], [2,b,c,h]
        return {"q": _slot_write(layer_cache["q"], q, pos, per_stream),
                "scale": _slot_write(layer_cache["scale"], s, pos,
                                     per_stream)}

    def read(self, layer_cache):
        deq = (layer_cache["q"].astype(jnp.float32)
               * layer_cache["scale"][..., None])
        return deq[0], deq[1]

    def place_prefix(self, cache, kv):
        q, s = self._q(kv)                 # [L,2,b,s,h,dh], [L,2,b,s,h]
        return {
            "q": jax.lax.dynamic_update_slice(
                cache["q"], q, (0, 0, 0, 0, 0, 0)),
            "scale": jax.lax.dynamic_update_slice(
                cache["scale"], s, (0, 0, 0, 0, 0)),
        }

    def paged_init(self, L, ntot, T, *entry, parts=2):
        hm = self.heads_major
        return {"q": jnp.zeros(_paged_shape(L, ntot, T, entry, parts, hm),
                               jnp.int8),
                "scale": jnp.zeros(_paged_shape(L, ntot, T, entry[:-1],
                                                parts, hm), jnp.float32)}

    def paged_write(self, pages, layer, kv, blk, off):
        """Codec applied per block: each written vector quantizes with the
        same per-vector absmax math as the monolithic write, so paged int8
        caches are bit-identical to monolithic int8 ones. Both leaves
        take the same ``[layer, blk, :, off]`` index (``heads_major``:
        ``[layer, blk, :, :, off]``)."""
        q, s = self._q(kv)                 # [2,b,c,h,dh], [2,b,c,h]
        return {
            "q": _paged_scatter(pages["q"], layer,
                                jnp.transpose(q, (1, 2, 0, 3, 4)),
                                blk, off, self.heads_major),
            "scale": _paged_scatter(pages["scale"], layer,
                                    jnp.transpose(s, (1, 2, 0, 3)),
                                    blk, off, self.heads_major),
        }

    def paged_read(self, pages, layer, bt):
        gq = _paged_gather(pages["q"], layer, bt, self.heads_major)
        gs = _paged_gather(pages["scale"], layer, bt, self.heads_major)
        deq = gq.astype(jnp.float32) * gs[..., None]
        return deq[:, 0], deq[:, 1]


def _kv_codec(cfg: TransformerConfig, kv_codec: Optional[str]):
    """The codec of ``cfg``'s caches, the order of its paged arena's rows
    decided here, once, by :func:`kv_heads_major` from the family's
    ``kv_entry``: the pool and every paged builder make their codec
    through this function, so they agree."""
    heads_major = kv_heads_major(cfg.family.kv_entry(cfg)[2])
    if kv_codec in (None, "raw"):
        return _RawKVCodec(cfg.dtype, heads_major)
    if kv_codec == "int8":
        return _Int8KVCodec(heads_major)
    raise ValueError(
        f"kv_codec must be None/'raw'/'int8', got {kv_codec!r}")


def init_cache(cfg: TransformerConfig, batch: int,
               max_seq: Optional[int] = None,
               kv_codec: Optional[str] = None):
    """Device-resident KV cache [L, 2, b, S, h, dh] (k=0, v=1 slots).
    ``kv_codec="int8"`` returns the quantized layout (values + per-vector
    scales) accepted by the matching ``build_*`` functions."""
    s = max_seq or cfg.max_seq
    return _kv_codec(cfg, kv_codec).init(
        cfg.n_layers, batch, s, cfg.n_heads, cfg.head_dim)


def build_decode_step(cfg: TransformerConfig,
                      max_seq: Optional[int] = None,
                      kv_codec: Optional[str] = None) -> Callable:
    """Incremental (KV-cached) single-token decode.

    ``step(params, token[int32 b], cache, pos[int32 scalar]) ->
    (logits[b, vocab], new_cache)`` — one position's q/k/v are computed,
    k/v written into the cache at ``pos`` (``dynamic_update_slice``), and
    attention runs against the cached prefix under a ``<= pos`` mask. The
    cache is a jittable carry: it stays in HBM across steps, the streaming
    pipeline's tensor_repo loop circulating only array handles (the
    reference's LSTM repo pattern, tests/nnstreamer_repo_lstm, scaled to
    autoregressive LM decode). Jit with ``donate_argnums`` on the cache to
    update it in place.

    Cache-length contract: ``pos`` is clamped to the last cache slot — a
    step past ``max_seq`` overwrites slot S-1 and attends over the stored
    prefix (bounded degradation, never an unmasked-garbage read). Callers
    streaming longer sequences should size the cache accordingly or reset
    it.

    ``pos`` may be a scalar (all streams in lock-step) or a ``[b]``
    vector — one position per batch row, the continuous-batching shape:
    sequences at different depths decode together in one dispatch, each
    writing its own cache slot and masking its own prefix.

    ``kv_codec="int8"`` stores the cache quantized (see _Int8KVCodec);
    pass the matching ``init_cache(..., kv_codec="int8")`` cache.
    """
    dtype = cfg.dtype
    s_max = max_seq or cfg.max_seq
    codec = _kv_codec(cfg, kv_codec)

    @jax.named_scope("nns.decode")
    def step(params, token, cache, pos):
        b = token.shape[0]
        pos = jnp.asarray(pos, jnp.int32)
        per_stream = pos.ndim == 1
        pos_c = jnp.minimum(pos, s_max - 1)  # see cache-length contract
        x = params["embed"][token].astype(dtype)[:, None]       # [b,1,d]
        positions = pos[:, None] if per_stream \
            else jnp.full((b, 1), pos, jnp.int32)
        layer_params = {k: v for k, v in params.items()
                        if k not in ("embed", "ln_f")}

        def layer(carry, lp_and_cache):
            x, = carry
            lp, layer_cache = lp_and_cache                # [2,b,S,h,dh]
            with jax.named_scope("qkv"):
                q, k, v = _block_qkv(x, lp, positions, dtype)  # [b,1,h,dh]
            with jax.named_scope("kv_write"):
                new_cache = codec.write(layer_cache, jnp.stack([k, v]),
                                        pos_c, per_stream)
            with jax.named_scope("kv_gather"):
                slots = jnp.arange(s_max)
                mask = slots[None, None, None, :] <= (
                    pos_c[:, None, None, None] if per_stream else pos_c)
                ck, cv = codec.read(new_cache)
            with jax.named_scope("attend"):
                a = _attend_cache(q, ck, cv, mask, cfg.head_dim, dtype)
            with jax.named_scope("ffn"):
                x = _block_tail(x, a, lp, cfg)
            return (x,), new_cache

        (x,), new_cache = lax.scan(layer, (x,), (layer_params, cache))
        with jax.named_scope("logits"):
            return _final_logits(x, params)[:, 0], new_cache

    return step


def build_chunk_decode(cfg: TransformerConfig,
                       max_seq: Optional[int] = None,
                       kv_codec: Optional[str] = None) -> Callable:
    """KV-cached decode of a WHOLE chunk of c tokens in one pass:
    ``chunk(params, tokens[int32 b,c], cache, pos0[int32 scalar]) ->
    (logits[b,c,vocab], new_cache)``.

    Generalizes :func:`build_decode_step` (c=1) to the shape speculative
    verification needs (models/speculative.py): the target model scores c
    candidate positions in ONE program — a [c, d_model] matmul per layer
    instead of c sequential single-row dispatches, which is exactly what
    the MXU wants. Position ``pos0+i`` writes cache slot ``pos0+i`` and
    attends under a ``slot <= pos0+i`` mask (write-before-attend, so
    stale kv beyond an accepted prefix is unreachable — the rewind-free
    speculative cache contract; see speculative.py docstring).

    ``pos0`` is clamped so the chunk's writes stay inside the cache
    (same bounded-degradation contract as build_decode_step).

    Like build_decode_step, ``pos0`` may also be a ``[b]`` vector — one
    chunk origin per batch row (the batched speculative-verify shape:
    every stream scores its own γ+1 candidates at its own depth in ONE
    program). The scalar path traces exactly as before.
    """
    dtype = cfg.dtype
    s_max = max_seq or cfg.max_seq
    codec = _kv_codec(cfg, kv_codec)

    def chunk(params, tokens, cache, pos0):
        b, c = tokens.shape
        pos0 = jnp.asarray(pos0, jnp.int32)
        per_stream = pos0.ndim == 1
        pos0 = jnp.minimum(pos0, s_max - c)
        if per_stream:
            positions = pos0[:, None] + jnp.arange(c)[None, :]   # [b,c]
            # query i of row r (global position pos0[r]+i) sees
            # slots <= pos0[r]+i
            qpos = positions[:, None, :, None]                # [b,1,c,1]
        else:
            positions = pos0 + jnp.arange(c)[None, :] * jnp.ones(
                (b, 1), jnp.int32)                               # [b,c]
            # query i (global position pos0+i) sees slots <= pos0+i
            qpos = (pos0 + jnp.arange(c))[None, None, :, None]
        x = params["embed"][tokens].astype(dtype)
        layer_params = {k: v for k, v in params.items()
                        if k not in ("embed", "ln_f")}

        def layer(carry, lp_and_cache):
            x, = carry
            lp, layer_cache = lp_and_cache
            q, k, v = _block_qkv(x, lp, positions, dtype)  # [b,c,h,dh]
            new_cache = codec.write(layer_cache, jnp.stack([k, v]), pos0,
                                    per_stream)
            slots = jnp.arange(s_max)
            mask = slots[None, None, None, :] <= qpos
            ck, cv = codec.read(new_cache)
            a = _attend_cache(q, ck, cv, mask, cfg.head_dim, dtype)
            x = _block_tail(x, a, lp, cfg)
            return (x,), new_cache

        (x,), new_cache = lax.scan(layer, (x,), (layer_params, cache))
        return _final_logits(x, params), new_cache

    return chunk


def build_paged_decode_step(cfg: TransformerConfig,
                            block_tokens: int,
                            max_seq: Optional[int] = None,
                            kv_codec: Optional[str] = None,
                            paged_attention_fn: Optional[Callable] = None
                            ) -> Callable:
    """Single-token decode against a PAGED KV cache (serving/kvpool.py):
    ``step(params, token[int32 b], arena, bt[int32 b,MB], pos[int32 b]) ->
    (logits[b, vocab], new_arena)``.

    The arena is the pool's ``[L, NTOT, 2, T, h, dh]`` pytree (heads-major
    ``[L, NTOT, 2, h, T, dh]`` where :func:`kv_heads_major` says so: the
    codec addresses either); ``bt`` maps
    each row's logical blocks ``0..MB-1`` (MB = S/T) to physical pool
    blocks, with unallocated entries holding the pool sentinel (≥ NTOT).
    Each layer scatters k/v into physical slot ``(layer, bt[pos//T],
    pos%T)`` and gathers the row's table back into the contiguous ``[b,
    S, ...]`` layout the shared attention core expects — same slot
    ordering, same write-before-attend discipline, and masked slots
    contribute EXACT zeros (−1e30 scores underflow softmax to 0.0), so
    greedy outputs are bit-identical to the monolithic cache. Rows whose
    table is all sentinel (empty batch lanes) drop their writes and read
    the zero block — inert by construction.

    The arena is ONE buffer that no scan slices: it rides the layer scan
    as a carry beside ``x`` and the layer number, and the codec addresses
    it whole with the layer as one more index. A carry of a donated
    argument is scattered into in place; as the scan's ``xs``/``ys`` each
    layer's 1/L of the pool is sliced out and written back every step
    and the compiler plans the pool twice (PERF.md §6, PR 26).

    ``paged_attention_fn(q, pages, layer, bt, pos_c, heads_major=)``
    (``ops/paged_attention.py``; for a raw arena, which is one leaf; the
    order is the codec's) takes the
    place of gather + mask + ``_attend_cache``: it reads each lane's
    live blocks where they lie, or builds the same gather form itself
    where its kernel does not run. None keeps the gather form.
    """
    dtype = cfg.dtype
    s_max = max_seq or cfg.max_seq
    T = int(block_tokens)
    if T <= 0 or s_max % T:
        raise ValueError(
            f"build_paged_decode_step: max_seq ({s_max}) must be a "
            f"positive multiple of block_tokens ({block_tokens})")
    codec = _kv_codec(cfg, kv_codec)

    @jax.named_scope("nns.decode")
    def step(params, token, arena, bt, pos):
        pos = jnp.asarray(pos, jnp.int32)
        pos_c = jnp.minimum(pos, s_max - 1)  # cache-length contract
        x = params["embed"][token].astype(dtype)[:, None]       # [b,1,d]
        positions = pos[:, None]
        blk = jnp.take_along_axis(bt, (pos_c // T)[:, None], axis=1)
        off = (pos_c % T)[:, None]                               # [b,1]
        layer_params = {k: v for k, v in params.items()
                        if k not in ("embed", "ln_f")}

        def layer(carry, lp):
            x, li, pages = carry                  # the whole arena
            with jax.named_scope("qkv"):
                q, k, v = _block_qkv(x, lp, positions, dtype)  # [b,1,h,dh]
            with jax.named_scope("kv_write"):
                pages = codec.paged_write(pages, li, jnp.stack([k, v]),
                                          blk, off)
            if paged_attention_fn is not None:
                a = paged_attention_fn(q, pages, li, bt, pos_c,
                                       heads_major=codec.heads_major)
            else:
                with jax.named_scope("kv_gather"):
                    slots = jnp.arange(s_max)
                    mask = slots[None, None, None, :] <= pos_c[
                        :, None, None, None]
                    ck, cv = codec.paged_read(pages, li, bt)
                with jax.named_scope("attend"):
                    a = _attend_cache(q, ck, cv, mask, cfg.head_dim, dtype)
            with jax.named_scope("ffn"):
                x = _block_tail(x, a, lp, cfg)
            return (x, li + 1, pages), None

        (x, _, new_arena), _ = lax.scan(
            layer, (x, jnp.int32(0), arena), layer_params)
        with jax.named_scope("logits"):
            return _final_logits(x, params)[:, 0], new_arena

    return step


def build_paged_chunk(cfg: TransformerConfig,
                      block_tokens: int,
                      max_seq: Optional[int] = None,
                      kv_codec: Optional[str] = None) -> Callable:
    """Chunk decode against a paged KV cache — build_chunk_decode's paged
    twin: ``chunk(params, tokens[int32 b,c], arena, bt[int32 b,MB],
    pos0[int32 b], limit[int32 b]) -> (logits[b,c,vocab], new_arena)``.

    Row r's token i sits at global position ``pos0[r]+i``, writes physical
    slot ``(bt[r, p//T], p%T)`` and attends under a ``slot <= p`` mask.
    ``limit[r]`` is the row's REAL chunk length: positions ≥ limit (bucket
    padding) redirect their writes to the sentinel and drop, so a padded
    warm prefix extension never smears pad k/v into pool blocks another
    stream could inherit. Used for prefix-cache extension and speculative
    verification on the paged path. The arena is addressed as in
    :func:`build_paged_decode_step`: whole, a carry of the layer scan, the
    layer an index.
    """
    dtype = cfg.dtype
    s_max = max_seq or cfg.max_seq
    T = int(block_tokens)
    if T <= 0 or s_max % T:
        raise ValueError(
            f"build_paged_chunk: max_seq ({s_max}) must be a positive "
            f"multiple of block_tokens ({block_tokens})")
    codec = _kv_codec(cfg, kv_codec)

    def chunk(params, tokens, arena, bt, pos0, limit):
        b, c = tokens.shape
        pos0 = jnp.minimum(jnp.asarray(pos0, jnp.int32), s_max - c)
        positions = pos0[:, None] + jnp.arange(c)[None, :]       # [b,c]
        valid = jnp.arange(c)[None, :] < jnp.asarray(
            limit, jnp.int32)[:, None]
        ntot = jax.tree_util.tree_leaves(arena)[0].shape[1]
        blk = jnp.take_along_axis(bt, positions // T, axis=1)    # [b,c]
        blk = jnp.where(valid, blk, jnp.int32(ntot))   # pad writes drop
        off = positions % T
        x = params["embed"][tokens].astype(dtype)
        layer_params = {k: v for k, v in params.items()
                        if k not in ("embed", "ln_f")}

        def layer(carry, lp):
            x, li, pages = carry                  # the whole arena
            q, k, v = _block_qkv(x, lp, positions, dtype)  # [b,c,h,dh]
            pages = codec.paged_write(pages, li, jnp.stack([k, v]), blk,
                                      off)
            slots = jnp.arange(s_max)
            mask = slots[None, None, None, :] <= positions[:, None, :,
                                                           None]
            ck, cv = codec.paged_read(pages, li, bt)
            a = _attend_cache(q, ck, cv, mask, cfg.head_dim, dtype)
            x = _block_tail(x, a, lp, cfg)
            return (x, li + 1, pages), None

        (x, _, new_arena), _ = lax.scan(
            layer, (x, jnp.int32(0), arena), layer_params)
        return _final_logits(x, params), new_arena

    return chunk


def build_prefill(cfg: TransformerConfig,
                  max_seq: Optional[int] = None,
                  attention_fn: Optional[Callable] = None,
                  kv_codec: Optional[str] = None) -> Callable:
    """Prompt ingestion for streaming decode: ``prefill(params,
    tokens[int32 b,s]) -> (logits[b, vocab], cache)`` — one full-sequence
    forward (the SAME shared layer body as :func:`build_forward`, with
    k/v captured) that seeds a fresh decode cache, so generation continues
    from ``pos = s`` with :func:`build_decode_step`. The last position's
    logits seed the first sampled token. ``attention_fn`` plugs in a flash
    kernel for the O(s²) prompt pass exactly as in build_forward.

    ``prefill(params, tokens, lengths)`` with ``lengths[int32 b]`` supports
    RIGHT-PADDED prompts (bucketed compile shapes, serving.engine): logits
    are taken at each row's true last position ``lengths-1``. Trailing-pad
    kv entries land in cache slots ≥ length; they are garbage but
    unreachable — decode's ``slots <= pos`` mask only admits slot i once
    pos reaches i, and the decode step WRITES slot i (overwriting the pad
    kv) before attending on that same step, so a padded prefill is
    bit-identical to an exact-length one for all future tokens."""
    dtype = cfg.dtype
    s_max = max_seq or cfg.max_seq
    codec = _kv_codec(cfg, kv_codec)
    layer_body = make_layer_body(cfg, attention_fn, capture_kv=True)

    @jax.named_scope("nns.prefill")
    def prefill(params, tokens, lengths=None):
        b, s = tokens.shape
        positions = jnp.arange(s)[None, :].astype(jnp.int32) * jnp.ones(
            (b, 1), jnp.int32)
        x = params["embed"][tokens].astype(dtype)
        layer_params = {k: v for k, v in params.items()
                        if k not in ("embed", "ln_f")}
        (x, _), kv = lax.scan(layer_body, (x, positions), layer_params)
        # park each layer's k/v ([L,2,b,s,h,dh]) in the first s cache slots
        cache = codec.place_prefix(
            codec.init(cfg.n_layers, b, s_max, cfg.n_heads, cfg.head_dim),
            kv)
        with jax.named_scope("logits"):
            x = _rmsnorm(x, params["ln_f"])
            if lengths is None:
                last = x[:, -1]
            else:
                idx = (jnp.asarray(lengths, jnp.int32) - 1)[:, None, None]
                last = jnp.take_along_axis(
                    x, jnp.broadcast_to(idx, (b, 1, x.shape[-1])), axis=1
                )[:, 0]
            logits = jnp.einsum("bd,vd->bv", last.astype(jnp.float32),
                                params["embed"])
        return logits, cache

    return prefill


def build_greedy_stream_step(cfg: TransformerConfig,
                             max_seq: Optional[int] = None,
                             kv_codec: Optional[str] = None,
                             steps: int = 1) -> Callable:
    """Pipeline-shaped greedy decode step for the tensor_repo loop:
    ``step(params, token, cache, pos) -> (next_token, cache, pos+steps)``
    — the state tuple a repo slot circulates (examples/llm_stream.py,
    bench config ``decode``).

    With ``steps > 1`` the step runs a ``lax.scan`` of that many decode
    steps inside ONE program and returns a fourth output, the ``[steps]``
    token block — the serving engine's multi-step-dispatch idea applied
    to the repo loop (per-invoke dispatch overhead amortizes over the
    block; the sequential token chain itself cannot be batched). Use
    ``input-combination=i0,i1,i2`` on the filter so the circulating state
    stays (token, cache, pos)."""
    decode = build_decode_step(cfg, max_seq, kv_codec)

    def one(params, token, cache, pos):
        logits, cache2 = decode(params, token.reshape(1).astype(jnp.int32),
                                cache, pos.reshape(()).astype(jnp.int32))
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return nxt, cache2, pos + 1

    if steps <= 1:
        return one

    def step(params, token, cache, pos):
        def body(carry, _):
            tok, cache, pos = carry
            nxt, cache, pos = one(params, tok, cache, pos)
            return (nxt, cache, pos), nxt.reshape(())

        (tok, cache, pos), toks = jax.lax.scan(
            body, (token.reshape(1).astype(jnp.int32), cache,
                   pos.reshape(()).astype(jnp.int32)),
            None, length=steps)
        return tok, cache, pos, toks

    return step


def make_sampler(vocab: int, temperature: float = 1.0,
                 top_k: int = 0, min_p: float = 0.0,
                 with_logprobs: bool = False) -> Callable:
    """The ONE sampling function: ``sample(logits[n, vocab],
    keys[uint32 n, 2]) -> (tokens[int32 n], new_keys[n, 2])`` — rows draw
    independently with their own threefry key, so results never depend on
    which other rows share the batch. ``temperature<=0`` degrades to
    greedy (keys pass through untouched); ``top_k>0`` restricts sampling
    to the k highest logits; ``min_p>0`` drops tokens whose probability
    is below ``min_p`` × the top token's (the modern min-p truncation —
    adaptive where top-k is fixed; both may combine). Shared by the
    repo-loop sampled step and the serving engine so their sampling math
    can never diverge.

    ``with_logprobs=True`` appends ``logprobs[float32 n]`` — the chosen
    token's log-probability under the UNMODIFIED distribution (fp32
    log_softmax of the raw logits; temperature/top-k shape the draw, the
    report stays the model's own confidence, the convention LM serving
    APIs use)."""
    if not 0.0 <= min_p <= 1.0:
        raise ValueError(
            f"make_sampler: min_p must be in [0, 1], got {min_p} "
            f"(it is a probability RATIO vs the top token, not a count "
            f"or percentage)")

    def sample(logits, keys):
        if temperature <= 0.0:
            toks = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            new_keys = keys
        else:
            scaled = logits / temperature
            if top_k > 0:
                k = min(top_k, vocab)  # over-asking = "no restriction"
                kth = jax.lax.top_k(scaled, k)[0][:, -1:]
                scaled = jnp.where(scaled >= kth, scaled, -1e30)
            if min_p > 0.0:
                # p_i >= min_p * p_max  ⟺  s_i >= s_max + log(min_p)
                # (on the temperature-scaled logits, after top-k)
                smax = jnp.max(scaled, axis=-1, keepdims=True)
                scaled = jnp.where(
                    scaled >= smax + np.log(min_p), scaled, -1e30)

            def row(key_row, logit_row):
                kk = jax.random.wrap_key_data(
                    jnp.asarray(key_row, jnp.uint32), impl="threefry2x32")
                kk, sub = jax.random.split(kk)
                tok = jax.random.categorical(sub, logit_row)
                return jax.random.key_data(kk), tok

            new_keys, toks = jax.vmap(row)(keys, scaled)
            toks = toks.astype(jnp.int32)
        if not with_logprobs:
            return toks, new_keys
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        chosen = jnp.take_along_axis(logp, toks[:, None], axis=1)[:, 0]
        return toks, new_keys, chosen

    return sample


def build_sample_stream_step(cfg: TransformerConfig,
                             max_seq: Optional[int] = None,
                             temperature: float = 1.0,
                             top_k: int = 0, min_p: float = 0.0,
                             kv_codec: Optional[str] = None) -> Callable:
    """Sampled decode step for the repo loop: ``step(params, token, cache,
    pos, key[uint32 2]) -> (next_token, cache, pos+1, next_key)`` — the
    PRNG key rides the state tuple like the cache does, so streaming stays
    deterministic given the seed. Sampling math is :func:`make_sampler`
    with one row."""
    decode = build_decode_step(cfg, max_seq, kv_codec)
    sample = make_sampler(cfg.vocab, temperature, top_k, min_p)

    def step(params, token, cache, pos, key):
        logits, cache2 = decode(params, token.reshape(1).astype(jnp.int32),
                                cache, pos.reshape(()).astype(jnp.int32))
        nxt, keys = sample(logits,
                           jnp.asarray(key, jnp.uint32).reshape(1, 2))
        return nxt, cache2, pos + 1, keys.reshape(2)

    return step


from nnstreamer_tpu.models.family import ModelFamily  # noqa: E402

#: the dense block as a member of the engine's model family: its programs
#: are the builders above, a lane holds nothing beside its blocks
DENSE = ModelFamily(
    name="dense", init_params=init_params, build_prefill=build_prefill,
    build_paged_decode_step=build_paged_decode_step,
    kv_entry=lambda cfg: (cfg.n_layers, 2, (cfg.n_heads, cfg.head_dim)),
    brings=("prefix_cache", "speculate", "prefill_chunk", "kv_quant",
            "mesh"),
    build_chunk_decode=build_chunk_decode,
    build_paged_chunk=build_paged_chunk,
    # the router is read in float32 and the embedding has two readers: the
    # lookup gathers rows and casts those, the head multiplies the stored
    # table in float32 (``_final_logits``)
    read_in_dtype=("qkv", "proj", "w_in", "w_out"))


def transformer_lm(vocab: int = 32000, d_model: int = 512, n_heads: int = 8,
                   n_layers: int = 4, d_ff: int = 2048, seq: int = 256,
                   batch: int = 1, dtype=jnp.bfloat16, num_experts: int = 0,
                   seed: int = 0, attention: str = "auto"
                   ) -> Tuple[Callable, Any, TensorsInfo, TensorsInfo]:
    """Filter-backend factory (single-device attention path).

    ``attention``: "auto" uses the Pallas flash kernel on TPU for tileable
    shapes (ops/flash_attention.py) and XLA attention elsewhere;
    "reference" forces XLA.
    """
    cfg = TransformerConfig(vocab=vocab, d_model=d_model, n_heads=n_heads,
                            n_layers=n_layers, d_ff=d_ff, dtype=dtype,
                            num_experts=num_experts)
    if attention not in ("auto", "reference"):
        raise ValueError(
            f"transformer_lm: attention must be 'auto' or 'reference', "
            f"got {attention!r}")
    params = init_params(cfg, seed)
    attention_fn = None
    if attention == "auto":
        from nnstreamer_tpu.ops import flash_attention

        attention_fn = lambda q, k, v: flash_attention(q, k, v, causal=True)
    fwd = build_forward(cfg, attention_fn)

    def apply_fn(params, tokens):
        return fwd(params, tokens)

    in_info = TensorsInfo.from_str(f"{seq}:{batch}", "int32")
    out_info = TensorsInfo.from_str(f"{vocab}:{seq}:{batch}", "float32")
    return apply_fn, params, in_info, out_info
