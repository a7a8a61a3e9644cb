"""int8-quantized KV cache (models/transformer._Int8KVCodec).

Claims under test: half the cache bytes, bounded numeric drift vs the
exact cache, and internal consistency (chunk vs sequential, prefill vs
decode) is EXACT — quantization error must be a property of the cache
content, not of which code path filled it.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from nnstreamer_tpu.models.transformer import (  # noqa: E402
    TransformerConfig,
    _kv_codec,
    _paged_gather,
    build_decode_step,
    build_paged_chunk,
    build_prefill,
    init_cache,
    init_params,
)
from nnstreamer_tpu.serving import ContinuousBatchingEngine  # noqa: E402

CFG = TransformerConfig(vocab=128, d_model=64, n_heads=4, n_layers=2,
                        d_ff=128, max_seq=48, dtype=jnp.float32)
PARAMS = init_params(CFG, seed=2)


def test_q8_cache_halves_bytes():
    import dataclasses

    bf16 = dataclasses.replace(CFG, dtype=jnp.bfloat16)
    raw = init_cache(bf16, batch=2)
    q8 = init_cache(bf16, batch=2, kv_codec="int8")
    raw_bytes = raw.nbytes
    q8_bytes = sum(x.nbytes for x in jax.tree.leaves(q8))
    # int8 values are half of bf16; scales add 4/dh per element
    assert q8_bytes < raw_bytes * (0.5 + 4 / bf16.head_dim + 0.05)
    assert q8["q"].dtype == jnp.int8


def _run_steps(decode, cache, tokens, start):
    logits_all = []
    tok = jnp.asarray([tokens[0]], jnp.int32)
    pos = jnp.asarray(start, jnp.int32)
    for t in tokens[1:] + [0]:
        logits, cache = decode(PARAMS, tok, cache, pos)
        logits_all.append(logits)
        tok = jnp.asarray([t], jnp.int32)
        pos = pos + 1
    return jnp.stack(logits_all, 1), cache


def test_q8_decode_close_to_exact():
    prefill = jax.jit(build_prefill(CFG))
    prefill_q = jax.jit(build_prefill(CFG, kv_codec="int8"))
    decode = jax.jit(build_decode_step(CFG))
    decode_q = jax.jit(build_decode_step(CFG, kv_codec="int8"))
    prompt = jnp.asarray([[7, 3, 11, 30, 2]], jnp.int32)
    l0, cache = prefill(PARAMS, prompt)
    l0q, cache_q = prefill_q(PARAMS, prompt)
    np.testing.assert_allclose(np.asarray(l0q), np.asarray(l0),
                               rtol=0.05, atol=0.05 * float(
                                   jnp.abs(l0).max()))
    toks = [9, 14, 27, 5, 18, 40]
    la, _ = _run_steps(decode, cache, toks, 5)
    lb, _ = _run_steps(decode_q, cache_q, toks, 5)
    # bounded drift: int8 per-vector absmax keeps logits within a few
    # percent of the exact cache on every step
    err = float(jnp.max(jnp.abs(la - lb)))
    ref = float(jnp.max(jnp.abs(la)))
    assert err < 0.08 * ref, (err, ref)


def test_q8_chunk_matches_sequential_q8_exactly():
    """Same cache content → same quantization: the paged chunk program
    and the step path run position by position must agree bitwise given
    identical inputs."""
    T = 8
    codec = _kv_codec(CFG, "int8")
    decode_q = jax.jit(build_decode_step(CFG, kv_codec="int8"))
    chunk_q = jax.jit(build_paged_chunk(CFG, T, kv_codec="int8"))
    mb = CFG.max_seq // T
    arena = codec.paged_init(CFG.n_layers, mb + 1, T, CFG.n_heads,
                             CFG.head_dim)
    bt = jnp.arange(mb, dtype=jnp.int32)[None]
    cache = init_cache(CFG, 1, kv_codec="int8")
    toks = jnp.asarray([[3, 1, 4, 9, 2, 6, 5]], jnp.int32)
    cl, arena = chunk_q(PARAMS, toks, arena, bt, jnp.asarray([0], jnp.int32),
                        jnp.asarray([7], jnp.int32))
    seq = []
    for i in range(7):
        lg, cache = decode_q(PARAMS, toks[:, i], cache,
                             jnp.asarray(i, jnp.int32))
        seq.append(lg)
    np.testing.assert_allclose(np.asarray(cl),
                               np.asarray(jnp.stack(seq, 1)),
                               rtol=2e-5, atol=2e-5)
    for layer in range(CFG.n_layers):  # [1, 2, S, h, dh] against [2, 1, ..]
        got = _paged_gather(arena["q"], layer, bt, codec.heads_major)
        np.testing.assert_array_equal(
            np.asarray(got)[0, :, :7], np.asarray(cache["q"])[layer, :, 0, :7])


def test_engine_with_q8_cache_generates_deterministically():
    def run(**kw):
        eng = ContinuousBatchingEngine(
            CFG, PARAMS, max_streams=2, steps_per_dispatch=4,
            temperature=0.0, **kw).start()
        try:
            return eng.generate([5, 11, 23], max_new_tokens=8,
                                timeout=240)
        finally:
            eng.stop()

    q1, q2 = run(kv_quant="int8"), run(kv_quant="int8")
    assert q1 == q2 and len(q1) == 8
    exact = run()
    # the first token comes from un-quantized prefill activations and is
    # bit-identical; later tokens read the int8 cache where argmax may
    # legitimately flip within the drift bound — exactness is not the
    # int8 contract
    assert q1[:1] == exact[:1]


def test_engine_q8_with_chunked_prefill():
    eng = ContinuousBatchingEngine(
        CFG, PARAMS, max_streams=2, steps_per_dispatch=4,
        temperature=0.0, kv_quant="int8", prefill_chunk=4).start()
    try:
        got = eng.generate([(i * 5 + 1) % CFG.vocab for i in range(11)],
                           max_new_tokens=6, timeout=240)
    finally:
        eng.stop()
    assert len(got) == 6 and all(0 <= t < CFG.vocab for t in got)


def test_bad_codec_rejected():
    with pytest.raises(ValueError):
        init_cache(CFG, 1, kv_codec="int4")
