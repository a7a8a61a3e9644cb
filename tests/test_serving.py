"""Continuous-batching serving engine (serving/engine.py).

Correctness bar: a stream's output must be IDENTICAL whether it runs
alone through the manual prefill+decode loop or shares the engine's
batch with other streams at arbitrary admission times — per-stream
results never depend on batch composition.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from nnstreamer_tpu.models.transformer import (  # noqa: E402
    TransformerConfig,
    build_decode_step,
    build_prefill,
    init_params,
    make_sampler,
)
from nnstreamer_tpu.serving import ContinuousBatchingEngine  # noqa: E402

CFG = TransformerConfig(vocab=97, d_model=64, n_heads=4, n_layers=2,
                        d_ff=128, max_seq=64, dtype=jnp.float32)
PARAMS = init_params(CFG, seed=3)


def reference_greedy(prompt, n_tokens, cfg=CFG, params=PARAMS,
                     kv_codec=None):
    """Exact-length prefill + one-at-a-time greedy decode (no padding,
    no batching, a contiguous cache) — the ground truth the engine must
    match."""
    prefill = jax.jit(build_prefill(cfg, kv_codec=kv_codec))
    decode = jax.jit(build_decode_step(cfg, kv_codec=kv_codec))
    tokens = jnp.asarray(np.asarray(prompt, np.int32)[None])
    logits, cache1 = prefill(params, tokens)
    out = [int(jnp.argmax(logits[0]))]
    # engine caches are batch-B; replicate slot 0 semantics with batch 1
    tok = jnp.asarray([out[0]], jnp.int32)
    pos = jnp.asarray(len(prompt), jnp.int32)
    cache = cache1
    for _ in range(n_tokens - 1):
        logits, cache = decode(params, tok, cache, pos)
        nxt = int(jnp.argmax(logits[0]))
        out.append(nxt)
        tok = jnp.asarray([nxt], jnp.int32)
        pos = pos + 1
    return out


@pytest.fixture(scope="module")
def engine():
    eng = ContinuousBatchingEngine(
        CFG, PARAMS, max_streams=3, steps_per_dispatch=4,
        temperature=0.0).start()
    yield eng
    eng.stop()


def test_single_stream_matches_manual_decode(engine):
    prompt = [5, 11, 23, 42, 7]
    got = engine.generate(prompt, max_new_tokens=13, timeout=120)
    assert got == reference_greedy(prompt, 13)


def test_bucketed_prefill_matches_exact_length(engine):
    # prompt lengths straddling a bucket edge (engine pads to 16/32)
    for prompt in ([3], [9, 2, 4] * 5, list(range(1, 18))):
        got = engine.generate(prompt, max_new_tokens=6, timeout=120)
        assert got == reference_greedy(prompt, 6), f"len={len(prompt)}"


def test_concurrent_streams_match_isolated_runs(engine):
    prompts = [[4, 8, 15], [16, 23], [42, 7, 9, 1], [2, 2, 2, 2, 2],
               [31, 59, 26, 53]]
    streams = [engine.submit(p, max_new_tokens=9) for p in prompts]
    results = [s.result(timeout=240) for s in streams]
    for p, got in zip(prompts, results):
        assert got == reference_greedy(p, 9), f"prompt={p}"


def test_more_streams_than_slots_all_complete(engine):
    # 7 submissions on 3 slots: admission must recycle slots
    prompts = [[i + 1, i + 2] for i in range(7)]
    streams = [engine.submit(p, max_new_tokens=5) for p in prompts]
    for p, s in zip(prompts, streams):
        assert s.result(timeout=240) == reference_greedy(p, 5)
    assert engine.active_streams == 0


def test_eos_truncates_stream(engine):
    prompt = [5, 11, 23, 42, 7]
    ref = reference_greedy(prompt, 12)
    eos = ref[4]  # a token the model will actually emit
    eng = ContinuousBatchingEngine(
        CFG, PARAMS, max_streams=2, steps_per_dispatch=4,
        temperature=0.0, eos_id=eos).start()
    try:
        s = eng.submit(prompt, max_new_tokens=12)
        got = s.result(timeout=120)
    finally:
        eng.stop()
    stop_at = ref.index(eos)
    assert got == ref[: stop_at + 1]
    assert s.finish_reason == "eos"


@pytest.mark.parametrize("steps", [4, 8])
def test_length_budget_respects_cache_window(steps):
    """With 8 steps a dispatch the last one runs past ``max_seq``: the
    stream's block table must not grow past its width for them."""
    eng = ContinuousBatchingEngine(
        CFG, PARAMS, max_streams=1, steps_per_dispatch=steps,
        temperature=0.0).start()
    try:
        prompt = list(range(1, 60))  # 59 tokens, S=64 → at most 5 new
        s = eng.submit(prompt, max_new_tokens=50)
        got = s.result(timeout=120)
    finally:
        eng.stop()
    assert got == reference_greedy(prompt, CFG.max_seq - len(prompt))
    assert s.finish_reason == "length"


def test_sampled_streams_are_deterministic_per_stream_id():
    def run():
        eng = ContinuousBatchingEngine(
            CFG, PARAMS, max_streams=2, steps_per_dispatch=4,
            temperature=0.8, top_k=8, seed=7).start()
        try:
            a = eng.submit([5, 6, 7], max_new_tokens=8)
            b = eng.submit([9, 10], max_new_tokens=8)
            return a.result(timeout=120), b.result(timeout=120)
        finally:
            eng.stop()

    r1, r2 = run(), run()
    assert r1 == r2  # same seed + stream ids → same draws
    assert all(0 <= t < CFG.vocab for t in r1[0] + r1[1])


def test_invalid_prompts_rejected(engine):
    with pytest.raises(ValueError):
        engine.submit([], max_new_tokens=3)
    with pytest.raises(ValueError):
        engine.submit(list(range(CFG.max_seq)), max_new_tokens=3)
    with pytest.raises(ValueError):
        engine.submit([1, 2], max_new_tokens=0)


@pytest.mark.parametrize("steps", ["auto", 2.5, 0, True])
def test_steps_per_dispatch_is_a_positive_integer(steps):
    """K sizes the decode program and the window arena: a number the
    caller chooses, checked at construction."""
    with pytest.raises(ValueError, match="steps_per_dispatch"):
        ContinuousBatchingEngine(CFG, PARAMS, steps_per_dispatch=steps)
    assert ContinuousBatchingEngine(
        CFG, PARAMS, steps_per_dispatch=np.int64(3)).K == 3


def test_stop_finishes_inflight_streams():
    eng = ContinuousBatchingEngine(
        CFG, PARAMS, max_streams=1, steps_per_dispatch=2,
        temperature=0.0).start()
    s = eng.submit([1, 2, 3], max_new_tokens=10_000_000)
    eng.stop()
    assert s.finished
    with pytest.raises(RuntimeError):
        eng.submit([1, 2], max_new_tokens=4)  # stopped engine


def test_sharded_engine_matches_unsharded():
    """Multi-chip serving: a dp=2 × tp=2 mesh engine must emit exactly
    what the single-device engine does (GSPMD may not change results)."""
    from nnstreamer_tpu.parallel.mesh import make_mesh

    mesh = make_mesh([("dp", 2), ("tp", 2)])
    eng = ContinuousBatchingEngine(
        CFG, PARAMS, max_streams=4, steps_per_dispatch=4,
        temperature=0.0, mesh=mesh).start()
    try:
        prompts = [[4, 8, 15], [16, 23, 9], [7, 7], [1, 2, 3, 4, 5]]
        streams = [eng.submit(p, max_new_tokens=7) for p in prompts]
        results = [s.result(timeout=240) for s in streams]
    finally:
        eng.stop()
    for p, got in zip(prompts, results):
        assert got == reference_greedy(p, 7), f"prompt={p}"


def test_dp_only_mesh_serving():
    """A mesh with no tp axis (pure data-parallel serving) must work —
    param specs naming absent axes are pruned to replicated."""
    from nnstreamer_tpu.parallel.mesh import make_mesh

    mesh = make_mesh([("dp", 2)])
    eng = ContinuousBatchingEngine(
        CFG, PARAMS, max_streams=2, steps_per_dispatch=4,
        temperature=0.0, mesh=mesh).start()
    try:
        got = eng.generate([5, 11, 23, 42, 7], max_new_tokens=6,
                           timeout=240)
    finally:
        eng.stop()
    assert got == reference_greedy([5, 11, 23, 42, 7], 6)


def test_sharded_engine_validates_divisibility():
    from nnstreamer_tpu.parallel.mesh import make_mesh

    mesh = make_mesh([("dp", 1), ("tp", 8)])  # CFG.n_heads == 4
    with pytest.raises(ValueError):
        ContinuousBatchingEngine(CFG, PARAMS, max_streams=4, mesh=mesh)


def test_chunked_prefill_matches_exact():
    """Chunked ingestion (C=8) must be bit-identical to whole-prompt
    prefill for lengths below/at/above chunk boundaries."""
    eng = ContinuousBatchingEngine(
        CFG, PARAMS, max_streams=2, steps_per_dispatch=4,
        temperature=0.0, prefill_chunk=8).start()
    try:
        for n in (1, 7, 8, 9, 16, 20, 37):
            prompt = [(i * 13 + 5) % CFG.vocab for i in range(n)]
            got = eng.generate(prompt, max_new_tokens=6, timeout=240)
            assert got == reference_greedy(prompt, 6), f"len={n}"
    finally:
        eng.stop()


def test_chunked_prefill_interleaves_with_decode():
    """A long prompt admitted while another stream decodes: both exact
    (prefill chunks run between decode dispatches, not instead of them)."""
    eng = ContinuousBatchingEngine(
        CFG, PARAMS, max_streams=2, steps_per_dispatch=2,
        temperature=0.0, prefill_chunk=4).start()
    try:
        a = eng.submit([5, 11, 23], max_new_tokens=20)
        long_prompt = [(i * 7 + 2) % CFG.vocab for i in range(30)]
        b = eng.submit(long_prompt, max_new_tokens=8)
        ra, rb = a.result(timeout=240), b.result(timeout=240)
    finally:
        eng.stop()
    assert ra == reference_greedy([5, 11, 23], 20)
    assert rb == reference_greedy(long_prompt, 8)
    assert eng.stats["prefill_chunks"] >= 8 + 1  # 30/4 → 8 + short prompt


def test_chunked_prefill_prompt_limit():
    """The bound is ceil(n/C)*C <= S: when C divides S it equals the
    plain n < S rule (no capacity lost); otherwise the last partial
    chunk must still fit the cache."""
    # C=8 divides S=64: same capacity as the unchunked engine (63)
    eng = ContinuousBatchingEngine(
        CFG, PARAMS, max_streams=1, prefill_chunk=8).start()
    try:
        assert len(eng.generate(list(range(1, 64)), max_new_tokens=5,
                                timeout=240)) == 1  # budget S-63 = 1
    finally:
        eng.stop()
    # C=12 does not divide S=64: limit is (64//12)*12 = 60
    eng = ContinuousBatchingEngine(
        CFG, PARAMS, max_streams=1, prefill_chunk=12).start()
    try:
        with pytest.raises(ValueError):
            eng.submit(list(range(61)), max_new_tokens=2)
        assert len(eng.generate(list(range(60)), max_new_tokens=9,
                                timeout=240)) == 4  # budget S-60 = 4
    finally:
        eng.stop()
    with pytest.raises(ValueError):
        ContinuousBatchingEngine(CFG, PARAMS, prefill_chunk=CFG.max_seq)


def test_prefix_cache_exact_hit_skips_prefill():
    eng = ContinuousBatchingEngine(
        CFG, PARAMS, max_streams=2, steps_per_dispatch=4,
        temperature=0.0, prefix_cache=4).start()
    try:
        prompt = [5, 11, 23, 42]
        a = eng.generate(prompt, max_new_tokens=7, timeout=240)
        prefills_before = eng.stats["prefills"]
        b = eng.generate(prompt, max_new_tokens=7, timeout=240)
    finally:
        eng.stop()
    assert a == b == reference_greedy(prompt, 7)
    assert eng.stats["prefix_hits"] == 1
    assert eng.stats["prefix_tokens_reused"] == len(prompt)
    # the hit still counts as an admission ("prefills") but computed no
    # new prefill program — verified by exactness + the hit counter
    assert eng.stats["prefills"] == prefills_before + 1


def test_prefix_cache_extension_is_exact():
    """A ... then A+B: the warm engine's A+B output must equal a cold
    engine's — reused kv is the same array a cold prefill computes."""
    base = [7, 3, 11, 30, 2, 9]
    full = base + [14, 27, 5]
    cold = reference_greedy(full, 9)
    # reuse is by whole blocks: blocks of 2 tokens divide the base
    eng = ContinuousBatchingEngine(
        CFG, PARAMS, max_streams=2, steps_per_dispatch=4,
        temperature=0.0, prefix_cache=4, block_tokens=2).start()
    try:
        eng.generate(base, max_new_tokens=3, timeout=240)
        got = eng.generate(full, max_new_tokens=9, timeout=240)
    finally:
        eng.stop()
    assert got == cold
    assert eng.stats["prefix_hits"] == 1
    assert eng.stats["prefix_tokens_reused"] == len(base)


def test_prefix_cache_with_chunked_prefill():
    """Chunked ingestion stores its prompts as entries but ingests every
    prompt from 0 (ROADMAP: chunked ingestion straight into blocks): the
    output is exact and nothing is counted as reused."""
    base = [(i * 13 + 5) % CFG.vocab for i in range(17)]
    full = base + [(i * 7 + 1) % CFG.vocab for i in range(9)]
    cold = reference_greedy(full, 6)
    eng = ContinuousBatchingEngine(
        CFG, PARAMS, max_streams=2, steps_per_dispatch=4,
        temperature=0.0, prefix_cache=4, prefill_chunk=8).start()
    try:
        eng.generate(base, max_new_tokens=3, timeout=240)
        chunks_before = eng.stats["prefill_chunks"]
        got = eng.generate(full, max_new_tokens=6, timeout=240)
        chunks_used = eng.stats["prefill_chunks"] - chunks_before
    finally:
        eng.stop()
    assert got == cold
    assert chunks_used == 4  # ceil(26/8): every chunk of the prompt
    assert eng.stats["prefix_tokens_reused"] == 0
    assert len(eng._prefix) == 2


def test_prefix_cache_shared_system_prompt():
    """Two DIFFERENT prompts sharing a preamble: the second reuses the
    common prefix of the first's cached kv (LCP match, not whole-entry
    match) and stays exact."""
    system = [9, 21, 33, 45, 2, 17, 8, 30]
    u1 = system + [50, 51]
    u2 = system + [60, 61, 62]
    cold_u2 = reference_greedy(u2, 8)
    # reuse is by whole blocks: the preamble is one block of 8 tokens
    eng = ContinuousBatchingEngine(
        CFG, PARAMS, max_streams=2, steps_per_dispatch=4,
        temperature=0.0, prefix_cache=4, block_tokens=8).start()
    try:
        eng.generate(u1, max_new_tokens=3, timeout=240)
        got = eng.generate(u2, max_new_tokens=8, timeout=240)
    finally:
        eng.stop()
    assert got == cold_u2
    assert eng.stats["prefix_hits"] == 1
    assert eng.stats["prefix_tokens_reused"] == len(system)


def test_prefix_cache_prompt_inside_longer_entry():
    """The new prompt is a strict PREFIX of a stored key: kv is reused
    for the whole blocks below its last position, which recomputes for
    its logits."""
    long_p = [5, 11, 23, 42, 7, 9, 14]
    short_p = long_p[:6]
    ref = reference_greedy(short_p, 6)
    # blocks of 2 tokens: positions 0..3 are shared (two whole blocks
    # below position n-1 = 5, and PREFIX_MIN_REUSE = 4), 4..5 recompute
    eng = ContinuousBatchingEngine(
        CFG, PARAMS, max_streams=2, steps_per_dispatch=4,
        temperature=0.0, prefix_cache=4, block_tokens=2).start()
    try:
        eng.generate(long_p, max_new_tokens=3, timeout=240)
        got = eng.generate(short_p, max_new_tokens=6, timeout=240)
    finally:
        eng.stop()
    assert got == ref
    assert eng.stats["prefix_hits"] == 1
    assert eng.stats["prefix_tokens_reused"] == 4


def test_prefix_cache_exact_repeat_wins_over_longer_tie():
    """With both [1..5] and [1..3] cached, resubmitting [1..3] must take
    the zero-prefill exact path (stored logits), not the longer key."""
    eng = ContinuousBatchingEngine(
        CFG, PARAMS, max_streams=1, steps_per_dispatch=4,
        temperature=0.0, prefix_cache=4).start()
    try:
        eng.generate([1, 2, 3, 4, 5], max_new_tokens=3, timeout=240)
        first = eng.generate([1, 2, 3], max_new_tokens=3, timeout=240)
        reused_before = eng.stats["prefix_tokens_reused"]
        again = eng.generate([1, 2, 3], max_new_tokens=3, timeout=240)
        reused = eng.stats["prefix_tokens_reused"] - reused_before
    finally:
        eng.stop()
    assert first == again == reference_greedy([1, 2, 3], 3)
    assert reused == 3  # whole prompt, not len-1 via the longer key


def test_prefix_cache_validation():
    with pytest.raises(ValueError):
        ContinuousBatchingEngine(CFG, PARAMS, prefix_cache=-1)


def test_prefix_cache_lru_eviction():
    eng = ContinuousBatchingEngine(
        CFG, PARAMS, max_streams=1, steps_per_dispatch=4,
        temperature=0.0, prefix_cache=1).start()
    try:
        for p in ([1, 2], [3, 4], [5, 6]):
            eng.generate(p, max_new_tokens=3, timeout=240)
        assert len(eng._prefix) == 1
        # oldest evicted: repeating the first prompt is a miss
        eng.generate([1, 2], max_new_tokens=3, timeout=240)
        assert eng.stats["prefix_hits"] == 0
    finally:
        eng.stop()


def test_engine_invoke_stats_populated(engine):
    engine.generate([4, 4, 4], max_new_tokens=6, timeout=240)
    assert engine.invoke_stats.total_invokes >= 1
    assert engine.invoke_stats.latency_us > 0


def test_moe_model_serves_exactly():
    """A mixture-of-experts config through the whole engine path
    (prefill capture, batched decode, chunked prefill) must match the
    isolated greedy decode — MoE routing rides _block_tail everywhere."""
    moe_cfg = TransformerConfig(vocab=97, d_model=64, n_heads=4,
                                n_layers=2, d_ff=64, max_seq=64,
                                dtype=jnp.float32, num_experts=4)
    moe_params = init_params(moe_cfg, seed=6)
    prompt = [5, 11, 23, 42, 9, 1]
    ref = reference_greedy(prompt, 8, cfg=moe_cfg, params=moe_params)
    for kw in ({}, {"prefill_chunk": 4}):
        eng = ContinuousBatchingEngine(
            moe_cfg, moe_params, max_streams=2, steps_per_dispatch=4,
            temperature=0.0, **kw).start()
        try:
            got = eng.generate(prompt, max_new_tokens=8, timeout=240)
        finally:
            eng.stop()
        assert got == ref, kw


def test_min_p_sampling():
    """min_p truncation: drawn tokens always satisfy p >= min_p * p_max;
    min_p=1.0 with temperature degenerates to greedy."""
    rng = np.random.default_rng(0)
    logits = jnp.asarray(rng.normal(0, 2, (1, CFG.vocab)), jnp.float32)
    probs = np.asarray(jax.nn.softmax(logits[0]))
    sample = make_sampler(CFG.vocab, temperature=1.0, min_p=0.5)
    keys = np.asarray([[1, 2]], np.uint32)
    drawn = set()
    for _ in range(64):
        tok, keys = sample(logits, jnp.asarray(keys))
        drawn.add(int(tok[0]))
        keys = np.asarray(keys)
    assert all(probs[t] >= 0.5 * probs.max() - 1e-9 for t in drawn), drawn
    # engine-level: min_p=1.0 ≡ greedy even at temperature 1
    eng = ContinuousBatchingEngine(
        CFG, PARAMS, max_streams=1, steps_per_dispatch=4,
        temperature=1.0, min_p=1.0).start()
    try:
        got = eng.generate([5, 11, 23], max_new_tokens=6, timeout=240)
    finally:
        eng.stop()
    assert got == reference_greedy([5, 11, 23], 6)


def test_logprobs_parallel_and_correct(engine):
    prompt = [5, 11, 23]
    s = engine.submit(prompt, max_new_tokens=6)
    toks = s.result(timeout=240)
    assert len(s.logprobs) == len(toks) == 6
    assert all(lp <= 0.0 for lp in s.logprobs)
    # greedy: the reported logprob is the max of the fp32 log_softmax at
    # that step — check the first (prefill-seeded) token by hand
    import jax

    from nnstreamer_tpu.models.transformer import build_prefill

    logits, _ = jax.jit(build_prefill(CFG))(
        PARAMS, jnp.asarray(np.asarray(prompt, np.int32)[None]))
    expect = float(jax.nn.log_softmax(
        logits[0].astype(jnp.float32))[toks[0]])
    assert s.logprobs[0] == pytest.approx(expect, rel=1e-5)


def test_cancel_active_stream_frees_slot():
    import dataclasses

    # large cache → budget min(max_new, S-n) ≈ 500: the engine cannot
    # length-finish in the instants between first token and cancel, so
    # the "cancelled" outcome is deterministic
    cfg = dataclasses.replace(CFG, max_seq=512)
    eng = ContinuousBatchingEngine(
        cfg, PARAMS, max_streams=1, steps_per_dispatch=2,
        temperature=0.0).start()
    try:
        s = eng.submit([1, 2, 3], max_new_tokens=500)
        for _ in s:  # first token proves the stream is admitted + live
            s.cancel()
            break
        s.result(timeout=240)
        assert s.finish_reason == "cancelled"
        assert len(s.tokens) < 500
        # the single slot must be free again: a new stream completes
        got = eng.generate([4, 5], max_new_tokens=4, timeout=240)
        assert len(got) == 4
    finally:
        eng.stop()


def test_cancel_pending_stream_never_admits():
    eng = ContinuousBatchingEngine(
        CFG, PARAMS, max_streams=1, steps_per_dispatch=2,
        temperature=0.0).start()
    try:
        blocker = eng.submit([1, 2], max_new_tokens=200)  # hogs the slot
        pending = eng.submit([3, 4], max_new_tokens=5)
        pending.cancel()
        assert pending.result(timeout=120) == []
        assert pending.finish_reason == "cancelled"
        blocker.cancel()
    finally:
        eng.stop()


def test_dispatch_failure_fails_streams_and_recovers():
    """A device failure mid-dispatch must fail in-flight streams fast
    (no hang), rebuild the donated-away cache, and keep serving new
    requests — the engine's failure-detection contract."""
    eng = ContinuousBatchingEngine(
        CFG, PARAMS, max_streams=2, steps_per_dispatch=4,
        temperature=0.0).start()
    try:
        real = eng._dispatch
        state = {"raised": False}

        def flaky(*args):
            if not state["raised"]:
                state["raised"] = True
                raise RuntimeError("injected device failure")
            return real(*args)

        eng._dispatch = flaky
        s = eng.submit([5, 11, 23], max_new_tokens=8)
        out = s.result(timeout=240)
        assert s.finish_reason == "error: injected device failure"
        assert out == s.tokens  # whatever was emitted pre-failure
        # engine recovered: fresh request completes correctly
        got = eng.generate([4, 8, 15], max_new_tokens=5, timeout=240)
        assert got == reference_greedy([4, 8, 15], 5)
    finally:
        eng.stop()


def test_concurrent_submit_stress():
    """Hammer submit() from many threads against few slots while streams
    complete and slots recycle: every stream must finish with the right
    token count and the engine must stay consistent (no deadlock, no
    dropped request) — the reference relies on GLib locking discipline
    for its pipeline races (SURVEY §5); this is ours, exercised."""
    import threading

    eng = ContinuousBatchingEngine(
        CFG, PARAMS, max_streams=2, steps_per_dispatch=2,
        temperature=0.0, prefix_cache=2).start()
    results, errors = {}, []

    def client(tid):
        try:
            out = []
            for i in range(3):
                prompt = [(tid * 7 + i * 3 + 1) % CFG.vocab + 1,
                          (tid + i) % CFG.vocab]
                out.append(eng.generate(prompt, max_new_tokens=4,
                                        timeout=300))
            results[tid] = out
        except Exception as e:  # noqa: BLE001 — collected for assertion
            errors.append((tid, e))

    threads = [threading.Thread(target=client, args=(t,))
               for t in range(6)]
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=600)
        assert not any(t.is_alive() for t in threads), "stress deadlock"
    finally:
        eng.stop()
    assert not errors, errors
    assert len(results) == 6
    for tid, outs in results.items():
        for out in outs:
            assert len(out) == 4, (tid, outs)
    assert eng.active_streams == 0


def test_submit_before_start_rejected():
    eng = ContinuousBatchingEngine(CFG, PARAMS, max_streams=1)
    with pytest.raises(RuntimeError):
        eng.submit([1, 2], max_new_tokens=4)


class TestPrefixTrie:
    """O(prompt_len) LCP index replacing the linear scan
    (serving/engine.py _PrefixTrie)."""

    @staticmethod
    def _brute(keys, prompt):
        best_key, best_lcp = None, 0
        for key in keys:
            m = min(len(key), len(prompt))
            lcp = 0
            while lcp < m and key[lcp] == prompt[lcp]:
                lcp += 1
            exact = lcp == len(prompt) == len(key)
            if lcp > best_lcp or (exact and lcp >= best_lcp):
                best_key, best_lcp = key, lcp
        return best_lcp

    def test_matches_brute_force_with_eviction(self):
        import random

        from nnstreamer_tpu.serving.engine import _PrefixTrie

        rng = random.Random(7)
        trie, keys = _PrefixTrie(), []
        for step in range(400):
            if keys and rng.random() < 0.3:
                k = keys.pop(rng.randrange(len(keys)))
                trie.remove(k)
                continue
            k = tuple(rng.randrange(4) for _ in range(rng.randrange(1, 10)))
            if k not in keys:
                keys.append(k)
                trie.insert(k)
            prompt = [rng.randrange(4) for _ in range(rng.randrange(1, 12))]
            got_key, got_lcp = trie.lookup(prompt)
            want_lcp = self._brute(keys, prompt)
            assert got_lcp == want_lcp
            if got_lcp:
                # returned key really shares got_lcp tokens with prompt
                assert tuple(got_key[:got_lcp]) == tuple(prompt[:got_lcp])

    def test_exact_match_preferred(self):
        from nnstreamer_tpu.serving.engine import _PrefixTrie

        trie = _PrefixTrie()
        trie.insert((1, 2, 3, 4, 5))  # longer key covering the prompt
        trie.insert((1, 2, 3))        # exact
        key, lcp = trie.lookup([1, 2, 3])
        assert key == (1, 2, 3) and lcp == 3

    def test_lookup_cost_is_prompt_bound(self):
        """visits are bounded by prompt length, not entry count."""
        from nnstreamer_tpu.serving.engine import _PrefixTrie

        trie = _PrefixTrie()
        for i in range(512):  # disjoint first tokens: a wide, shallow trie
            trie.insert((1000 + i, 1, 2, 3))
        calls = 0
        orig_get = dict.get

        class CountingDict(dict):
            def get(self, *a):
                nonlocal calls
                calls += 1
                return orig_get(self, *a)

        # wrap every kids dict
        def wrap(node):
            node["kids"] = CountingDict(node["kids"])
            for k in node["kids"].values():
                wrap(k)

        wrap(trie.root)
        trie.lookup([1000, 1, 2, 3, 9, 9, 9, 9])
        assert calls <= 8 + 1  # one child probe per prompt token


class TestEngineRestartAfterStuckStop:
    def test_start_reaps_dead_leftover_thread(self):
        """ADVICE r2: a timed-out stop() retains _thread; once that loop
        exits, start() must reap it and spin a fresh loop (not no-op)."""
        eng = ContinuousBatchingEngine(
            CFG, PARAMS, max_streams=2, steps_per_dispatch=2,
            temperature=0.0).start()
        try:
            assert eng.generate([4, 8], max_new_tokens=2, timeout=120)
            eng.stop()
            # simulate the timed-out-stop leftover: thread ref retained
            # though the loop has exited
            dead = eng._thread if eng._thread is not None else None
            if dead is None:
                import threading

                dead = threading.Thread(target=lambda: None)
                dead.start()
                dead.join()
                eng._thread = dead
                eng._stop_evt.set()
            eng.start()  # must reap and restart, not silently no-op
            assert eng._thread is not None and eng._thread.is_alive()
            assert eng.generate([4, 8], max_new_tokens=2, timeout=120)
        finally:
            eng.stop()


def test_dp_slot_scaling_throughput():
    """Aggregate throughput must scale with dp-sharded batch slots,
    holding the mesh fixed: a dp4×tp2 engine with 8 slots vs the SAME
    mesh with 4 slots (VERDICT r3 item 6: prove the dp4 gain). The
    asserted quantity is tokens per decode dispatch — the structural
    win slot scaling buys (on real chips each dispatch costs roughly
    the same wall time, so tokens/dispatch IS the throughput gain);
    wall-clock ratios on a shared CI host are too noisy to gate on."""
    from nnstreamer_tpu.parallel.mesh import make_mesh

    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, CFG.vocab, 6).tolist() for _ in range(8)]
    mesh = make_mesh([("dp", 4), ("tp", 2)])

    def tokens_per_dispatch(streams):
        eng = ContinuousBatchingEngine(
            CFG, PARAMS, max_streams=streams, steps_per_dispatch=8,
            temperature=0.0, mesh=mesh).start()
        try:
            # compile off the clock (each engine has its own batch shape)
            eng.generate(prompts[0], max_new_tokens=8, timeout=240)
            d0 = eng.stats["dispatches"]
            t0 = eng.stats["tokens_generated"]
            ss = [eng.submit(p, max_new_tokens=24) for p in prompts]
            total = sum(len(s.result(timeout=240)) for s in ss)
            assert total == 8 * 24
            d = eng.stats["dispatches"] - d0
            t = eng.stats["tokens_generated"] - t0
            return t / max(d, 1)
        finally:
            eng.stop()

    slots4 = tokens_per_dispatch(4)
    slots8 = tokens_per_dispatch(8)
    # 2x the dp-sharded slots → the 8 concurrent streams run in one
    # admission wave instead of two, roughly doubling the tokens each
    # dispatch delivers (tail effects eat a little of the 2x)
    assert slots8 > 1.5 * slots4, (slots8, slots4)
