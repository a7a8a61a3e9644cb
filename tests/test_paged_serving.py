"""Paged-KV continuous batching (serving/engine.py + serving/kvpool.py).

The correctness bar:

- the block arena is the engine's one KV store: ``block_tokens`` is a
  size, never a mode, and no environment variable switches it off;
- greedy outputs are byte-identical to the model-level reference over a
  contiguous cache (``build_prefill`` + ``build_decode_step``) for the
  same prompts — single stream, concurrent streams, ``kv_quant=int8``,
  chunked prefill, and oversubscription (more streams than decode
  lanes) alike;
- the decode loop stays ONE jitted program (retrace count pinned);
- under a starved pool the evict -> shed ladder fires, shed streams'
  blocks return to the free list, and surviving streams stay exact;
- copy-on-write prefix sharing retains blocks once across streams;
- paging x int8 x mesh=dp2 composes byte-identically (satellite 4);
- the arena is ONE buffer the decode program updates in place (PR 26): a
  carry of both scans, never a scan's ``xs``/``ys``; every layer's slots,
  the last included, hold what the monolithic cache holds; an empty lane
  writes nowhere and the zero block stays zero.
"""

import dataclasses

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from nnstreamer_tpu.serving import ContinuousBatchingEngine  # noqa: E402
from tests.test_serving import CFG, PARAMS, reference_greedy  # noqa: E402

T = 8


def assert_zero_block_is_zero(eng):
    """Index NTOT-1 of every layer and leaf: never allocated, never
    written — what the empty lanes and the unallocated tails read."""
    for leaf in jax.tree.leaves(eng._pool.arena):
        assert not np.asarray(leaf)[:, eng._pool.ntot - 1].any()


def paged_engine(**kw):
    kw.setdefault("max_streams", 3)
    kw.setdefault("steps_per_dispatch", 4)
    kw.setdefault("temperature", 0.0)
    kw.setdefault("block_tokens", T)
    return ContinuousBatchingEngine(CFG, PARAMS, **kw).start()


PROMPTS = [[5, 11, 23, 42, 7], [4, 8, 15], [16, 23], [42, 7, 9, 1],
           [2, 2, 2, 2, 2], [31, 59, 26, 53], [9] * 17, [13, 2]]


# -- one KV store: a size, not a mode ---------------------------------------


def test_default_engine_serves_from_a_block_pool():
    """An engine built as its signature suggests, with no
    ``block_tokens``, has the arena and one of the two decode forms."""
    from nnstreamer_tpu.serving.kvpool import BlockPool

    eng = ContinuousBatchingEngine(
        CFG, PARAMS, max_streams=2, steps_per_dispatch=4,
        temperature=0.0).start()
    try:
        assert isinstance(eng._pool, BlockPool)
        assert eng.block_tokens == 16 and eng.MB == CFG.max_seq // 16
        assert eng.decode_attention in ("gather", "paged_kernel")
        got = eng.generate(PROMPTS[0], max_new_tokens=9, timeout=120)
        assert eng._pool.live_blocks() == 0
    finally:
        eng.stop()
    assert got == reference_greedy(PROMPTS[0], 9)


def test_removed_env_switch_changes_nothing(monkeypatch):
    """``NNSTPU_PAGED_KV`` was the monolithic cache's kill switch; it is
    read by nothing now, and must not come back unnoticed."""
    monkeypatch.setenv("NNSTPU_PAGED_KV", "0")
    eng = paged_engine()
    try:
        assert eng._pool is not None and eng.block_tokens == T
        got = eng.generate(PROMPTS[0], max_new_tokens=9, timeout=120)
        assert eng.stats["kv_blocks_table"] > 0   # decoded from the arena
    finally:
        eng.stop()
    assert got == reference_greedy(PROMPTS[0], 9)


@pytest.mark.parametrize("block_tokens", [0, -1, 24],
                         ids=["zero", "negative", "non-divisor"])
def test_block_tokens_must_be_a_positive_divisor_of_max_seq(block_tokens):
    with pytest.raises(ValueError, match="positive divisor of max_seq"):
        ContinuousBatchingEngine(CFG, PARAMS, block_tokens=block_tokens)


# -- greedy byte-parity vs the contiguous-cache reference -----------------


def test_single_stream_matches_reference():
    eng = paged_engine()
    try:
        for p in PROMPTS[:4]:
            assert eng.generate(p, max_new_tokens=9, timeout=120) == \
                reference_greedy(p, 9), f"prompt={p}"
    finally:
        eng.stop()
    assert_zero_block_is_zero(eng)   # two of the three lanes were empty


def test_concurrent_streams_match_isolated_runs():
    eng = paged_engine()
    try:
        streams = [eng.submit(p, max_new_tokens=9) for p in PROMPTS[:5]]
        results = [s.result(timeout=240) for s in streams]
    finally:
        eng.stop()
    for p, got in zip(PROMPTS, results):
        assert got == reference_greedy(p, 9), f"prompt={p}"


def test_int8_paged_matches_int8_monolithic():
    """The per-block int8 codec must equal the model-level int8
    contiguous cache (``build_prefill`` / ``build_decode_step`` with
    ``kv_codec="int8"``) bit for bit — same quantization grid, different
    storage layout."""
    want = [reference_greedy(p, 9, kv_codec="int8") for p in PROMPTS[:3]]
    eng = paged_engine(kv_quant="int8")
    try:
        got = [eng.generate(p, max_new_tokens=9, timeout=120)
               for p in PROMPTS[:3]]
    finally:
        eng.stop()
    assert got == want
    assert_zero_block_is_zero(eng)


def test_chunked_prefill_composes_with_paging():
    eng = paged_engine(prefill_chunk=16)
    try:
        for p in (PROMPTS[6], list(range(1, 30))):
            assert eng.generate(p, max_new_tokens=6, timeout=120) == \
                reference_greedy(p, 6), f"len={len(p)}"
    finally:
        eng.stop()


# -- one jitted decode program --------------------------------------------


def test_decode_loop_stays_one_jitted_program():
    eng = paged_engine()
    try:
        streams = [eng.submit(p, max_new_tokens=7) for p in PROMPTS[:5]]
        for s in streams:
            s.result(timeout=240)
        # every dispatch reuses the single traced program: block tables
        # and positions are data, not shape, so stream churn and block
        # growth never retrace
        assert eng._dispatch._cache_size() == 1
    finally:
        eng.stop()


# -- oversubscription: more streams than decode lanes ---------------------


def test_oversubscribed_streams_stay_exact():
    """12 streams over 2 decode lanes: EDF time-sharing parks and
    rebinds lanes at block granularity, and every stream's output is
    still byte-identical to its isolated run."""
    eng = paged_engine(max_streams=2, kv_blocks=64)
    try:
        prompts = [PROMPTS[i % len(PROMPTS)] for i in range(12)]
        streams = [eng.submit(p, max_new_tokens=8) for p in prompts]
        results = [s.result(timeout=480) for s in streams]
        assert eng.stats["concurrent_streams_max"] > eng.B
    finally:
        eng.stop()
    for p, got in zip(prompts, results):
        assert got == reference_greedy(p, 8), f"prompt={p}"


def test_starved_pool_sheds_and_recycles_blocks():
    """A pool too small for the offered load must shed (most-late
    stream first), count it, and return every block to the free list —
    never wedge admission or leak."""
    eng = paged_engine(max_streams=2, kv_blocks=6, prefix_cache=0)
    try:
        streams = [eng.submit(PROMPTS[i % len(PROMPTS)],
                              max_new_tokens=24) for i in range(8)]
        done = [s.result(timeout=480) for s in streams]
        reasons = [s.finish_reason for s in streams]
        assert eng.stats["kv_sheds"] > 0
        assert all(r in ("length", "shed", "eos") for r in reasons)
        # shed streams still returned their partial output
        assert all(done[i] is not None for i in range(len(done)))
        assert eng._pool.live_blocks() == 0
        # non-shed streams remained exact despite the churn
        for s, p, got in zip(streams, [PROMPTS[i % len(PROMPTS)]
                                       for i in range(8)], done):
            if s.finish_reason == "length":
                assert got == reference_greedy(p, 24), f"prompt={p}"
    finally:
        eng.stop()


# -- copy-on-write prefix sharing -----------------------------------------


def test_prefix_cache_shares_blocks_copy_on_write():
    base = [7, 3, 9, 1, 4, 6, 2, 8, 5, 11, 13, 17, 19, 23, 29, 27, 25]
    eng = paged_engine(prefix_cache=4, kv_blocks=64)
    try:
        cold = eng.generate(base, max_new_tokens=6, timeout=120)
        live_after_cold = eng._pool.live_blocks()
        assert live_after_cold > 0      # the entry retains its blocks
        hit = eng.generate(base, max_new_tokens=6, timeout=120)
        ext = eng.generate(base + [31, 37], max_new_tokens=6, timeout=120)
        assert eng.stats["prefix_hits"] >= 2
        assert eng.stats["prefix_tokens_reused"] >= len(base) + 16
    finally:
        eng.stop()
    assert hit == cold == reference_greedy(base, 6)
    assert ext == reference_greedy(base + [31, 37], 6)


def test_prefix_entry_blocks_survive_donor_stream_exit():
    """The cached prefix must stay valid after the stream that created
    it finishes and its private blocks are recycled — the refcount is
    what keeps the shared full blocks alive."""
    base = list(range(1, 18))
    eng = paged_engine(prefix_cache=8, kv_blocks=64)
    try:
        eng.generate(base, max_new_tokens=4, timeout=120)
        # churn the pool: unrelated streams recycle the donor's blocks
        for p in PROMPTS[:4]:
            eng.generate(p, max_new_tokens=6, timeout=120)
        got = eng.generate(base, max_new_tokens=9, timeout=120)
        assert eng.stats["prefix_hits"] >= 1
    finally:
        eng.stop()
    assert got == reference_greedy(base, 9)


# -- either order of the rows inside a block (PR 34) ----------------------


def _reference_with_logprobs(prompt, n_tokens, cfg, params, kv_codec=None):
    """``reference_greedy`` with each token's log-probability."""
    from nnstreamer_tpu.models.transformer import (
        build_decode_step,
        build_prefill,
    )

    prefill = jax.jit(build_prefill(cfg, kv_codec=kv_codec))
    decode = jax.jit(build_decode_step(cfg, kv_codec=kv_codec))
    logits, cache = prefill(
        params, jnp.asarray(np.asarray(prompt, np.int32)[None]))
    toks, lps = [], []
    for i in range(n_tokens):
        lp = jax.nn.log_softmax(logits[0].astype(jnp.float32))
        toks.append(int(jnp.argmax(lp)))
        lps.append(float(lp[toks[-1]]))
        logits, cache = decode(
            params, jnp.asarray(toks[-1:], jnp.int32), cache,
            jnp.asarray(len(prompt) + i, jnp.int32))
    return toks, lps


@pytest.mark.parametrize("codec", [None, "int8"], ids=["raw", "int8"])
@pytest.mark.parametrize("heads", [1, 2, 4, 8],
                         ids=["heads1_major", "heads2_major", "heads4_major",
                              "heads8_token_major"])
def test_engine_serves_the_contiguous_reference_in_either_order(heads,
                                                                codec):
    """Prefill scatter, decode writes across block edges, the shared
    prefix's blocks, its copy-on-write tail and the prefix-extension
    program: tokens AND log-probabilities of the contiguous-cache
    reference, whichever way the arena orders a block's rows."""
    from nnstreamer_tpu.models.transformer import init_params

    cfg = dataclasses.replace(CFG, n_heads=heads)
    params = init_params(cfg, seed=3)
    base = [7, 3, 9, 1, 4, 6, 2, 8, 5, 11, 13, 17, 19, 23, 29, 27, 25]
    prompts = [base, base + [31, 37], PROMPTS[0], PROMPTS[6]]
    eng = ContinuousBatchingEngine(
        cfg, params, max_streams=3, steps_per_dispatch=4, temperature=0.0,
        block_tokens=T, prefix_cache=4, kv_blocks=64,
        kv_quant=codec).start()
    try:
        assert eng._pool.heads_major == (heads < 8)
        assert eng._pool.snapshot()["heads_major"] == int(heads < 8)
        got = []
        for p in prompts:
            stream = eng.submit(p, max_new_tokens=11)
            got.append((stream.result(timeout=240), list(stream.logprobs)))
        assert eng.stats["prefix_hits"] >= 1
    finally:
        eng.stop()
    for p, (toks, lps) in zip(prompts, got):
        want_toks, want_lps = _reference_with_logprobs(p, 11, cfg, params,
                                                       kv_codec=codec)
        assert toks == want_toks, f"prompt={p}"
        # a prefix hit attends over STORED keys and values where the
        # reference's prefill attends over fresh ones: int8 rounds them
        np.testing.assert_allclose(lps, want_lps,
                                   atol=2e-5 if codec is None else 2e-3)
    assert_zero_block_is_zero(eng)


# -- satellite 4: paging x int8 x mesh=dp2 --------------------------------


def test_paged_int8_dp2_mesh_matches_single_device():
    from nnstreamer_tpu.parallel.mesh import make_mesh

    mono = ContinuousBatchingEngine(
        CFG, PARAMS, max_streams=2, steps_per_dispatch=4,
        temperature=0.0, kv_quant="int8").start()
    try:
        want = [mono.generate(p, max_new_tokens=8, timeout=240)
                for p in PROMPTS[:3]]
    finally:
        mono.stop()

    mesh = make_mesh([("dp", 2)])
    eng = ContinuousBatchingEngine(
        CFG, PARAMS, max_streams=2, steps_per_dispatch=4,
        temperature=0.0, kv_quant="int8", block_tokens=T,
        mesh=mesh).start()
    try:
        # the arena (incl. zero block) divides over dp ranks
        assert eng._pool.ntot % 2 == 0
        got = [eng.generate(p, max_new_tokens=8, timeout=240)
               for p in PROMPTS[:3]]
        streams = [eng.submit(p, max_new_tokens=8) for p in PROMPTS[:3]]
        conc = [s.result(timeout=240) for s in streams]
    finally:
        eng.stop()
    assert got == want
    assert conc == want


# -- the arena's addressing: one buffer, the layer an index (PR 26) -------

CODECS = pytest.mark.parametrize("codec", [None, "int8"], ids=["raw", "int8"])


def _toy(heads=CFG.n_heads):
    """3 layers (so an index off by one lands on a real layer or off the
    end), 3 lanes, the last one EMPTY: its table is all sentinel.
    ``heads`` under 8 make the arena heads-major."""
    from nnstreamer_tpu.models.transformer import init_params

    cfg = dataclasses.replace(CFG, n_layers=3, max_seq=32, n_heads=heads)
    mb = cfg.max_seq // T
    nb = 3 * mb                                   # ntot 13, sentinel 13
    bt = np.full((3, mb), nb + 1, np.int32)
    bt[0], bt[1] = [7, 2, 9, 4], [0, 11, 5, 3]    # scrambled on purpose
    return cfg, init_params(cfg, seed=5), bt, nb


def _assert_pool_is_the_monolithic_cache(arena, cache, bt, nb,
                                         heads_major=False):
    """Every layer of the pool, the LAST included, holds what the
    monolithic cache holds for the live lanes, slot for slot; every block
    no live lane owns — the zero block first — is still zero."""
    live = sorted(set(bt[:2].ravel()))
    rest = [i for i in range(nb + 1) if i not in live]
    assert nb in rest
    for leaf, mono in zip(jax.tree.leaves(arena), jax.tree.leaves(cache)):
        leaf, mono = np.asarray(leaf), np.asarray(mono)
        if heads_major:                  # [L,NTOT,2,h,T,..] as [..,T,h,..]
            leaf = np.swapaxes(leaf, 3, 4)
        assert mono[-1].any(), "the last layer wrote nothing to compare"
        for layer in range(leaf.shape[0]):
            for lane in (0, 1):
                blocks = leaf[layer][bt[lane]]         # [MB,2,T,...]
                view = np.moveaxis(blocks, 1, 0)       # [2,MB,T,...]
                view = view.reshape((2, -1) + view.shape[3:])
                np.testing.assert_array_equal(
                    view, mono[layer, :, lane], f"layer {layer} lane {lane}")
        assert not leaf[:, rest].any()


@CODECS
@pytest.mark.parametrize("builder", ["step", "chunk"])
@pytest.mark.parametrize("heads", [4, 1, 2, 8],
                         ids=["", "heads1_major", "heads2_major",
                              "heads8_token_major"])
def test_paged_builders_are_bit_identical_to_the_monolithic_ones(
        builder, codec, heads):
    from nnstreamer_tpu.models.transformer import (
        _kv_codec,
        build_chunk_decode,
        build_decode_step,
        build_paged_chunk,
        build_paged_decode_step,
        init_cache,
    )

    cfg, params, bt, nb = _toy(heads)
    made = _kv_codec(cfg, codec)
    assert made.heads_major == (heads < 8)
    arena = made.paged_init(
        cfg.n_layers, nb + 1, T, cfg.n_heads, cfg.head_dim)
    for leaf in jax.tree.leaves(arena):
        assert leaf.shape[3:5] == ((heads, T) if heads < 8 else (T, heads))
    cache = init_cache(cfg, 3, kv_codec=codec)
    tables = jnp.asarray(bt)
    rng = np.random.default_rng(11)
    if builder == "step":
        paged = jax.jit(build_paged_decode_step(cfg, T, kv_codec=codec))
        mono = jax.jit(build_decode_step(cfg, kv_codec=codec))
        for i in range(10):           # lanes 0 and 1 cross a block edge
            tok = jnp.asarray(rng.integers(1, cfg.vocab, 3), jnp.int32)
            pos = jnp.asarray([i, i + 3, i + 1], jnp.int32)
            got, arena = paged(params, tok, arena, tables, pos)
            want, cache = mono(params, tok, cache, pos)
            np.testing.assert_array_equal(got[:2], want[:2], f"step {i}")
            assert np.isfinite(np.asarray(got[2])).all()
    else:
        paged = jax.jit(build_paged_chunk(cfg, T, kv_codec=codec))
        mono = jax.jit(build_chunk_decode(cfg, kv_codec=codec))
        for i in range(2):
            toks = jnp.asarray(rng.integers(1, cfg.vocab, (3, 5)), jnp.int32)
            pos0 = jnp.asarray([5 * i, 5 * i + 3, 5 * i + 1], jnp.int32)
            got, arena = paged(params, toks, arena, tables, pos0,
                               jnp.full((3,), 5, jnp.int32))
            want, cache = mono(params, toks, cache, pos0)
            np.testing.assert_array_equal(got[:2], want[:2], f"chunk {i}")
    _assert_pool_is_the_monolithic_cache(arena, cache, bt, nb,
                                         made.heads_major)


def _scans(jaxpr):
    """Every ``scan`` equation of a jaxpr, nested ones included."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _scans(sub)


@CODECS
def test_arena_is_a_carry_of_both_scans_and_is_updated_in_place(codec):
    """What keeps a later edit from putting the slices back: no scan of
    the decode step or of the K-step dispatch takes the arena as ``xs`` or
    returns it as ``ys`` (each layer's 1/L of the pool sliced out and
    written back every step, and a second pool: PERF.md PR 26); it is a
    carry of both, and the donated buffer is the one that comes back."""
    eng = ContinuousBatchingEngine(
        CFG, PARAMS, max_streams=3, steps_per_dispatch=4, temperature=0.0,
        block_tokens=T, kv_quant=codec)
    arena = eng._pool.arena
    pool_shapes = {leaf.shape for leaf in jax.tree.leaves(arena)}
    bt = np.full((eng.B, eng.MB), eng._pool.SENTINEL, np.int32)
    bt[0, :2], bt[1, :2] = [3, 1], [0, 2]         # lane 2 stays empty
    tok = jnp.asarray([5, 9, 0], jnp.int32)
    pos = jnp.asarray([2, 6, 0], jnp.int32)
    keys = jnp.zeros((eng.B, 2), jnp.uint32)
    step = jax.make_jaxpr(eng._paged_decode)(
        eng.params, tok, arena, jnp.asarray(bt), pos)
    dispatch = jax.make_jaxpr(eng._build_dispatch(eng.K))(
        eng.params, tok, arena, jnp.asarray(bt), pos, keys)
    for jaxpr, n_scans in ((step, 1), (dispatch, 2)):
        scans = list(_scans(jaxpr.jaxpr))
        assert len(scans) == n_scans
        for eqn in scans:
            nc, nk = eqn.params["num_consts"], eqn.params["num_carry"]
            carried = {v.aval.shape for v in eqn.invars[nc:nc + nk]}
            sliced = {v.aval.shape for v in eqn.invars[nc + nk:]} \
                | {v.aval.shape for v in eqn.outvars[nk:]}
            assert pool_shapes <= carried
            assert not pool_shapes & sliced
    out = eng._dispatch(eng.params, tok, arena, jnp.asarray(bt), pos, keys)
    assert all(leaf.is_deleted() for leaf in jax.tree.leaves(arena))
    for leaf in jax.tree.leaves(out[2]):
        leaf = np.asarray(leaf)
        # the empty lane wrote nowhere and the zero block is still zero
        assert leaf[:, [0, 1, 2, 3]].any()
        assert not leaf[:, 4:].any()


# -- a pool of three kinds: full blocks, window blocks, lane state ---------

def _three_kinds_pool(mesh=None):
    from nnstreamer_tpu.models.sambay import SambaYConfig
    from nnstreamer_tpu.serving import kvpool

    cfg = SambaYConfig(
        vocab=64, d_model=32, n_layers=8, n_heads=8, n_kv_heads=4,
        head_dim=8, window=8, d_ff=48, ssm_inner=64, ssm_state=16,
        dt_rank=2, max_seq=32, dtype=jnp.float32, param_dtype=jnp.float32)
    return cfg, kvpool.BlockPool(cfg, 6, 4, lanes=2, window_blocks=5,
                                 mesh=mesh)


def test_a_pool_of_three_kinds_allocs_scatters_and_releases():
    """A family that states ``kv_window`` AND ``lane_state``: one arena
    pytree ``{"kv", "win", "state"}``, ONE scatter for a prefill's three
    kinds, each kind's bookkeeping its own."""
    cfg, pool = _three_kinds_pool()
    assert set(pool.arena) == {"kv", "win", "state"}
    assert pool.heads_major                       # 2 pairs: heads-major
    assert pool.arena["kv"].shape == (1, 7, 2, 2, 4, 16)
    assert pool.arena["win"].shape == (2, 6, 2, 2, 4, 16)
    assert pool.arena["state"]["ssm"].shape == (3, 2, 1, 16, 64)
    assert pool.arena["state"]["conv"].shape == (3, 2, 3, 64)
    snap = pool.snapshot()
    assert (snap["num_blocks"], snap["window_blocks"], snap["state_slots"]) \
        == (6, 5, 2)
    assert snap["nbytes"] == sum(
        int(a.size) * 4 for a in jax.tree_util.tree_leaves(pool.arena))
    assert snap["window_bytes"] + snap["state_bytes"] < snap["nbytes"]
    # a prompt of 11 tokens in a bucket of 16: 3 full blocks, and of the
    # window layers' the last two (the first lies behind the window)
    rng = np.random.default_rng(0)
    cache = {
        "kv": jnp.asarray(rng.standard_normal((1, 2, 1, 16, 2, 16)),
                          jnp.float32),
        "win": jnp.asarray(rng.standard_normal((2, 2, 1, 16, 2, 16)),
                           jnp.float32),
        "state": {"ssm": jnp.asarray(rng.standard_normal((3, 1, 1, 16, 64)),
                                     jnp.float32),
                  "conv": jnp.asarray(rng.standard_normal((3, 1, 3, 64)),
                                      jnp.float32)}}
    blocks, wblocks, lane = pool.alloc(3), pool.win.alloc(2), pool.alloc_lane()
    other = pool.alloc_lane()
    assert (lane, other) == (0, 1) and pool.alloc_lane() is None
    before = {k: np.asarray(v) for k, v in pool.arena["state"].items()}
    pool.scatter_prefill(cache, blocks, lane=other, window_ids=wblocks,
                         window_first=1)
    np.testing.assert_array_equal(pool.stream_rows(blocks, 11),
                                  np.asarray(cache["kv"][:, :, 0, :11]))
    np.testing.assert_array_equal(
        pool.stream_rows(wblocks, 7, window=True),
        np.asarray(cache["win"][:, :, 0, 4:11]))
    got = pool.lane_state(other)
    for name in ("ssm", "conv"):
        np.testing.assert_array_equal(got[name],
                                      np.asarray(cache["state"][name][:, 0]))
        # the other lane's slot is what it was
        np.testing.assert_array_equal(pool.lane_state(lane)[name],
                                      before[name][:, lane])
    # every block no one holds is still zero, in both arenas
    held = set(blocks)
    for i in range(pool.ntot):
        if i not in held:
            assert not np.asarray(pool.arena["kv"][:, i]).any()
    for i in set(range(pool.win.ntot)) - set(wblocks):
        assert not np.asarray(pool.arena["win"][:, i]).any()
    snap = pool.snapshot()
    assert (snap["live_blocks"], snap["window_blocks_live"],
            snap["state_slots_live"]) == (3, 2, 2)
    pool.release(blocks)
    pool.win.release(wblocks)
    pool.release_lane(lane)
    pool.release_lane(other)
    snap = pool.snapshot()
    assert (snap["live_blocks"], snap["window_blocks_live"],
            snap["state_slots_live"], snap["free_blocks"]) == (0, 0, 0, 6)
    pool.reset()
    assert not any(np.asarray(a).any()
                   for a in jax.tree_util.tree_leaves(pool.arena))


def test_a_window_arena_still_refuses_a_mesh():
    from jax.sharding import Mesh

    mesh = Mesh(np.array(jax.devices()[:2]), ("dp",))
    with pytest.raises(ValueError, match="mesh"):
        _three_kinds_pool(mesh=mesh)
