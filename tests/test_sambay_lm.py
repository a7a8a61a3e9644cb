"""The decoder-hybrid-decoder (models/sambay.py) at a small size, float32,
seeded weights: the program against the plain reference
(benchmark/reference_sambay.py), and the family through
``ContinuousBatchingEngine`` with its three kinds of lane memory.

8 layers: 2 x [Mamba, window], [Mamba with memory, full], 1 x [GMU, cross].
Window 8, 4 tokens a block, 2 steps a dispatch: a prompt crosses the window
within a few dispatches and the window's first slot moves through a block.

Tolerances: everything here is float32. The program and the reference sum
in different orders (a chunked associative scan against a scan over time,
blocks of a paged cache against one softmax, a pair's two halves in one
product), which costs a few float32 roundings of numbers of size 1 to 10:
1e-4 holds every comparison of log-probabilities; a wrong model reads 1e-2
or more.
"""

import dataclasses
import functools

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from benchmark import controls_sambay as controls  # noqa: E402
from benchmark import reference_sambay as ref  # noqa: E402
from nnstreamer_tpu.models import sambay  # noqa: E402
from nnstreamer_tpu.models.sambay import SambaYConfig  # noqa: E402
from nnstreamer_tpu.serving import ContinuousBatchingEngine  # noqa: E402

pytestmark = pytest.mark.filterwarnings("ignore::DeprecationWarning")

W, T, K = 8, 4, 2
CFG = SambaYConfig(
    vocab=211, d_model=64, n_layers=8, n_heads=16, n_kv_heads=8, head_dim=8,
    window=W, d_ff=96, ssm_inner=128, ssm_state=16, ssm_conv=4, dt_rank=4,
    ssm_blocks=2, ssm_chunk=4, max_seq=64, dtype=jnp.float32,
    param_dtype=jnp.float32)
#: the same with 8 key-value pairs: blocks token-major, as the published
#: configuration's 10 are
TOKEN_MAJOR = dataclasses.replace(CFG, n_heads=32, n_kv_heads=16, head_dim=4)
TOL = 1e-4
MBW = -(-(W + K) // T) + 1


def _params(cfg=CFG, seed=5):
    """Seeded weights with norm scales that differ from one another (ones
    could not tell a norm from its neighbour), ``D`` likewise, and
    embeddings, projections and lambda vectors large enough for attention
    to be sharp and for each mixer to matter in 64 dims (at normal x 0.02
    both softmaxes of a pair are flat, and the pair norm hides lambda)."""
    params = cfg.family.init_params(cfg, seed=seed)
    rng = np.random.default_rng(seed)

    def scale(leaf):
        return jnp.asarray(rng.uniform(0.5, 1.5, leaf.shape), jnp.float32)

    for lp in params["layers"]:
        for name in ("ln1", "ln2", "sub_norm", "D"):
            if name in lp:
                lp[name] = scale(lp[name])
        for name in ("lam_q1", "lam_k1", "lam_q2", "lam_k2"):
            if name in lp:
                lp[name] = lp[name] * 3
        for name, by in (("ssm_in", 3), ("x_proj", 3), ("dt_proj", 3),
                         ("gmu_in", 5), ("wqkv", 10),
                         ("wq", 10), ("wo", 2)):
            if name in lp:
                lp[name] = lp[name] * by
    params["ln_f"] = scale(params["ln_f"])
    params["embed"] = params["embed"] * 8
    return params


PARAMS = _params()


def _prompt(n, seed=0):
    return np.random.default_rng([seed, n]).integers(
        1, CFG.vocab, n).astype(np.int32)


def _engine(cfg=CFG, params=PARAMS, **kw):
    kw.setdefault("max_streams", 3)
    return ContinuousBatchingEngine(
        cfg, params, steps_per_dispatch=K, temperature=0.0, block_tokens=T,
        min_bucket=8, **kw)


#: positions the one compiled reference takes, and log-probabilities it
#: gives back: every test's sequence is padded to the first (causal: what
#: follows a position does not matter to it) and reads the first ``count``
#: of the second; the wrong models' switches are traced, so the reference
#: compiles once a configuration
PAD, COUNT = 128, 45


@functools.lru_cache(maxsize=None)
def _reference(cfg):
    return jax.jit(lambda params, tokens, first, stop, wrong:
                   ref.sambay_check(params, tokens, first, COUNT, stop, cfg,
                                    **wrong))


def _ref_check(tokens, first, count, stop=0, cfg=CFG, params=PARAMS,
               **wrong):
    """``ref.sambay_check`` over ``tokens``: ``(logprobs [count, vocab],
    left)``, the rows of ``left`` cut to the tokens given."""
    n = len(tokens)
    padded = np.zeros(PAD, np.int32)
    padded[:n] = tokens
    lp, left = _reference(cfg)(
        params, jnp.asarray(padded), first, stop,
        {name: name in wrong for name in ref.WRONG})
    left = {k: np.asarray(v) for k, v in left.items()}
    for name in ("kv", "win"):
        left[name] = left[name][:, :, :n]
    return np.asarray(lp)[:count], left


def _ref_logprobs(tokens, first, count, cfg=CFG, params=PARAMS, **wrong):
    return _ref_check(tokens, first, count, 0, cfg, params, **wrong)[0]


def _served_against_reference(stream, prompt, new, cfg=CFG, params=PARAMS,
                              **wrong):
    """The largest distance of a served token's reported log-probability
    from the reference's at that position, teacher-forced."""
    toks = np.asarray(stream.tokens[:new], np.int64)
    whole = np.concatenate([prompt, toks]).astype(np.int32)
    lp = _ref_logprobs(whole, len(prompt) - 1, new, cfg, params, **wrong)
    return np.abs(lp[np.arange(new), toks]
                  - np.asarray(stream.logprobs[:new])).max()


@pytest.fixture(scope="module")
def engine():
    """ONE engine of the default size for the tests that only serve: its
    programs compile once."""
    eng = _engine().start()
    yield eng
    eng.stop()


def _serve_on(eng, prompt, new):
    """``prompt`` served alone on the idle ``eng``: the stream, and what
    its lane and its blocks are left with."""
    stream = eng.submit(prompt, max_new_tokens=new)
    stream.result(timeout=300)
    fed = len(prompt) + K * -(-(new - 1) // K)
    first, ids = stream.window_blocks
    return stream, {
        "state": eng._pool.lane_state(stream.lane), "fed": fed,
        "kv": eng._pool.stream_rows(stream.blocks, fed), "win_first": first,
        "win": eng._pool.stream_rows(ids, fed - first * T, window=True)}


def _serve(prompt, new, cfg=CFG, params=PARAMS, **kw):
    eng = _engine(cfg, params, **kw).start()
    try:
        stream, left = _serve_on(eng, prompt, new)
    finally:
        eng.stop()
    return eng, stream, left


def _forward_logprobs(toks, cfg=CFG, params=PARAMS):
    return np.asarray(jax.nn.log_softmax(jax.jit(sambay.build_forward(cfg))(
        params, jnp.asarray(toks[None]))[0]))


def _pairs(rows, cfg=CFG):
    """The reference's ``[.., kv heads, head_dim]`` as the pair entries the
    arena holds."""
    rows = np.asarray(rows)
    return rows.reshape(rows.shape[:-2] + (cfg.kv_pairs, cfg.pair_width))


def _tiles(states, cfg=CFG):
    """The reference's ``[layers, inner, state]`` as the lanes' tiles."""
    s = np.asarray(states).transpose(0, 2, 1)
    s = s.reshape(s.shape[:2] + (cfg.ssm_blocks, -1))
    return s.transpose(0, 2, 1, 3)


# -- the program against the reference ---------------------------------------

def test_the_layers_are_laid_out_by_the_published_rule():
    assert CFG.layer_types == (
        sambay.MAMBA, sambay.WINDOW, sambay.MAMBA, sambay.WINDOW,
        sambay.MAMBA, sambay.FULL, sambay.GMU, sambay.CROSS)
    full = SambaYConfig()
    kinds = full.layer_types
    assert kinds[16] == sambay.MAMBA and kinds[17] == sambay.FULL
    assert (full.ssm_layers, full.window_layers, full.cross_layers,
            kinds.count(sambay.GMU), kinds.count(sambay.FULL)) \
        == (9, 8, 7, 7, 1)
    assert [ref.layer_kind(i, 32) for i in range(32)] == [
        {"sliding_attention": "window", "full_attention": "full",
         "cross_attention": "cross"}.get(k, k) for k in kinds]
    assert full.family.kv_entry(full) == (1, 2, (10, 128))
    assert full.family.kv_window(full) == (8, 512)
    assert full.family.lane_state(full)["ssm"] == ((1, 16, 5120),
                                                   jnp.float32)


@pytest.mark.parametrize("cfg", [CFG, TOKEN_MAJOR], ids=["heads_major",
                                                         "token_major"])
def test_forward_equals_the_reference_at_every_position(cfg):
    params = PARAMS if cfg is CFG else _params(cfg)
    toks = _prompt(45)
    got = _forward_logprobs(toks, cfg, params)
    assert np.abs(got - _ref_logprobs(toks, 0, 45, cfg, params)).max() < TOL


@pytest.mark.parametrize("wrong", ref.WRONG)
def test_the_tolerance_tells_each_wrong_model_from_the_right_one(wrong):
    """Each control of the cell's check that is a wrong reference
    (``benchmark/controls_sambay.py``), those the chip cannot tell at
    window 512 among them: this test is what holds those."""
    toks = _prompt(45)
    got = _forward_logprobs(toks)
    assert np.abs(got - _ref_logprobs(toks, 0, 45, **{wrong: True})).max() \
        > 100 * TOL


@pytest.mark.parametrize("n,bucket", [(5, 8), (16, 16), (37, 64)])
def test_two_stage_prefill_hands_over_what_every_layer_everywhere_leaves(
        n, bucket):
    """The prefill runs the cross-decoder for ONE row a prompt: its logits
    are the last real token's of every layer at every position (the
    program's own forward, and the reference's), and rows, states and tails
    are the reference's after ``n`` tokens, whatever the padding."""
    toks = _prompt(n)
    padded = np.zeros((1, bucket), np.int32)
    padded[0, :n] = toks
    logits, cache = jax.jit(sambay.build_prefill(CFG))(
        PARAMS, jnp.asarray(padded), lengths=jnp.asarray([n], jnp.int32))
    assert cache["kv"].shape == (1, 2, 1, bucket, 4, 16)
    assert cache["win"].shape == (2, 2, 1, bucket, 4, 16)
    assert cache["state"]["ssm"].shape == (3, 1, 2, 16, 64)
    assert cache["state"]["conv"].shape == (3, 1, 3, 128)
    got = np.asarray(jax.nn.log_softmax(logits[0]))
    assert np.abs(got - _forward_logprobs(toks)[n - 1]).max() < TOL
    lp, want = _ref_check(toks, n - 1, 1, n)
    assert np.abs(got - lp[0]).max() < TOL
    for name in ("kv", "win"):
        assert np.abs(np.asarray(cache[name][:, :, 0, :n])
                      - _pairs(want[name])).max() < TOL
    assert np.abs(np.asarray(cache["state"]["ssm"][:, 0])
                  - _tiles(want["ssm"])).max() < TOL
    assert np.abs(np.asarray(cache["state"]["conv"][:, 0])
                  - np.asarray(want["conv"])).max() < TOL


def test_the_chunked_scan_is_the_scan_over_time():
    rng = np.random.default_rng(3)
    b, s, c, n = 2, 19, 24, 5
    x, dt = (jnp.asarray(rng.standard_normal((b, s, c)), jnp.float32)
             for _ in range(2))
    dt = jax.nn.softplus(dt).at[1, 13:].set(0.0)     # a row of 13 tokens
    a = -jnp.exp(jnp.asarray(rng.standard_normal((n, c)), jnp.float32))
    bm, cm = (jnp.asarray(rng.standard_normal((b, s, n)), jnp.float32)
              for _ in range(2))
    y, state = sambay.selective_scan_chunked(x, dt, a, bm, cm, 4)
    want = np.zeros((b, n, c), np.float32)
    for t in range(s):
        want = np.exp(np.asarray(dt)[:, t, None, :] * np.asarray(a)) * want \
            + np.asarray(bm)[:, t, :, None] \
            * np.asarray(x * dt)[:, t, None, :]
        np.testing.assert_allclose(
            np.asarray(y[:, t]),
            (want * np.asarray(cm)[:, t, :, None]).sum(1), atol=1e-4)
        if t == 12:
            at_13 = want[1].copy()
    np.testing.assert_allclose(np.asarray(state), want, atol=1e-4)
    np.testing.assert_allclose(np.asarray(state[1]), at_13, atol=1e-4)


# -- through the engine ------------------------------------------------------

@pytest.mark.parametrize("n,new", [(5, 21), (6, 9), (13, 17), (16, 8),
                                   (37, 11)])
def test_served_tokens_equal_the_reference(engine, n, new):
    """A prompt shorter than the window that crosses it while decoding (5,
    6), one longer than it at admission (13, 37), one that ends on a
    block's boundary (16); the other lanes idle."""
    prompt = _prompt(n)
    stream, _ = _serve_on(engine, prompt, new)
    assert stream.finish_reason == "length" and len(stream.tokens) == new
    assert _served_against_reference(stream, prompt, new) < TOL
    if n + new > W + 1:
        assert _served_against_reference(
            stream, prompt, new, window_off_by_one=True) > 100 * TOL


def test_served_tokens_equal_the_reference_token_major():
    params = _params(TOKEN_MAJOR)
    prompt = _prompt(13)
    eng, stream, _ = _serve(prompt, 17, TOKEN_MAJOR, params)
    assert not eng._pool.heads_major
    assert _served_against_reference(stream, prompt, 17, TOKEN_MAJOR,
                                     params) < TOL


def test_what_a_lane_is_left_with_is_the_references(engine):
    """Served alone on an idle engine: the one full layer's blocks hold the
    whole context, a window layer's exactly the positions the next token
    may read, the lane's slot the three states and tails after the same
    tokens."""
    n, new = 21, 12
    prompt = _prompt(n, seed=2)
    stream, left = _serve_on(engine, prompt, new)
    fed, full, first, win, state = (left[k] for k in (
        "fed", "kv", "win_first", "win", "state"))
    whole = np.concatenate([prompt, stream.tokens]).astype(np.int32)
    _, want = _ref_check(whole[:fed], 0, 1, fed)
    assert full.shape[0] == 1       # ONE layer's blocks, read by two
    assert np.abs(full - _pairs(want["kv"])).max() < TOL
    assert first == (fed - W + 1) // T
    assert np.abs(win - _pairs(want["win"])[:, :, first * T:]).max() < TOL
    assert np.abs(state["ssm"] - _tiles(want["ssm"])).max() < TOL
    assert np.abs(state["conv"] - np.asarray(want["conv"])).max() < TOL


def test_window_blocks_go_back_and_the_full_layers_do_not():
    """After every dispatch a lane holds no window block wholly before
    ``pos - W + 1`` and at most ``ceil((W + K) / T) + 1``, and every block
    of the full arena it was ever given; a lane's slot is claimed for as
    long."""
    eng = _engine(max_streams=2)
    seen = []
    real = eng._decode_step_paged

    def watched():
        real()
        win = eng._pool.win
        with win._lock:
            free = set(win._free)
        for st in eng._sstate.values():
            held = st["wblocks"]
            seen.append(len(held))
            assert st["wfirst"] == max(0, st["pos"] - W + 1) // T
            assert not free & set(held)
            assert len(st["blocks"]) >= (st["pos"] - 1) // T + 1
            assert st["slot"] in eng._pool._lane_live
        live = sum(len(st["wblocks"]) for st in eng._sstate.values())
        assert win.live_blocks() == live
        assert eng._pool.live_blocks() == sum(
            len(st["blocks"]) for st in eng._sstate.values())

    eng._decode_step_paged = watched
    eng.start()
    try:
        streams = [eng.submit(_prompt(n), max_new_tokens=new)
                   for n, new in ((5, 40), (30, 25), (11, 33))]
        for s in streams:
            s.result(timeout=300)
    finally:
        eng.stop()
    assert MBW == 4 and seen and max(seen) <= MBW
    assert eng._pool.win.num_blocks == 2 * MBW
    assert eng.stats["kv_window_blocks_released"] > 0
    assert eng.stats["kv_window_blocks_live"] < eng.stats["kv_blocks_live"]
    assert eng._pool.win.live_blocks() == eng._pool.live_blocks() == 0
    snap = eng._pool.snapshot()
    assert snap["window_blocks"] == 2 * MBW and snap["window_blocks_live"] == 0
    assert snap["state_slots"] == 2 and snap["state_slots_live"] == 0
    assert snap["nbytes"] == sum(int(a.size) * 4 for a in
                                 jax.tree_util.tree_leaves(eng._pool.arena))
    for s, (n, new) in zip(streams, ((5, 40), (30, 25), (11, 33))):
        assert _served_against_reference(s, _prompt(n), new) < TOL


def test_streams_share_the_lanes_and_each_equals_the_reference(engine):
    """Five streams over three lanes: busy neighbours, slots and blocks that
    another request used before."""
    work = [(_prompt(n, seed=7), new)
            for n, new in ((9, 14), (20, 10), (3, 19), (33, 8), (12, 12))]
    streams = [engine.submit(p, max_new_tokens=new) for p, new in work]
    for s in streams:
        s.result(timeout=300)
    for (prompt, new), s in zip(work, streams):
        assert _served_against_reference(s, prompt, new) < TOL


@pytest.mark.parametrize("starved", ["kv_window_blocks", "kv_blocks"])
def test_exhaustion_of_either_arena_defers_admission(starved):
    sizes = {"kv_blocks": 40, "kv_window_blocks": 40}
    sizes[starved] = MBW if starved == "kv_window_blocks" else 7
    eng = _engine(max_streams=2, **sizes).start()
    try:
        a = eng.submit(_prompt(14), max_new_tokens=9)
        b = eng.submit(_prompt(17, seed=1), max_new_tokens=9)
        a.result(timeout=300)
        b.result(timeout=300)
    finally:
        eng.stop()
    assert eng.stats["kv_defers"] > 0 and eng.stats["kv_sheds"] == 0
    assert b.admit_t >= a.finish_t
    assert _served_against_reference(b, _prompt(17, seed=1), 9) < TOL
    assert eng._pool.win.live_blocks() == eng._pool.live_blocks() == 0
    assert eng._pool.snapshot()["state_slots_live"] == 0


# -- the controls that are a wrong PROGRAM -----------------------------------

def _left_against_reference(left, prompt, stream):
    fed = left["fed"]
    whole = np.concatenate([prompt, stream.tokens]).astype(np.int32)
    _, want = _ref_check(whole[:fed], 0, 1, fed)

    def relative(mine, theirs):
        return np.linalg.norm(mine - theirs) / np.linalg.norm(theirs)

    return {"first_state": relative(left["state"]["ssm"][0],
                                    _tiles(want["ssm"])[0]),
            "rows": relative(left["kv"], _pairs(want["kv"]))}


@pytest.mark.parametrize("control,reading,least", [
    ("state_unchanged", "first_state", 1e-2),
    ("bf16_state", "first_state", 1e-4),
    ("int8_rows", "rows", 1e-3)])
def test_a_wrong_program_is_told_from_the_right_one(engine, control,
                                                    reading, least):
    """The controls that serve from a patched engine
    (``controls_sambay.PATCHED``): a state left alone for a step, a
    bfloat16 state, 8-bit rows: each moves the reading its limit is on far
    past what the right program reads."""
    prompt, new = _prompt(21, seed=2), 12
    stream, left = _serve_on(engine, prompt, new)
    right = _left_against_reference(left, prompt, stream)
    assert right[reading] < 1e-5
    cfg = controls.patched_config(CFG, control)
    with controls.patch(control)():
        _, stream, left = _serve(prompt, new, cfg)
    assert _left_against_reference(left, prompt, stream)[reading] > least


# -- what the family states and the engine reports ---------------------------

@pytest.mark.parametrize("option", [
    dict(prefix_cache=2), dict(speculate=2), dict(prefill_chunk=8),
    dict(kv_quant="int8")])
def test_options_the_family_does_not_bring_are_refused_by_name(option):
    with pytest.raises(ValueError) as err:
        _engine(**option)
    assert next(iter(option)) in str(err.value)
    assert "R3" in str(err.value) and "R4" in str(err.value)


def test_engine_reports_three_kinds_and_what_the_cross_layers_read():
    eng, _, _ = _serve(_prompt(13), 17)
    stats = eng.stats
    assert eng.state_update == stats["state_update"] == "reference"
    assert stats["decode_attention"] == eng.decode_attention == "gather"
    # one layer of each kind is what the block counters count; the cross
    # layers' reads are the full layer's, once a cross layer
    assert stats["kv_shared_reads"] == CFG.cross_layers \
        * stats["kv_blocks_live"] > 0
    assert 0 < stats["kv_window_blocks_live"] < stats["kv_blocks_live"]
    # one prompt in its bucket's one program: every row of it computed,
    # the rows that hold no prompt too
    rows = eng._prefill_rows(16)
    assert stats["prefills"] == stats["prefill_programs"] == 1
    assert (stats["prefill_rows_self"], stats["prefill_rows_cross"]) \
        == (stats["prefill_bucket_tokens"], rows) == (16 * rows, rows)
    arena = eng._pool.arena
    assert set(arena) == {"kv", "win", "state"}
    assert arena["kv"].shape[0] == 1 and arena["win"].shape[0] == 2
    assert arena["state"]["ssm"].shape == (3, 3, 2, 16, 64)
    # bytes a token: one layer's pair rows, and the window layers' two
    assert stats["kv_bytes_per_token"] == 2 * 4 * 16 * 4
    assert stats["kv_window_bytes_per_token"] == 2 * 2 * 4 * 16 * 4
