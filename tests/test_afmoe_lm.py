"""The window-and-full attention decoder (models/afmoe.py) at a small size,
float32, seeded weights: the program against the plain reference
(benchmark/reference_afmoe.py), and the family through
``ContinuousBatchingEngine`` with its two block arenas.

Window 8, 4 tokens a block, 2 steps a dispatch: a prompt crosses the window
within a few dispatches and the window's first slot moves through a block.

Tolerances: everything here is float32. The program and the reference sum
in different orders (blocks of a paged cache against one softmax, tiles
against a scan over experts), which costs a few float32 roundings of
numbers of size 1 to 10: 1e-4 holds every comparison of log-probabilities;
a wrong model reads 1e-2 or more.
"""

import dataclasses

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from benchmark import reference_afmoe as ref  # noqa: E402
from nnstreamer_tpu.models import afmoe, hybrid  # noqa: E402
from nnstreamer_tpu.models.afmoe import FULL, SLIDING, AfmoeConfig  # noqa: E402
from nnstreamer_tpu.serving import ContinuousBatchingEngine  # noqa: E402

pytestmark = pytest.mark.filterwarnings("ignore::DeprecationWarning")

W, T, K = 8, 4, 2
CFG = AfmoeConfig(
    vocab=211, d_model=64, layer_types=(SLIDING, SLIDING, FULL, SLIDING),
    num_dense_layers=1, n_heads=8, n_kv_heads=2, head_dim=16, window=W,
    dense_width=96, num_experts=16, experts_per_token=4, expert_width=32,
    shared_width=32, experts_held=(0, 4), max_seq=64, dtype=jnp.float32,
    param_dtype=jnp.float32)
TOL = 1e-4
MBW = -(-(W + K) // T) + 1


def _params(cfg=CFG, seed=5):
    """Seeded weights with norm scales that differ from one another (ones
    could not tell a norm from its neighbour) and a selection bias large
    enough to move choices."""
    params = cfg.family.init_params(cfg, seed=seed)
    rng = np.random.default_rng(seed)

    def scale(leaf):
        return jnp.asarray(rng.uniform(0.5, 1.5, leaf.shape), jnp.float32)

    for lp in params["layers"]:
        for name in ("ln1", "ln1_post", "ln2", "ln2_post", "q_norm",
                     "k_norm"):
            lp[name] = scale(lp[name])
        if "expert_bias" in lp:
            lp["expert_bias"] = lp["expert_bias"] * 10
    params["ln_f"] = scale(params["ln_f"])
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


def _ref_logprobs(tokens, first, count, cfg=CFG, params=PARAMS, **wrong):
    return np.asarray(ref.afmoe_logprobs(
        params, jnp.asarray(tokens), first, count, cfg, **wrong))


def _served_against_reference(stream, prompt, new, **wrong):
    """The largest distance of a served token's reported log-probability
    from the reference's at that position, teacher-forced."""
    toks = np.asarray(stream.tokens[:new], np.int64)
    whole = np.concatenate([prompt, toks]).astype(np.int32)
    lp = _ref_logprobs(whole, len(prompt) - 1, new, **wrong)
    return np.abs(lp[np.arange(new), toks]
                  - np.asarray(stream.logprobs[:new])).max()


# -- the program against the reference ---------------------------------------

def test_forward_equals_the_reference_at_every_position():
    toks = _prompt(45)
    got = np.asarray(jax.nn.log_softmax(jax.jit(afmoe.build_forward(CFG))(
        PARAMS, jnp.asarray(toks[None]))[0]))
    assert np.abs(got - _ref_logprobs(toks, 0, 45)).max() < TOL


@pytest.mark.parametrize("wrong", [
    "no_window", "half_window", "window_off_by_one", "rotary_on_full",
    "no_rotary_on_window", "no_gate", "softmax_scores", "no_route_norm",
    "no_route_scale", "no_post_norms", "no_embed_scale", "renormalise_held",
    "no_bias"])
def test_the_tolerance_tells_each_wrong_model_from_the_right_one(wrong):
    """Each control of the cell's check (``benchmark/controls_afmoe.py``),
    the two the chip cannot tell at window 4096 among them (a window off by
    ONE key, the selection bias left out): this test is what holds those."""
    toks = _prompt(45)
    got = np.asarray(jax.nn.log_softmax(jax.jit(afmoe.build_forward(CFG))(
        PARAMS, jnp.asarray(toks[None]))[0]))
    assert np.abs(got - _ref_logprobs(toks, 0, 45, **{wrong: True})).max() \
        > 100 * TOL


@pytest.mark.parametrize("n,bucket", [(5, 8), (16, 16), (37, 64)])
def test_padded_prefill_hands_over_the_last_real_tokens_logits_and_rows(
        n, bucket):
    toks = _prompt(n)
    padded = np.zeros((1, bucket), np.int32)
    padded[0, :n] = toks
    logits, cache = jax.jit(afmoe.build_prefill(CFG))(
        PARAMS, jnp.asarray(padded), lengths=jnp.asarray([n], jnp.int32))
    assert cache["kv"].shape == (1, 2, 1, bucket, 2, 16)
    assert cache["win"].shape == (3, 2, 1, bucket, 2, 16)
    lp, want = ref.afmoe_check(PARAMS, jnp.asarray(toks), n - 1, 1, 0, CFG)
    assert np.abs(np.asarray(jax.nn.log_softmax(logits[0]))
                  - np.asarray(lp[0])).max() < TOL
    for name in ("kv", "win"):
        assert np.abs(np.asarray(cache[name][:, :, 0, :n])
                      - np.asarray(want[name])).max() < TOL


# -- the expert layer --------------------------------------------------------

def _layer_inputs(seed=3, t=40):
    lp = next(lp for lp in PARAMS["layers"] if "router" in lp)
    h = jnp.asarray(np.random.default_rng(seed).standard_normal(
        (t, CFG.d_model)), jnp.float32)
    return h, lp


def test_the_selection_bias_moves_the_choice_and_not_the_gates():
    h, lp = _layer_inputs()
    whole = dataclasses.replace(CFG, experts_held=(0, 16))
    wide = {**lp, "w_in": jnp.tile(lp["w_in"], (4, 1, 1)),
            "w_out": jnp.tile(lp["w_out"], (4, 1, 1))}
    got, _ = hybrid.moe_ffn(h, wide, whole)
    assert np.abs(np.asarray(got) - np.asarray(ref.routed_experts(
        h, wide, whole))).max() < 1e-5
    # without the bias other experts are chosen: the layer's output moves
    assert np.abs(np.asarray(got) - np.asarray(ref.routed_experts(
        h, wide, whole, no_bias=True))).max() > 1e-3
    # a bias that moves NO choice (the same for every expert) moves nothing:
    # the gates are the chosen's own scores, the bias is no part of them
    shifted = {**wide, "expert_bias": wide["expert_bias"] + 3.0}
    again, _ = hybrid.moe_ffn(h, shifted, whole)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(again))
    # and the gates sum to route_scale over a token's chosen experts
    logits = h @ lp["router"]
    s = jax.nn.sigmoid(logits)
    _, choice = jax.lax.top_k(s + lp["expert_bias"], 4)
    gates = jnp.take_along_axis(s, choice, -1)
    gates = gates / gates.sum(-1, keepdims=True) * CFG.routed_scaling_factor
    np.testing.assert_allclose(np.asarray(gates.sum(-1)),
                               CFG.routed_scaling_factor, rtol=1e-5)


def test_the_shares_add_up_to_the_uncut_layer():
    """Sixteen experts over four shares of four: the routed parts that the
    shares give, plus the shared expert counted ONCE, are what the uncut
    reference gives for the whole layer."""
    h, lp = _layer_inputs()
    rng = np.random.default_rng(11)
    w_in = jnp.asarray(rng.standard_normal((16, 64, 64)) * 0.05, jnp.float32)
    w_out = jnp.asarray(rng.standard_normal((16, 32, 64)) * 0.05,
                        jnp.float32)
    whole = dataclasses.replace(CFG, experts_held=(0, 16))
    shared = ref._gated(h, lp["shared_in"], lp["shared_out"])
    want = ref.routed_experts(h, {**lp, "w_in": w_in, "w_out": w_out},
                              whole) + shared
    total = shared
    for lo in range(0, 16, 4):
        share = dataclasses.replace(CFG, experts_held=(lo, lo + 4))
        part, counts = hybrid.moe_ffn(
            h, {**lp, "w_in": w_in[lo:lo + 4], "w_out": w_out[lo:lo + 4]},
            share)
        assert int(counts["moe_tokens_held"]) \
            + int(counts["moe_tokens_absent"]) == 40 * 4
        total = total + part
    assert np.abs(np.asarray(total) - np.asarray(want)).max() < 1e-5


# -- through the engine ------------------------------------------------------

@pytest.mark.parametrize("n,new", [(5, 21), (6, 9), (13, 17), (37, 11)])
def test_served_tokens_equal_the_reference(n, new):
    """A prompt shorter than the window that crosses it while decoding (5,
    6), one longer than it at admission (13, 37): log-probabilities, and
    that the nearest wrong window is told apart."""
    eng = _engine().start()
    try:
        prompt = _prompt(n)
        stream = eng.submit(prompt, max_new_tokens=new)
        stream.result(timeout=300)
    finally:
        eng.stop()
    assert stream.finish_reason == "length" and len(stream.tokens) == new
    assert _served_against_reference(stream, prompt, new) < TOL
    if n + new > W + 1:
        assert _served_against_reference(
            stream, prompt, new, window_off_by_one=True) > 100 * TOL


def test_rows_of_both_arenas_are_the_references():
    """Served alone on an idle engine: the full layer's blocks hold the
    whole context, a window layer's exactly the positions the next token
    may read (and the rest of the oldest block still held)."""
    n, new = 21, 12
    eng = _engine().start()
    try:
        prompt = _prompt(n, seed=2)
        stream = eng.submit(prompt, max_new_tokens=new)
        stream.result(timeout=300)
        fed = n + K * -(-(new - 1) // K)
        full = eng._pool.stream_rows(stream.blocks, fed)
        first, ids = stream.window_blocks
        win = eng._pool.stream_rows(ids, fed - first * T, window=True)
    finally:
        eng.stop()
    whole = np.concatenate([prompt, stream.tokens]).astype(np.int32)
    _, want = ref.afmoe_check(PARAMS, jnp.asarray(whole[:fed]), 0, 1, 0, CFG)
    assert np.abs(full - np.asarray(want["kv"])).max() < TOL
    # the next token, at position fed, may read fed - W + 1 .. fed
    assert first == (fed - W + 1) // T
    assert first * T <= fed - W + 1 < (first + 1) * T
    assert np.abs(win - np.asarray(want["win"])[:, :, first * T:]).max() < TOL


def test_a_lane_never_holds_more_window_blocks_than_the_bound():
    """After every dispatch a window layer's lane holds no block wholly
    before ``pos - W + 1`` and at most ``ceil((W + K) / T) + 1``; every
    block given back is in the window arena's free list, and none of the
    full arena's is touched."""
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
        live = sum(len(st["wblocks"]) for st in eng._sstate.values())
        assert win.live_blocks() == live
        assert len(free) == win.num_blocks - live

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
    assert eng._pool.win.free_blocks == eng._pool.win.num_blocks
    snap = eng._pool.snapshot()
    assert snap["window_blocks"] == 2 * MBW and snap["window_blocks_live"] == 0
    assert snap["nbytes"] == sum(int(a.size) * 4 for a in
                                 jax.tree_util.tree_leaves(eng._pool.arena))


@pytest.mark.parametrize("starved", ["kv_window_blocks", "kv_blocks"])
def test_exhaustion_of_either_arena_defers_admission(starved):
    """One lane's worth of blocks in one arena, plenty in the other: the
    second request waits for the first to finish, then is served right."""
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
    assert a.finish_reason == b.finish_reason == "length"
    assert b.admit_t >= a.finish_t
    assert _served_against_reference(b, _prompt(17, seed=1), 9) < TOL
    assert eng._pool.win.live_blocks() == eng._pool.live_blocks() == 0


def test_streams_share_the_lanes_and_each_equals_the_reference():
    eng = _engine(max_streams=3).start()
    try:
        work = [(_prompt(n, seed=7), new)
                for n, new in ((9, 14), (20, 10), (3, 19), (33, 8), (12, 12))]
        streams = [eng.submit(p, max_new_tokens=new) for p, new in work]
        for s in streams:
            s.result(timeout=300)
    finally:
        eng.stop()
    for (prompt, new), s in zip(work, streams):
        assert _served_against_reference(s, prompt, new) < TOL


@pytest.mark.parametrize("option", [
    dict(prefix_cache=2), dict(speculate=2), dict(prefill_chunk=8),
    dict(kv_quant="int8")])
def test_options_the_family_does_not_bring_are_refused_by_name(option):
    with pytest.raises(ValueError) as err:
        _engine(**option)
    assert next(iter(option)) in str(err.value)
    assert "R4" in str(err.value)


def test_engine_reports_both_arenas():
    eng = _engine()
    assert eng.decode_attention == "gather"   # CPU
    assert eng.stats["decode_attention"] == "gather"
    # float32: 2 parts x 2 heads x 16 x 4 B a layer
    assert eng.stats["kv_bytes_per_token"] == 1 * 256
    assert eng.stats["kv_window_bytes_per_token"] == 3 * 256
    assert eng._bt_w.shape == eng._bt.shape == (3, 16)
    assert (eng._bt_w == eng._pool.win.SENTINEL).all()
    with pytest.raises(ValueError, match="auto"):
        ContinuousBatchingEngine(CFG, PARAMS, steps_per_dispatch="auto",
                                 block_tokens=T)
