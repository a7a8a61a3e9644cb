"""The hybrid family's third mixer kind (the gated delta rule) and its gated
attention (norms over query and key heads, partial rotary positions, an
output gate), with a gated shared expert and an untied head, against the
plain reference ``benchmark/reference_qwen3_next.py`` at a small size on the
CPU: prefill, prefill + decode through the engine's two arenas, the chunked
rule against the sequential recurrence, the rotary positions and head norms
against a hand-written case, the expert shares, the lanes' slots, the
narrowing of the new leaves and the names the benchmark's readers look for.

Tolerance: everything here is float32 and the program differs from the
reference only in the order of its sums (a prompt solved chunk by chunk
where the reference goes token by token, experts by sorted tile where the
reference goes expert by expert), so 1e-4 on log-probabilities of size 6 is
fifty times the rounding seen (2e-6) and a thousandth of what a write
strength fixed at one, a rotation over the whole head or a state handed
over one token late shows. The weights are eight times the initialisation's
so that the layers, not the embedding, decide the logits.
"""

import dataclasses
import functools
import time

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from benchmark import reference_hybrid  # noqa: E402
from benchmark import reference_qwen3_next as ref  # noqa: E402
from nnstreamer_tpu.models import gated_delta, hybrid  # noqa: E402
from nnstreamer_tpu.models.family import serving_params  # noqa: E402
from nnstreamer_tpu.models.hybrid import HybridConfig  # noqa: E402
from nnstreamer_tpu.ops.flash_attention import flash_attention  # noqa: E402
from nnstreamer_tpu.serving import ContinuousBatchingEngine  # noqa: E402
from nnstreamer_tpu.serving import engine as engine_mod  # noqa: E402

TOL = 1e-4
CFG = HybridConfig(
    vocab=211, d_model=64,
    layer_types=("linear_attention",) * 3 + ("attention",),
    n_heads=4, n_kv_heads=2, head_dim=16, attention_scale=0.25,
    rotary_dim=4, rope_theta=1e4, qk_norm=True, attn_gate=True,
    la_key_heads=2, la_value_heads=4, la_key_dim=8, la_value_dim=16,
    la_conv=4, la_chunk=8,
    num_experts=8, experts_per_token=3, expert_width=32, shared_width=32,
    shared_gate=True, experts_held=(0, 4), tie_embeddings=False,
    embedding_multiplier=1.0, residual_multiplier=1.0, logits_scaling=1.0,
    rms_eps=1e-6, max_seq=128, dtype=jnp.float32, param_dtype=jnp.float32)


def _weights(cfg, seed):
    return jax.tree.map(
        lambda a: a * 8 if a.ndim >= 2 and a.shape[0] != cfg.vocab else a,
        hybrid.init_params(cfg, seed))


PARAMS = _weights(CFG, 5)
SCOPES = ("la_in", "la_conv", "la_update", "la_out", "qkv", "kv_write",
          "kv_gather", "attend", "router", "experts", "shared_ffn",
          "logits", "sample")


def _prompt(n, seed=0):
    return np.random.default_rng([seed, n]).integers(
        1, CFG.vocab, n, dtype=np.int32)


def _engine(cfg=CFG, params=PARAMS, **kw):
    kw.setdefault("max_streams", 4)
    kw.setdefault("steps_per_dispatch", 4)
    kw.setdefault("block_tokens", 16)
    return ContinuousBatchingEngine(cfg, params, **kw)


def _reference(tokens, first, count, cfg=CFG, params=PARAMS, **wrong):
    return np.asarray(jax.jit(
        lambda p, t: ref.qwen3_next_logprobs(p, t, first, count, cfg,
                                             **wrong))(
            params, jnp.asarray(tokens)))


# -- (a) prefill -------------------------------------------------------------

@pytest.mark.parametrize("n,bucket", [(5, 16), (16, 16), (37, 64),
                                      (64, 64)])
def test_prefill_logits_state_and_tail_equal_the_references(n, bucket):
    prompt = _prompt(n)
    padded = np.zeros((1, bucket), np.int32)
    padded[0, :n] = prompt
    logits, cache = jax.jit(hybrid.build_prefill(CFG))(
        PARAMS, jnp.asarray(padded), lengths=jnp.asarray([n], jnp.int32))
    want, state = jax.jit(lambda p, t: ref.qwen3_next_check(
        p, t, n - 1, 1, n, CFG))(PARAMS, jnp.asarray(prompt))
    got = np.asarray(jax.nn.log_softmax(logits[0]))
    assert np.abs(got - np.asarray(want[0])).max() < TOL
    assert cache["kv"].shape == (1, 2, 1, bucket, 2, 16)
    assert cache["state"]["ssm"].shape == (3, 1, 4, 8, 16)
    assert cache["state"]["conv"].shape == (3, 1, 3, 2 * 16 + 64)
    for name in ("ssm", "conv"):
        assert np.abs(np.asarray(cache["state"][name][:, 0])
                      - np.asarray(state[name])).max() < TOL, name
    assert np.abs(np.asarray(state["ssm"])).max() > 0.1


def test_forward_equals_the_reference_at_every_position():
    tokens = _prompt(50)
    got = jax.nn.log_softmax(jax.jit(hybrid.build_forward(CFG))(
        PARAMS, jnp.asarray(tokens[None]))[0])
    assert np.abs(np.asarray(got) - _reference(tokens, 0, 50)).max() < TOL


@pytest.mark.parametrize("wrong", ["beta_one", "full_rotary",
                                   "renormalise_held"])
def test_the_tolerance_tells_each_wrong_model_from_the_right_one(wrong):
    """What the benchmark's controls leave out, at this size: each moves
    the log-probabilities by a thousand tolerances or more."""
    tokens = _prompt(50)
    right = _reference(tokens, 0, 50)
    assert np.abs(_reference(tokens, 0, 50, **{wrong: True})
                  - right).max() > 1000 * TOL


# -- (b) prefill, then decode through the engine's paged path ----------------

@pytest.mark.parametrize("n,form", [
    (7, "gather"), (21, "gather"), (40, "gather"), (21, "paged_kernel"),
    (40, "paged_kernel")], ids=["7", "21", "40", "21-kernel", "40-kernel"])
def test_engine_serves_what_the_reference_computes_at_every_step(
        n, form, monkeypatch):
    """Prefill (padded to its bucket), the hand-over of state and blocks,
    and 25 decode steps in dispatches of 4, rotary positions from the
    lane's own: each served token's reported log-probability is the
    reference's, from ONE forward over prompt + served tokens, and each
    token is the reference's best. Two key-value heads: the arena is
    heads-major (``[.., 2, 16, dh]`` a block half), in the gather form
    and, at a head dim the kernel takes (8 query heads of 128 over the
    2), through ``nns_paged_decode`` interpreted inside the real K-step
    program."""
    cfg, params = CFG, PARAMS
    if form == "paged_kernel":
        import nnstreamer_tpu.ops as ops_pkg
        from nnstreamer_tpu.ops.paged_attention import paged_attention

        cfg = dataclasses.replace(CFG, n_heads=8, head_dim=128,
                                  attention_scale=128 ** -0.5)
        params = _weights(cfg, 5)
        monkeypatch.setattr(ops_pkg, "paged_attention", functools.partial(
            paged_attention, force="pallas"))
    new = 26
    eng = _engine(cfg, params).start()
    try:
        assert eng._pool.heads_major and eng._pool.arena["kv"].shape[3:5] \
            == (2, 16)
        prompt = _prompt(n, seed=1)
        stream = eng.submit(prompt, max_new_tokens=new)
        toks = np.asarray(stream.result(timeout=300))
        text = engine_mod.decode_program_text(eng.obs_name) \
            if form == "paged_kernel" else ""
    finally:
        eng.stop()
    assert ("kv_gather/gather" not in text and "/attend/" in text) \
        == (form == "paged_kernel")
    assert len(toks) == new and stream.finish_reason == "length"
    lp = _reference(np.concatenate([prompt, toks[:-1]]), n - 1, new, cfg,
                    params)
    at = lp[np.arange(new), toks]
    assert np.abs(at - np.asarray(stream.logprobs)).max() < TOL
    assert (lp.max(axis=1) - at).max() < TOL


@pytest.mark.parametrize("n", [7, 40])
def test_a_lanes_slot_holds_the_references_state_when_its_stream_ends(n):
    """After 1 + 2 dispatches of 4 a lane's slot is the reference's
    delta-rule state, and the last three rows of its convolution's input,
    after the prompt and the 8 tokens those dispatches took in. The lane is
    the third: two other streams hold lanes 0 and 1 meanwhile, the first of
    them in a slot that an earlier request left its state in."""
    eng = _engine().start()
    try:
        eng.generate(_prompt(30, seed=2), max_new_tokens=5, timeout=300)
        others = [eng.submit(_prompt(9, seed=s), max_new_tokens=60)
                  for s in (6, 7)]
        while not all(o.first_t for o in others):
            time.sleep(0.005)
        prompt = _prompt(n, seed=1)
        stream = eng.submit(prompt, max_new_tokens=9)
        toks = stream.result(timeout=300)
        for o in others:
            o.result(timeout=300)
        held = eng._pool.lane_state(stream.lane)
    finally:
        eng.stop()
    assert stream.lane == 2 and [o.lane for o in others] == [0, 1]
    _, want = jax.jit(lambda p, t: ref.qwen3_next_check(
        p, t, n - 1, 9, n + 8, CFG))(
            PARAMS, jnp.asarray(np.concatenate([prompt, toks])))
    assert held["ssm"].shape == (3, 4, 8, 16) and held["ssm"].any()
    for name in ("ssm", "conv"):
        assert np.abs(held[name] - np.asarray(want[name])).max() < TOL, name


def test_a_reused_lane_starts_from_a_zero_state():
    one = _engine(max_streams=1).start()
    try:
        first = one.generate(_prompt(30, seed=2), max_new_tokens=9,
                             timeout=300)
        again = one.submit(_prompt(11, seed=3), max_new_tokens=9)
        again.result(timeout=300)
    finally:
        one.stop()
    fresh = _engine(max_streams=1).start()
    try:
        alone = fresh.submit(_prompt(11, seed=3), max_new_tokens=9)
        alone.result(timeout=300)
    finally:
        fresh.stop()
    assert len(first) == 9
    assert again.tokens == alone.tokens
    assert np.abs(np.asarray(again.logprobs)
                  - np.asarray(alone.logprobs)).max() < 1e-6


# -- (c) the chunked rule against the sequential recurrence ------------------

@pytest.mark.parametrize("pad", [False, True], ids=["exact", "padded"])
@pytest.mark.parametrize("n", [1, 7, 8, 9, 63, 64, 65, 200])
def test_chunked_rule_equals_the_recurrence(n, pad):
    """Outputs at the real positions, the state after the LAST REAL token
    and the convolution's last three real input rows, whatever follows
    them in the bucket; chunks of 8, lengths that are no multiple of it."""
    lp = PARAMS["layers"][0]
    s = max(1 << (n - 1).bit_length(), 2) if pad else n
    h = np.zeros((1, s, CFG.d_model), np.float32)
    rng = np.random.default_rng(n)
    h[0, :n] = rng.standard_normal((n, CFG.d_model))
    h[0, n:] = 7.0 * rng.standard_normal((s - n, CFG.d_model))  # junk
    out, state, tail = jax.jit(
        lambda h, n_: hybrid._la_prefill(h, lp, n_, CFG))(
            jnp.asarray(h), jnp.asarray([n], jnp.int32))

    def one(carry, h_t):
        o, st, tl = hybrid._la_decode(h_t[None], lp, carry[0], carry[1],
                                      jnp.ones((1,), bool), CFG)
        return (st, tl), o[0]

    zero = (jnp.zeros((1, 4, 8, 16)), jnp.zeros((1, 3, CFG.la_conv_dim)))
    (st_seq, tail_seq), out_seq = jax.jit(
        lambda x: jax.lax.scan(one, zero, x))(jnp.asarray(h[0, :n]))
    assert np.abs(np.asarray(out[0, :n]) - np.asarray(out_seq)).max() < TOL
    assert np.abs(np.asarray(state) - np.asarray(st_seq)).max() < TOL
    assert np.abs(np.asarray(tail) - np.asarray(tail_seq)).max() < 1e-6
    assert not np.asarray(tail)[0, :max(0, 3 - n)].any()  # before the prompt
    # and all three are the plain reference's sequential mixer, which is
    # given the junk too and asked for what it holds after n tokens
    want, st_ref, tail_ref = jax.jit(
        lambda x: ref._delta_mixer(x, lp, CFG, n))(jnp.asarray(h[0]))
    assert np.abs(np.asarray(out_seq) - np.asarray(want[:n])).max() < TOL
    assert np.abs(np.asarray(st_seq[0]) - np.asarray(st_ref)).max() < TOL
    assert np.abs(np.asarray(tail_seq[0]) - np.asarray(tail_ref)).max() < TOL
    assert np.abs(np.asarray(st_ref)).max() > 0.05


def test_one_step_of_the_rule_by_hand():
    """One head, key and value of two: decay by a half, read under the
    key, correct by beta, write, then the output from the written state."""
    state = jnp.asarray([[2.0, 0.0], [0.0, 4.0]])
    k, q = jnp.asarray([1.0, 0.0]), jnp.asarray([1.0, 1.0])
    v = jnp.asarray([3.0, 1.0])
    o, new = gated_delta.gated_delta_step(
        state, q, k, v, jnp.log(0.5), jnp.asarray(0.5))
    # decayed [[1,0],[0,2]]; read = row of key 0 = [1,0]; d = .5([3,1]-[1,0])
    assert np.allclose(np.asarray(new), [[2.0, 0.5], [0.0, 2.0]])
    assert np.allclose(np.asarray(o), [2.0, 2.5])


def test_unit_lower_inverse_is_the_inverse():
    rng = np.random.default_rng(3)
    a = np.tril(rng.standard_normal((2, 3, 16, 16)), -1).astype(np.float32)
    got = np.asarray(jax.jit(gated_delta._unit_lower_inverse)(jnp.asarray(a)))
    assert np.abs(got @ (np.eye(16) + a) - np.eye(16)).max() < 1e-4


# -- (d) rotary positions and head norms, by hand ----------------------------

def test_partial_rotary_turns_the_first_dims_and_passes_the_rest():
    """Head dim 8, rotary over the first 4 (a quarter-turn case): dim 0
    turns with dim 2 by ``pos`` radians, dim 1 with dim 3 by ``pos / theta
    ** 0.5``; dims 4-7 pass. Program and reference alike."""
    theta = 100.0
    x = np.arange(1, 9, dtype=np.float32)[None, None, None, :]   # [1,1,1,8]
    x = np.repeat(x, 3, axis=1)                                  # 3 positions
    pos = np.asarray([[0, 1, 5]], np.int32)
    got = np.asarray(hybrid._rope(jnp.asarray(x), jnp.asarray(pos), 4, theta))
    for i, p in enumerate(pos[0]):
        for j, angle in ((0, p * 1.0), (1, p / 10.0)):
            a, b = x[0, i, 0, j], x[0, i, 0, j + 2]
            assert np.isclose(got[0, i, 0, j],
                              a * np.cos(angle) - b * np.sin(angle),
                              atol=1e-5)
            assert np.isclose(got[0, i, 0, j + 2],
                              b * np.cos(angle) + a * np.sin(angle),
                              atol=1e-5)
        assert np.array_equal(got[0, i, 0, 4:], x[0, i, 0, 4:])
    # the reference numbers positions from 0 itself
    want = np.asarray(ref.rotate(jnp.asarray(x[0, :, :, :]), 4, theta))
    at = np.asarray(hybrid._rope(jnp.asarray(x), jnp.asarray([[0, 1, 2]]), 4,
                                 theta))
    assert np.abs(at[0] - want).max() < 1e-6


def test_queries_and_keys_are_normed_head_by_head_then_turned():
    """``_qkv`` with identity projections: each head of q and k comes out
    with mean square ``scale ** 2`` before the rotation (which keeps
    lengths), v is untouched, and the gate is its own projection."""
    cfg = dataclasses.replace(CFG, d_model=32, n_heads=2, n_kv_heads=2)
    eye = jnp.eye(32).reshape(32, 2, 16)
    lp = {"wq": eye, "wk": 3 * eye, "wv": eye, "wg": -eye,
          "q_norm": jnp.full(16, 2.0), "k_norm": jnp.ones(16)}
    h = jnp.asarray(np.random.default_rng(0).standard_normal((1, 5, 32)),
                    jnp.float32)
    pos = jnp.arange(5)[None]
    q, k, v, gate = hybrid._qkv(h, lp, pos, cfg)
    assert np.allclose(np.mean(np.asarray(q) ** 2, -1), 4.0, atol=1e-3)
    assert np.allclose(np.mean(np.asarray(k) ** 2, -1), 1.0, atol=1e-3)
    assert np.array_equal(np.asarray(v), np.asarray(h).reshape(1, 5, 2, 16))
    assert np.array_equal(np.asarray(gate), -np.asarray(v))
    assert np.allclose(np.asarray(q)[0, 0], 2 * np.asarray(
        reference_hybrid._rmsnorm(h[0, 0].reshape(2, 16), 1.0, 1e-6)),
        atol=1e-5)  # position 0: no turn


# -- (e) the expert shares add up --------------------------------------------

def test_two_expert_shares_with_the_rest_once_are_the_whole_layer():
    """The halves (0,4) and (4,8) of the routed experts, with the router,
    the gated shared expert and the residual counted once, sum to the
    uncut layer: in the program and in the reference."""
    whole = dataclasses.replace(CFG, experts_held=(0, 8))
    lp = _weights(whole, 9)["layers"][0]
    x = jnp.asarray(np.random.default_rng(4).standard_normal(
        (1, 37, CFG.d_model)), jnp.float32)
    tol = 1e-5
    full, _ = jax.jit(lambda x: hybrid._expert_layer(x, lp, whole))(x)
    h = reference_hybrid._rmsnorm(x[0], lp["ln2"], CFG.rms_eps)
    shared = reference_hybrid._gated(h, lp["shared_in"], lp["shared_out"]) \
        * jax.nn.sigmoid(h @ lp["shared_gate"])[:, None]
    parts = []
    for lo, hi in ((0, 4), (4, 8)):
        share = dataclasses.replace(CFG, experts_held=(lo, hi))
        mine = {**lp, "w_in": lp["w_in"][lo:hi], "w_out": lp["w_out"][lo:hi]}
        y, counts = jax.jit(lambda h, p, c=share: hybrid.moe_ffn(h, p, c))(
            h, mine)
        parts.append(np.asarray(y))
        assert int(counts["moe_tokens_held"]) \
            + int(counts["moe_tokens_absent"]) == 37 * 3
        want = reference_hybrid.routed_experts(h, mine, share)
        assert np.abs(parts[-1] - np.asarray(want)).max() < tol
        # a share's layer is the residual, its experts and the shared expert
        mine_out, _ = jax.jit(
            lambda x, p, c=share: hybrid._expert_layer(x, p, c))(x, mine)
        assert np.abs(np.asarray(mine_out[0]) - np.asarray(x[0])
                      - parts[-1] - np.asarray(shared)).max() < tol
    assert np.abs(np.asarray(full[0]) - np.asarray(x[0]) - parts[0]
                  - parts[1] - np.asarray(shared)).max() < tol
    uncut = reference_hybrid.routed_experts(h, lp, whole) + shared
    assert np.abs(parts[0] + parts[1] + np.asarray(shared)
                  - np.asarray(uncut)).max() < tol
    assert np.abs(parts[0]).max() > 1e-2 and np.abs(parts[1]).max() > 1e-2
    assert np.abs(np.asarray(shared)).max() > 1e-2


# -- (f) lanes ---------------------------------------------------------------

def test_an_empty_lane_leaves_its_slot_alone_and_reads_zeros():
    eng = _engine()
    pool = eng._pool
    rng = np.random.default_rng(0)
    arena = jax.tree.map(
        lambda a: jnp.asarray(rng.standard_normal(a.shape), a.dtype),
        pool.arena)
    arena["kv"] = arena["kv"].at[:, -1].set(0)  # the pool's zero block
    bt = np.full((4, eng.MB), pool.SENTINEL, np.int32)
    bt[1, 0] = 3  # lane 1 alone is live
    step = jax.jit(hybrid.build_paged_decode_step(CFG, 16))
    tokens = jnp.asarray([5, 6, 7, 8], jnp.int32)
    logits, new, counts = step(PARAMS, tokens, arena, jnp.asarray(bt),
                               jnp.zeros(4, jnp.int32))
    for name in ("ssm", "conv"):
        old, now = np.asarray(arena["state"][name]), np.asarray(
            new["state"][name])
        assert np.array_equal(old[:, [0, 2, 3]], now[:, [0, 2, 3]]), name
        assert not np.array_equal(old[:, 1], now[:, 1]), name
    kv_old, kv_new = np.asarray(arena["kv"]), np.asarray(new["kv"])
    changed = np.argwhere((kv_old != kv_new).reshape(
        kv_old.shape[:2] + (-1,)).any(-1))
    assert changed.tolist() == [[0, 3]]  # the live lane's block alone
    assert int(counts["moe_tokens_held"]) \
        + int(counts["moe_tokens_absent"]) == CFG.n_layers * 3
    zeroed = jax.tree.map(jnp.zeros_like, arena)
    logits0, _, _ = step(PARAMS, tokens, zeroed, jnp.asarray(bt),
                         jnp.zeros(4, jnp.int32))
    assert np.array_equal(np.asarray(logits)[[0, 2, 3]],
                          np.asarray(logits0)[[0, 2, 3]])


def test_eight_requests_together_equal_the_same_eight_alone():
    prompts = [_prompt(n, seed=4) for n in (3, 9, 16, 17, 30, 31, 45, 60)]
    eng = _engine().start()
    try:
        streams = [eng.submit(p, max_new_tokens=10) for p in prompts]
        for s in streams:
            s.result(timeout=300)
        assert eng.stats["concurrent_streams_max"] == 4  # lanes, no more
        alone = []
        for p in prompts:
            alone.append(eng.submit(p, max_new_tokens=10))
            alone[-1].result(timeout=300)
    finally:
        eng.stop()
    for a, b in zip(streams, alone):
        assert a.tokens == b.tokens
        assert np.abs(np.asarray(a.logprobs)
                      - np.asarray(b.logprobs)).max() < 1e-5
    assert eng._pool.live_blocks() == 0
    assert eng._pool.snapshot()["state_slots_live"] == 0


@pytest.mark.parametrize("option", [
    {"prefix_cache": 2}, {"speculate": 2}, {"prefill_chunk": 8}],
    ids=lambda o: next(iter(o)))
def test_options_that_copy_or_resume_the_state_are_refused(option):
    with pytest.raises(ValueError, match="lane.*does not yet support"):
        _engine(**option)


def test_config_takes_one_recurrent_kind_beside_attention():
    with pytest.raises(ValueError, match="layer_types"):
        HybridConfig(layer_types=("mamba", "linear_attention", "attention"))
    with pytest.raises(ValueError, match="layer_types"):
        HybridConfig(layer_types=("linear_attention",) * 2)
    with pytest.raises(ValueError, match="la_value_heads"):
        dataclasses.replace(CFG, la_key_heads=3)
    with pytest.raises(ValueError, match="rotary_dim"):
        dataclasses.replace(CFG, rotary_dim=18)


# -- (g) held weights, names, counters, snapshot -----------------------------

def test_serving_params_narrows_the_new_leaves_bit_equal():
    """Given float32 weights and a bfloat16 ``dtype``, every matrix the
    programs read through ``.astype(dtype)`` is held narrowed, to the very
    bits the program's own cast gives; scales, the convolution and the
    per-head vectors stay as stored."""
    cfg = dataclasses.replace(CFG, dtype=jnp.bfloat16)
    held, record = serving_params(cfg, PARAMS)
    narrowed = {"la_in", "la_ba", "la_out", "wq", "wk", "wv", "wo", "wg",
                "router", "w_in", "w_out", "shared_in", "shared_out",
                "shared_gate"}
    for lp, hp in zip(PARAMS["layers"], held["layers"]):
        for name, leaf in lp.items():
            if name in narrowed:
                assert hp[name].dtype == jnp.bfloat16, name
                assert np.array_equal(
                    np.asarray(hp[name].astype(jnp.float32)),
                    np.asarray(leaf.astype(jnp.bfloat16).astype(
                        jnp.float32))), name
            else:
                assert hp[name] is leaf, name
    assert held["lm_head"].dtype == jnp.bfloat16
    assert held["embed"] is PARAMS["embed"] and held["ln_f"] is PARAMS["ln_f"]
    n_mats = 3 * (3 + 6) + (5 + 6) + 1
    assert record["weight_leaves_narrowed"] == n_mats
    assert record["weight_bytes_held"] < record["weight_bytes_given"]
    # and a tree already in dtype is passed through whole
    same, again = serving_params(cfg, held)
    assert again["weight_leaves_narrowed"] == 0
    assert all(a is b for a, b in zip(jax.tree.leaves(same),
                                      jax.tree.leaves(held)))


def _has_scope(text, scope):
    return f'"{scope}/' in text or f"/{scope}/" in text


def test_decode_program_registers_and_holds_every_scope():
    eng = _engine()
    build, k, shapes = engine_mod._DECODE_PROGRAMS[eng.obs_name]
    assert build is eng._build_dispatch and k == eng.K
    text = eng._dispatch.lower(*shapes).as_text(debug_info=True)
    assert "module @jit_dispatch" in text and "nns.decode" in text
    for scope in SCOPES:
        assert _has_scope(text, scope), scope
    assert not _has_scope(text, "ssm_update")
    compiled = engine_mod.decode_program_text(eng.obs_name)
    for scope in SCOPES:
        assert f"/{scope}/" in compiled, scope


def test_prefill_program_holds_every_scope_and_the_flash_kernel():
    def flash(q, k, v, scale):
        return flash_attention(q, k, v, block_q=16, block_k=16,
                               force="pallas", scale=scale)

    fn = jax.jit(hybrid.build_prefill(CFG, attention_fn=flash))
    text = fn.lower(PARAMS, jax.ShapeDtypeStruct((1, 32), jnp.int32),
                    lengths=jax.ShapeDtypeStruct((1,), jnp.int32)).as_text(
                        debug_info=True)
    assert "module @jit_prefill" in text and "nns.prefill" in text
    for scope in ("la_in", "la_conv", "la_scan", "la_out", "qkv", "attend",
                  "router", "experts", "shared_ffn", "logits"):
        assert _has_scope(text, scope), scope
    assert "nns_flash_prefill" in text


def test_counters_and_pool_snapshot_cover_the_new_state():
    eng = _engine().start()
    try:
        snap = eng._pool.snapshot()
        assert snap["state_slots"] == 4 and snap["state_slots_live"] == 0
        per_lane = 3 * (4 * 8 * 16 * 4 + 3 * CFG.la_conv_dim * 4)
        assert snap["state_bytes"] == 4 * per_lane
        assert snap["nbytes"] == snap["state_bytes"] + int(
            eng._pool.arena["kv"].nbytes)
        stream = eng.submit(_prompt(20), max_new_tokens=13)
        stream.result(timeout=300)
    finally:
        eng.stop()
    stats = eng.stats
    assert stats["moe_layer_steps"] == stats["dispatches"] * eng.K \
        * CFG.n_layers
    assert stats["moe_tokens_held"] + stats["moe_tokens_absent"] \
        == 3 * stats["moe_layer_steps"]
    assert stats["moe_experts_hit"] == stats["moe_tokens_held"]
