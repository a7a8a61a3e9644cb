"""The latent-attention decoder (models/mla.py) at a small size, float32,
seeded weights: the program against the plain reference
(benchmark/reference_deepseek_v2.py), its two attention paths against each
other, and the family through ``ContinuousBatchingEngine``.

Tolerances: everything here is float32. The program and the reference sum
in different orders (absorbed against expanded attention, tiles against a
scan over experts), which costs a few float32 roundings of numbers of size
1 to 10: 2e-4 holds every comparison of logits and 1e-5 every comparison
of one layer's output; a wrong model reads 1e-2 or more.
"""

import dataclasses

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from benchmark import reference_deepseek_v2 as ref  # noqa: E402
from benchmark import reference_hybrid  # noqa: E402
from nnstreamer_tpu.models import hybrid, mla  # noqa: E402
from nnstreamer_tpu.models.family import serving_params  # noqa: E402
from nnstreamer_tpu.models.mla import MLAConfig  # noqa: E402
from nnstreamer_tpu.ops import flash_attention  # noqa: E402
from nnstreamer_tpu.ops.paged_attention import (  # noqa: E402
    paged_attention,
    paged_attention_reference,
)
from nnstreamer_tpu.serving import ContinuousBatchingEngine  # noqa: E402
from nnstreamer_tpu.serving import engine as engine_mod  # noqa: E402
from nnstreamer_tpu.serving import kvpool  # noqa: E402

pytestmark = pytest.mark.filterwarnings("ignore::DeprecationWarning")

CFG = MLAConfig(
    vocab=211, d_model=64, n_layers=3, n_heads=8, qk_nope_dim=16,
    qk_rope_dim=8, v_head_dim=24, kv_lora_rank=128, rope_original_max=32,
    rope_factor=4.0, dense_width=96, num_experts=8, experts_per_token=3,
    expert_width=32, shared_width=64, experts_held=(0, 4), max_seq=128,
    dtype=jnp.float32, param_dtype=jnp.float32)
PARAMS = CFG.family.init_params(CFG, seed=5)
T, K = 8, 4
TOL = 2e-4
SCOPES = ("mla_q", "mla_kv", "kv_write", "kv_gather", "attend", "mla_out",
          "dense_ffn", "router", "experts", "shared_ffn", "logits", "sample")


def _prompt(n, seed=0):
    return np.random.default_rng([seed, n]).integers(
        1, CFG.vocab, n).astype(np.int32)


def _engine(**kw):
    return ContinuousBatchingEngine(
        CFG, PARAMS, max_streams=3, steps_per_dispatch=K, temperature=0.0,
        block_tokens=T, **kw)


def _ref_logprobs(tokens, first, count, **wrong):
    return np.asarray(ref.deepseek_v2_logprobs(
        PARAMS, jnp.asarray(tokens), first, count, CFG, **wrong))


# -- (a) the closed forms ----------------------------------------------------

def test_yarn_table_and_softmax_scale_at_the_published_keys():
    cfg = MLAConfig()
    f = mla.rotary_frequencies(cfg).astype(np.float64)
    plain = 10000.0 ** (-2 * np.arange(32) / 64)
    # low = floor(corr(32)) = 10, high = ceil(corr(1)) = 23
    corr = lambda n: 64 * np.log(4096 / (2 * np.pi * n)) / (2 * np.log(1e4))
    assert (int(np.floor(corr(32))), int(np.ceil(corr(1)))) == (10, 23)
    ramp = np.clip((np.arange(32) - 10) / 13, 0, 1)
    np.testing.assert_allclose(f, plain * ((1 - ramp) + ramp / 40),
                               rtol=1e-6)
    np.testing.assert_allclose(f[:11], plain[:11], rtol=1e-6)   # untouched
    np.testing.assert_allclose(f[23:], plain[23:] / 40, rtol=1e-6)
    assert abs(mla.yarn_mscale(40, 0.707) - 1.26080) < 1e-5
    assert abs(mla.softmax_scale(cfg) - 0.114721) < 1e-6
    # the reference holds the same table from its own lines
    np.testing.assert_allclose(np.asarray(ref.frequencies(cfg)), f,
                               rtol=1e-5)
    # factor 1: the plain table, no mscale
    one = dataclasses.replace(cfg, rope_factor=1.0)
    np.testing.assert_allclose(mla.rotary_frequencies(one), plain,
                               rtol=1e-6)
    assert mla.softmax_scale(one) == pytest.approx(192 ** -0.5)
    assert (cfg.row_width, cfg.row_store) == (576, 640)


def test_rotation_turns_pairs_half_split_by_the_scaled_angles():
    x = jnp.asarray(np.random.default_rng(1).standard_normal((1, 6, 2, 8)),
                    jnp.float32)
    pos = jnp.asarray([[0, 1, 5, 31, 32, 100]])
    got = np.asarray(mla._rotate(x, pos, CFG))
    f = mla.rotary_frequencies(CFG).astype(np.float64)
    for j, p in enumerate([0, 1, 5, 31, 32, 100]):
        cos, sin = np.cos(p * f), np.sin(p * f)
        x1, x2 = np.asarray(x[0, j, :, :4]), np.asarray(x[0, j, :, 4:])
        np.testing.assert_allclose(got[0, j, :, :4], x1 * cos - x2 * sin,
                                   atol=1e-5)
        np.testing.assert_allclose(got[0, j, :, 4:], x1 * sin + x2 * cos,
                                   atol=1e-5)


# -- (b) the program against the reference -----------------------------------

def test_forward_equals_the_reference_at_every_position():
    toks = _prompt(45)
    got = np.asarray(jax.nn.log_softmax(
        jax.jit(mla.build_forward(CFG))(PARAMS, jnp.asarray(toks[None]))[0]))
    assert np.abs(got - _ref_logprobs(toks, 0, 45)).max() < TOL


@pytest.mark.parametrize("n,bucket", [(5, 16), (16, 16), (37, 64),
                                      (64, 64)])
def test_padded_prefill_hands_over_the_last_real_tokens_logits_and_rows(
        n, bucket):
    toks = _prompt(n)
    padded = np.zeros((1, bucket), np.int32)
    padded[0, :n] = toks
    logits, rows = jax.jit(mla.build_prefill(CFG))(
        PARAMS, jnp.asarray(padded), lengths=jnp.asarray([n], jnp.int32))
    assert rows.shape == (CFG.n_layers, 1, 1, bucket, CFG.row_store)
    assert not np.asarray(rows[..., CFG.row_width:]).any()
    lp, want = ref.deepseek_v2_check(PARAMS, jnp.asarray(toks), n - 1, 1, 0,
                                     CFG)
    assert np.abs(np.asarray(jax.nn.log_softmax(logits[0]))
                  - np.asarray(lp[0])).max() < TOL
    assert np.abs(np.asarray(rows[:, 0, 0, :n, :CFG.row_width])
                  - np.asarray(want["rows"])).max() < TOL


@pytest.mark.parametrize("wrong", ["renormalise", "no_mscale",
                                   "plain_rotary", "no_kv_norm",
                                   "no_shared"])
def test_the_tolerance_tells_each_wrong_model_from_the_right_one(wrong):
    toks = jnp.asarray(_prompt(45))
    right = ref.deepseek_v2_check(PARAMS, toks, 0, 45, 0, CFG)
    other = ref.deepseek_v2_check(PARAMS, toks, 0, 45, 0, CFG,
                                  **{wrong: True})
    # in the log-probabilities, or already in the rows a cache would hold
    assert max(np.abs(np.asarray(right[0]) - np.asarray(other[0])).max(),
               np.abs(np.asarray(right[1]["rows"])
                      - np.asarray(other[1]["rows"])).max()) > 10 * TOL


@pytest.mark.parametrize("n", [7, 21, 40])
def test_engine_serves_what_the_reference_computes_at_every_step(n):
    """Prefill (expanded) + paged decode (absorbed) through the latent
    arena: every served token is the reference's best at its position and
    its reported log-probability the reference's, and the rows the
    stream's blocks are left with are the reference's."""
    eng = _engine().start()
    try:
        prompt = _prompt(n)
        stream = eng.submit(prompt, max_new_tokens=13)
        toks = stream.result(timeout=300)
        rows = eng._pool.stream_rows(stream.blocks, n + 12)
    finally:
        eng.stop()
    full = np.concatenate([prompt, toks]).astype(np.int32)
    lp, want = ref.deepseek_v2_check(PARAMS, jnp.asarray(full), n - 1, 13,
                                     0, CFG)
    lp = np.asarray(lp)
    assert [int(t) for t in toks] == lp.argmax(-1).tolist()
    assert np.abs(lp[np.arange(13), toks]
                  - np.asarray(stream.logprobs)).max() < TOL
    # rows 0..n-1 from the prefill, n..n+11 one a decode step (the 13th
    # token was sampled and never fed)
    assert rows.shape == (CFG.n_layers, 1, n + 12, CFG.row_store)
    assert np.abs(rows[:, 0, :, :CFG.row_width]
                  - np.asarray(want["rows"])[:, :n + 12]).max() < TOL
    assert not rows[..., CFG.row_width:].any()
    assert len(stream.blocks) == -(-(n + 12) // T) or \
        len(stream.blocks) == -(-(n + 12 + K) // T)


def test_absorbed_decode_equals_expanded_attention_on_the_same_cache():
    """One layer's attention for one new token a lane, both ways, over
    rows written by the program: the expanded form by hand."""
    rng = np.random.default_rng(2)
    lp = PARAMS["layers"][1]
    held = (1, 9, 23)
    nb, MB = 12, 4
    pages = jnp.zeros((1, nb + 1, 1, T, CFG.row_store), jnp.float32)
    bt = np.full((3, MB), nb + 1, np.int32)
    order = rng.permutation(nb).reshape(3, MB)
    hs = []
    for lane, n in enumerate(held):
        bt[lane, :-(-n // T)] = order[lane, :-(-n // T)]
        h = jnp.asarray(rng.standard_normal((1, n, CFG.d_model)),
                        jnp.float32)
        row = mla._latent_rows(h, lp, jnp.arange(n)[None], CFG)[0]
        for t in range(n):
            pages = pages.at[0, bt[lane, t // T], 0, t % T].set(row[t])
        hs.append(h[:, -1:])
    h = jnp.concatenate(hs)                                     # [3,1,d]
    pos = jnp.asarray(np.asarray(held) - 1)
    q_nope, q_rope = mla._queries(h, lp, pos[:, None], CFG)
    rank, nope = CFG.kv_lora_rank, CFG.qk_nope_dim
    q = jnp.concatenate([jnp.einsum("bqhc,rhc->bqhr", q_nope,
                                    lp["wkv_b"][..., :nope]), q_rope,
                         jnp.zeros((3, 1, CFG.n_heads,
                                    CFG.row_store - CFG.row_width))], -1)
    scale = mla.softmax_scale(CFG)
    o_lat = paged_attention_reference(q, pages, 0, jnp.asarray(bt), pos,
                                      scale=scale, v_width=rank)
    got = np.asarray(jnp.einsum("bqhr,rhc->bqhc", o_lat,
                                lp["wkv_b"][..., nope:]))
    kern = paged_attention(q, pages, 0, jnp.asarray(bt), pos, scale=scale,
                           v_width=rank, force="pallas", chunk_blocks=2)
    np.testing.assert_allclose(np.asarray(kern), np.asarray(o_lat),
                               atol=1e-5)
    for lane, n in enumerate(held):
        rows = np.concatenate([np.asarray(pages[0, b, 0])
                               for b in bt[lane, :-(-n // T)]])[:n]
        kv = np.einsum("sr,rhc->shc", rows[:, :rank], np.asarray(lp["wkv_b"]))
        k = np.concatenate([kv[..., :nope], np.broadcast_to(
            rows[:, None, rank:CFG.row_width],
            (n, CFG.n_heads, CFG.qk_rope_dim))], -1)
        qq = np.concatenate([np.asarray(q_nope[lane, 0]),
                             np.asarray(q_rope[lane, 0])], -1)  # [h, c]
        s = np.einsum("hc,shc->hs", qq, k) * scale
        p = np.exp(s - s.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        want = np.einsum("hs,shc->hc", p, kv[..., nope:])
        np.testing.assert_allclose(got[lane, 0], want, atol=1e-5)


# -- (c) the layers' second halves -------------------------------------------

def test_gates_are_the_softmax_over_all_outputs_and_not_renormalised():
    lp = PARAMS["layers"][1]
    whole = dataclasses.replace(CFG, experts_held=(0, 8))
    lp = {**lp, "w_in": jnp.concatenate([lp["w_in"]] * 2),
          "w_out": jnp.concatenate([lp["w_out"]] * 2)}
    h = jnp.asarray(np.random.default_rng(3).standard_normal(
        (29, CFG.d_model)), jnp.float32)
    got, counts = jax.jit(lambda h: hybrid.moe_ffn(h, lp, whole))(h)
    p = np.asarray(jax.nn.softmax(h @ lp["router"], -1))
    choice = np.argsort(-p, -1)[:, :3]
    want = np.zeros_like(np.asarray(h))
    for t in range(29):
        for e in choice[t]:
            want[t] += p[t, e] * np.asarray(reference_hybrid._gated(
                h[t:t + 1], lp["w_in"][e], lp["w_out"][e]))[0]
    assert np.abs(np.asarray(got) - want).max() < 1e-5
    assert int(counts["moe_tokens_held"]) == 29 * 3
    # the gates of a token sum to less than one: nothing renormalised them
    assert np.take_along_axis(p, choice, -1).sum(-1).max() < 0.9
    # and the scaling factor is a plain factor on the routed part
    twice = dataclasses.replace(whole, routed_scaling_factor=2.0)
    doubled, _ = jax.jit(lambda h: hybrid.moe_ffn(h, lp, twice))(h)
    np.testing.assert_allclose(np.asarray(doubled), 2 * np.asarray(got),
                               rtol=1e-5, atol=1e-6)
    # the registered configurations' gates: softmax over the chosen
    renorm = dataclasses.replace(whole, norm_topk_prob=True)
    other, _ = jax.jit(lambda h: hybrid.moe_ffn(h, lp, renorm))(h)
    assert np.abs(np.asarray(other) - np.asarray(
        reference_hybrid.routed_experts(h, lp, renorm))).max() < 1e-5


def test_the_leading_layer_is_dense_and_the_rest_route():
    layers = PARAMS["layers"]
    assert "dense_in" in layers[0] and "router" not in layers[0]
    assert all("router" in lp and "dense_in" not in lp for lp in layers[1:])
    assert layers[0]["dense_in"].shape == (CFG.d_model, 2 * CFG.dense_width)
    x = jnp.asarray(np.random.default_rng(6).standard_normal(
        (1, 11, CFG.d_model)), jnp.float32)
    got, counts = mla._ffn(x, layers[0], CFG)
    h = reference_hybrid._rmsnorm(x[0], layers[0]["ln2"], CFG.rms_eps)
    want = x[0] + reference_hybrid._gated(h, layers[0]["dense_in"],
                                          layers[0]["dense_out"])
    assert counts is None
    assert np.abs(np.asarray(got[0]) - np.asarray(want)).max() < 1e-5
    two = dataclasses.replace(CFG, first_dense_layers=2)
    assert ["dense_in" in lp for lp in two.family.init_params(two, 1)[
        "layers"]] == [True, True, False]


def test_two_expert_shares_with_the_shared_experts_once_are_the_whole_layer():
    """The halves (0,4) and (4,8) of the routed experts, with the shared
    experts and the residual counted once, sum to the uncut layer: in the
    program and in the reference (``model-configs`` section 4)."""
    whole = dataclasses.replace(CFG, experts_held=(0, 8))
    lp = whole.family.init_params(whole, 9)["layers"][1]
    x = jnp.asarray(np.random.default_rng(4).standard_normal(
        (1, 37, CFG.d_model)), jnp.float32)
    tol = 1e-5
    full, _ = jax.jit(lambda x: mla._ffn(x, lp, whole))(x)
    h = reference_hybrid._rmsnorm(x[0], lp["ln2"], CFG.rms_eps)
    shared = np.asarray(reference_hybrid._gated(h, lp["shared_in"],
                                                lp["shared_out"]))
    parts = []
    for lo, hi in ((0, 4), (4, 8)):
        share = dataclasses.replace(CFG, experts_held=(lo, hi))
        mine = {**lp, "w_in": lp["w_in"][lo:hi], "w_out": lp["w_out"][lo:hi]}
        y, counts = jax.jit(lambda h, p, c=share: hybrid.moe_ffn(h, p, c))(
            h, mine)
        parts.append(np.asarray(y))
        assert int(counts["moe_tokens_held"]) \
            + int(counts["moe_tokens_absent"]) == 37 * 3
        want = ref.routed_experts(h, mine, share)
        assert np.abs(parts[-1] - np.asarray(want)).max() < tol
        mine_out, _ = jax.jit(lambda x, p, c=share: mla._ffn(x, p, c))(
            x, mine)
        assert np.abs(np.asarray(mine_out[0]) - np.asarray(x[0])
                      - parts[-1] - shared).max() < tol
    assert np.abs(np.asarray(full[0]) - np.asarray(x[0]) - parts[0]
                  - parts[1] - shared).max() < tol
    uncut = np.asarray(ref.routed_experts(h, lp, whole)) + shared
    assert np.abs(parts[0] + parts[1] + shared - uncut).max() < tol
    assert np.abs(parts[0]).max() > 1e-3 and np.abs(parts[1]).max() > 1e-3
    assert np.abs(shared).max() > 1e-3


# -- (d) the engine ----------------------------------------------------------

def test_eight_requests_through_three_lanes_equal_the_same_eight_alone():
    """Lanes and blocks are reused: more requests than lanes, each served
    beside others, deliver what each delivers alone."""
    prompts = [_prompt(n, seed=1) for n in (3, 9, 17, 30, 5, 41, 12, 8)]
    eng = _engine().start()
    try:
        alone = [eng.submit(p, max_new_tokens=9).result(timeout=300)
                 for p in prompts]
        streams = [eng.submit(p, max_new_tokens=9) for p in prompts]
        together = [s.result(timeout=300) for s in streams]
        assert eng._pool.live_blocks() == 0
    finally:
        eng.stop()
    assert together == alone
    assert eng.stats["concurrent_streams_max"] >= 3


@pytest.mark.parametrize("option,value", [
    ("prefix_cache", 4), ("speculate", 2), ("prefill_chunk", 16),
    ("kv_quant", "int8"), ("mesh", object())])
def test_options_the_family_does_not_bring_are_refused_by_name(option,
                                                               value):
    with pytest.raises(ValueError, match=rf"mla model family.*latent row"
                                         rf".*R5.*does not yet support "
                                         rf"{option}"):
        _engine(**{option: value})


def test_no_codec_narrows_a_latent_row():
    with pytest.raises(ValueError, match="no codec 'int8'"):
        mla.build_paged_decode_step(CFG, T, kv_codec="int8")
    with pytest.raises(ValueError, match="multiple of block_tokens"):
        mla.build_paged_decode_step(CFG, 7)
    with pytest.raises(ValueError, match="experts_held"):
        MLAConfig(experts_held=(3, 3))


def test_the_record_says_what_each_family_brings():
    from nnstreamer_tpu.models.transformer import DENSE

    assert set(DENSE.brings) == {"prefix_cache", "speculate",
                                 "prefill_chunk", "kv_quant", "mesh"}
    assert DENSE.build_paged_chunk
    for family in (hybrid.HYBRID, mla.MLA):
        assert family.brings == () and family.build_paged_chunk is None
        assert "ROADMAP.md R" in family.refusal
    assert mla.MLA.lane_state(CFG) is None
    assert mla.MLA.kv_entry(CFG) == (3, 1, (256,))
    assert mla.MLA.latent_value_width(CFG) == 128
    eng = _engine()
    assert eng._paged_chunk_fn is None


def test_serving_params_narrows_the_families_leaves_bit_equal():
    cfg = dataclasses.replace(CFG, dtype=jnp.bfloat16)
    held, record = serving_params(cfg, PARAMS)
    assert record["weight_bytes_held"] < record["weight_bytes_given"]
    lp, given = held["layers"][1], PARAMS["layers"][1]
    for name in ("wq", "wkv_a", "wkv_b", "wo", "router", "w_in", "w_out",
                 "shared_in", "shared_out"):
        assert lp[name].dtype == jnp.bfloat16, name
        assert np.array_equal(np.asarray(lp[name], np.float32), np.asarray(
            given[name].astype(jnp.bfloat16), np.float32))
    assert held["layers"][0]["dense_in"].dtype == jnp.bfloat16
    assert held["lm_head"].dtype == jnp.bfloat16
    for name in ("ln1", "ln2", "kv_norm"):
        assert lp[name] is given[name]
    assert held["embed"] is PARAMS["embed"]


# -- (e) spans and counters --------------------------------------------------

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
    compiled = engine_mod.decode_program_text(eng.obs_name)
    for scope in SCOPES:
        assert f"/{scope}/" in compiled, scope


def test_prefill_program_holds_every_scope_and_the_flash_kernel():
    def flash(q, k, v, scale):
        return flash_attention(q, k, v, block_q=16, block_k=16,
                               force="pallas", scale=scale)

    fn = jax.jit(mla.build_prefill(CFG, attention_fn=flash))
    toks = np.zeros((1, 32), np.int32)
    toks[0, :21] = _prompt(21)
    text = fn.lower(PARAMS, jnp.asarray(toks),
                    lengths=jnp.asarray([21], jnp.int32)).as_text(
                        debug_info=True)
    assert "module @jit_prefill" in text and "nns.prefill" in text
    for scope in ("mla_q", "mla_kv", "attend", "mla_out", "dense_ffn",
                  "router", "experts", "shared_ffn", "logits"):
        assert _has_scope(text, scope), scope
    assert "nns_flash_prefill" in text
    # queries and keys 24 wide against values of 24: the kernel's output
    # takes the values' width, and serves what the XLA form serves
    logits, rows = fn(PARAMS, jnp.asarray(toks),
                      lengths=jnp.asarray([21], jnp.int32))
    plain, rows2 = jax.jit(mla.build_prefill(CFG))(
        PARAMS, jnp.asarray(toks), lengths=jnp.asarray([21], jnp.int32))
    assert np.abs(np.asarray(logits) - np.asarray(plain)).max() < TOL
    assert np.abs(np.asarray(rows) - np.asarray(rows2))[:, :, :, :21].max() \
        < TOL


def test_stats_carry_the_attention_form_the_row_bytes_and_the_counters():
    eng = _engine().start()
    try:
        assert eng.decode_attention == "gather"      # off a TPU
        assert eng.stats["decode_attention"] == "gather"
        # one row of 136 float32 a token a layer, held at 256 columns,
        # three layers
        assert eng.stats["kv_bytes_per_token"] == 3 * 256 * 4
        assert eng.expert_matmul == "tile_loop"
        snap = eng._pool.snapshot()
        assert snap["state_slots"] == 0 and snap["state_bytes"] == 0
        assert snap["nbytes"] == 3 * (3 * 16 + 1) * T * 256 * 4
        eng.submit(_prompt(20), max_new_tokens=13).result(timeout=300)
    finally:
        eng.stop()
    stats = eng.stats
    # the leading layer is dense: two expert layers a step
    assert stats["moe_layer_steps"] == stats["dispatches"] * K * 2
    assert stats["moe_tokens_held"] + stats["moe_tokens_absent"] \
        == 3 * stats["moe_layer_steps"]
    assert stats["kv_blocks_live"] > 0
    assert isinstance(kvpool.BlockPool(CFG, 4, T).lane_state(0), dict)
