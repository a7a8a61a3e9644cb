"""The hybrid family (state-space + attention mixers, a share of the routed
experts) against its plain reference, at a small size on the CPU: prefill,
prefill + decode through the engine's paged path, the chunked scan against
the one-step recurrence, the expert shares, the lanes' state slots, the
refused options, and the names the benchmark's readers look for.

Tolerance: everything here is float32, and the program differs from the
reference only in the order of its sums (a prompt in chunks where the
reference goes token by token, experts by sorted tile where the reference
goes expert by expert), so 1e-4 on log-probabilities of size 5 is a
hundred times the rounding seen (1e-6) and a thousandth of what a wrong
gate (0.1) or a state handed over one token late shows.
"""

import time

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from benchmark import reference_hybrid as ref  # noqa: E402
from nnstreamer_tpu.models import hybrid  # noqa: E402
from nnstreamer_tpu.models.hybrid import HybridConfig  # noqa: E402
from nnstreamer_tpu.models.transformer import _attend_cache  # noqa: E402
from nnstreamer_tpu.ops.flash_attention import (  # noqa: E402
    attention_reference,
    flash_attention,
)
from nnstreamer_tpu.serving import ContinuousBatchingEngine  # noqa: E402
from nnstreamer_tpu.serving import engine as engine_mod  # noqa: E402

TOL = 1e-4
#: multipliers chosen so that the layers, not the embedding, decide the
#: logits: an error in a mixer or an expert shows in the first digits
CFG = HybridConfig(
    vocab=211, d_model=64,
    layer_types=("mamba", "mamba", "attention", "mamba"),
    n_heads=4, n_kv_heads=2, head_dim=16, attention_scale=0.2,
    ssm_heads=16, ssm_head_dim=8, ssm_state=16, ssm_conv=4, ssm_chunk=16,
    num_experts=8, experts_per_token=3, expert_width=32, shared_width=48,
    experts_held=(0, 4), embedding_multiplier=2.0, residual_multiplier=0.5,
    logits_scaling=0.125, max_seq=128, dtype=jnp.float32,
    param_dtype=jnp.float32)
PARAMS = hybrid.init_params(CFG, seed=5)
SCOPES = ("ssm_in", "ssm_conv", "ssm_update", "ssm_out", "qkv", "kv_write",
          "kv_gather", "attend", "router", "experts", "shared_ffn",
          "logits", "sample")


def _prompt(n, seed=0):
    return np.random.default_rng([seed, n]).integers(
        1, CFG.vocab, n, dtype=np.int32)


def _engine(**kw):
    kw.setdefault("max_streams", 4)
    kw.setdefault("steps_per_dispatch", 4)
    kw.setdefault("block_tokens", 16)
    return ContinuousBatchingEngine(CFG, PARAMS, **kw)


def _reference(tokens, first, count):
    return np.asarray(jax.jit(
        lambda p, t: ref.hybrid_logprobs(p, t, first, count, CFG))(
            PARAMS, jnp.asarray(tokens)))


# -- (a) prefill -------------------------------------------------------------

@pytest.mark.parametrize("n,bucket", [(5, 16), (16, 16), (37, 64),
                                      (64, 64)])
def test_prefill_logits_equal_the_references(n, bucket):
    prompt = _prompt(n)
    padded = np.zeros((1, bucket), np.int32)
    padded[0, :n] = prompt
    logits, cache = jax.jit(hybrid.build_prefill(CFG))(
        PARAMS, jnp.asarray(padded), lengths=jnp.asarray([n], jnp.int32))
    got = np.asarray(jax.nn.log_softmax(logits[0]))
    assert np.abs(got - _reference(prompt, n - 1, 1)[0]).max() < TOL
    assert cache["kv"].shape == (1, 2, 1, bucket, 2, 16)
    assert cache["state"]["ssm"].shape == (3, 1, 16, 8, 16)
    assert cache["state"]["conv"].shape == (3, 1, 3, 16 * 8 + 32)


def test_forward_equals_the_reference_at_every_position():
    tokens = _prompt(50)
    got = jax.nn.log_softmax(jax.jit(hybrid.build_forward(CFG))(
        PARAMS, jnp.asarray(tokens[None]))[0])
    assert np.abs(np.asarray(got) - _reference(tokens, 0, 50)).max() < TOL


# -- (b) prefill, then decode through the engine's paged path ----------------

@pytest.mark.parametrize("n", [7, 21, 40])
def test_engine_serves_what_the_reference_computes_at_every_step(n):
    """Prefill (padded to its bucket), the hand-over of state and blocks,
    and 25 decode steps in dispatches of 4: the log-probability the engine
    reports for each served token is the reference's, from ONE forward
    over prompt + served tokens, and each token is the reference's best."""
    new = 26
    eng = _engine().start()
    try:
        prompt = _prompt(n, seed=1)
        stream = eng.submit(prompt, max_new_tokens=new)
        toks = np.asarray(stream.result(timeout=300))
    finally:
        eng.stop()
    assert len(toks) == new and stream.finish_reason == "length"
    lp = _reference(np.concatenate([prompt, toks[:-1]]), n - 1, new)
    at = lp[np.arange(new), toks]
    assert np.abs(at - np.asarray(stream.logprobs)).max() < TOL
    assert (lp.max(axis=1) - at).max() < TOL


@pytest.mark.parametrize("n", [7, 40])
def test_a_lanes_slot_holds_the_references_state_when_its_stream_ends(n):
    """A stream says which lane it kept (``GenerationStream.lane``), and
    with the engine idle ``BlockPool.lane_state`` reads that lane's slot:
    after 1 + 2 dispatches of 4 it is the reference's recurrent state, and
    the last three rows of its convolution's input, after the prompt and
    the 8 tokens those dispatches took in. The lane is not the first: two
    other streams hold lanes 0 and 1 meanwhile."""
    eng = _engine().start()
    try:
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
    _, want = jax.jit(lambda p, t: ref.hybrid_check(p, t, n - 1, 9, n + 8,
                                                    CFG))(
        PARAMS, jnp.asarray(np.concatenate([prompt, toks])))
    assert held["ssm"].shape == (3, 16, 8, 16) and held["ssm"].any()
    for name in ("ssm", "conv"):
        assert np.abs(held[name] - np.asarray(want[name])).max() < TOL, name


# -- (c) the chunked scan against the one-step recurrence --------------------

SCAN = HybridConfig(
    vocab=11, d_model=32, layer_types=("mamba", "attention"), n_heads=2,
    n_kv_heads=1, head_dim=8, ssm_heads=8, ssm_head_dim=8, ssm_state=16,
    ssm_chunk=256, num_experts=2, experts_per_token=1, expert_width=8,
    shared_width=8, experts_held=(0, 2), dtype=jnp.float32,
    param_dtype=jnp.float32)


@pytest.mark.parametrize("pad", [False, True], ids=["exact", "padded"])
@pytest.mark.parametrize("n", [1, 255, 256, 257, 700])
def test_chunked_scan_equals_the_recurrence(n, pad):
    """Outputs at the real positions, the state after the LAST REAL token
    and the convolution's last three real input rows, whatever follows
    them in the bucket."""
    lp = hybrid.init_params(SCAN, seed=2)["layers"][0]
    s = 1 << (n - 1).bit_length() if pad else n
    s = max(s, 2) if pad else s
    h = np.zeros((1, s, SCAN.d_model), np.float32)
    rng = np.random.default_rng(n)
    h[0, :n] = rng.standard_normal((n, SCAN.d_model))
    h[0, n:] = 7.0 * rng.standard_normal((s - n, SCAN.d_model))  # junk
    out, state, tail = jax.jit(
        lambda h, n_: hybrid._ssm_prefill(h, lp, n_, SCAN))(
            jnp.asarray(h), jnp.asarray([n], jnp.int32))

    def one(carry, h_t):
        o, st, tl = hybrid._ssm_decode(h_t[None], lp, carry[0], carry[1],
                                       jnp.ones((1,), bool), SCAN)
        return (st, tl), o[0]

    zero = (jnp.zeros((1, 8, 8, 16)), jnp.zeros((1, 3, SCAN.conv_dim)))
    (st_seq, tail_seq), out_seq = jax.jit(
        lambda x: jax.lax.scan(one, zero, x))(jnp.asarray(h[0, :n]))
    assert np.abs(np.asarray(out[0, :n]) - np.asarray(out_seq)).max() < TOL
    assert np.abs(np.asarray(state) - np.asarray(st_seq)).max() < TOL
    assert np.abs(np.asarray(tail) - np.asarray(tail_seq)).max() < 1e-6
    assert not np.asarray(tail)[0, :max(0, 3 - n)].any()  # before the prompt
    # and all three are the plain reference's sequential mixer, which is
    # given the junk too and asked for what it holds after n tokens
    want, st_ref, tail_ref = jax.jit(
        lambda x: ref._mamba(x, lp, SCAN, n))(jnp.asarray(h[0]))
    assert np.abs(np.asarray(out_seq) - np.asarray(want[:n])).max() < TOL
    assert np.abs(np.asarray(st_seq[0]) - np.asarray(st_ref)).max() < TOL
    assert np.abs(np.asarray(tail_seq[0]) - np.asarray(tail_ref)).max() < TOL


# -- (d) the expert shares add up --------------------------------------------

def test_two_expert_shares_and_the_shared_mlp_once_are_the_whole_layer():
    import dataclasses

    whole = dataclasses.replace(CFG, experts_held=(0, 8))
    lp = hybrid.init_params(whole, seed=9)["layers"][0]
    h = jnp.asarray(np.random.default_rng(4).standard_normal(
        (37, CFG.d_model)), jnp.float32)
    tol = 1e-6  # of outputs of size 3e-3: float32 sums in another order
    parts = []
    for lo, hi in ((0, 4), (4, 8)):
        share = dataclasses.replace(CFG, experts_held=(lo, hi))
        mine = {**lp, "w_in": lp["w_in"][lo:hi], "w_out": lp["w_out"][lo:hi]}
        y, counts = jax.jit(lambda h, p, c=share: hybrid.moe_ffn(h, p, c))(
            h, mine)
        parts.append(np.asarray(y))
        assert int(counts["moe_tokens_held"]) \
            + int(counts["moe_tokens_absent"]) == 37 * 3
        # the plain reference, given the same share
        want = ref.routed_experts(h, mine, share)
        assert np.abs(parts[-1] - np.asarray(want)).max() < tol
    shared = hybrid._gated(h, lp["shared_in"], lp["shared_out"],
                           jnp.float32)
    uncut = ref.routed_experts(h, lp, whole) \
        + ref._gated(h, lp["shared_in"], lp["shared_out"])
    assert np.abs(parts[0] + parts[1] + np.asarray(shared)
                  - np.asarray(uncut)).max() < tol
    assert np.abs(parts[0]).max() > 1e-3 and np.abs(parts[1]).max() > 1e-3


def test_an_expert_nobody_chose_is_never_computed():
    """The tile loop's trip count follows the routing: with every token
    sent to experts that are not held, it runs no tile at all."""
    import dataclasses

    absent = dataclasses.replace(CFG, experts_held=(7, 8))
    lp = dict(hybrid.init_params(absent, seed=9)["layers"][0])
    router = np.zeros((CFG.d_model, 8), np.float32)
    router[:, :3] = 1.0  # every token's three choices are experts 0..2
    lp["router"] = jnp.asarray(router)
    h = jnp.abs(jnp.asarray(np.random.default_rng(4).standard_normal(
        (9, CFG.d_model)), jnp.float32))
    y, counts = hybrid.moe_ffn(h, lp, absent)
    assert not np.asarray(y).any()
    assert int(counts["moe_tokens_held"]) == 0
    assert int(counts["moe_tokens_absent"]) == 27
    assert int(counts["moe_experts_hit"]) == 0


# -- (e) lanes ---------------------------------------------------------------

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


def test_an_empty_lane_writes_nowhere_and_reads_zeros():
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
    logits, new, counts = step(PARAMS, jnp.asarray([5, 6, 7, 8], jnp.int32),
                               arena, jnp.asarray(bt),
                               jnp.asarray([0, 0, 0, 0], jnp.int32))
    for name in ("ssm", "conv"):
        old, now = np.asarray(arena["state"][name]), np.asarray(
            new["state"][name])
        assert np.array_equal(old[:, [0, 2, 3]], now[:, [0, 2, 3]]), name
        assert not np.array_equal(old[:, 1], now[:, 1]), name
    kv_old, kv_new = np.asarray(arena["kv"]), np.asarray(new["kv"])
    changed = np.argwhere((kv_old != kv_new).reshape(
        kv_old.shape[:2] + (-1,)).any(-1))
    assert changed.tolist() == [[0, 3]]  # the live lane's block alone
    # the empty lanes are left out of the routing, and read a zero state:
    # whatever their slots hold, their logits are those of a zeroed arena
    assert int(counts["moe_tokens_held"]) \
        + int(counts["moe_tokens_absent"]) == CFG.n_layers * 3
    zeroed = jax.tree.map(jnp.zeros_like, arena)
    logits0, _, _ = step(PARAMS, jnp.asarray([5, 6, 7, 8], jnp.int32),
                         zeroed, jnp.asarray(bt), jnp.zeros(4, jnp.int32))
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


# -- (f) what the family refuses for now -------------------------------------

@pytest.mark.parametrize("option", [
    {"prefix_cache": 2}, {"speculate": 2}, {"prefill_chunk": 8},
    {"kv_quant": "int8"}, {"mesh": "dp2"}],
    ids=lambda o: next(iter(o)))
def test_refused_options_raise_at_construction(option):
    if "mesh" in option:
        from jax.sharding import Mesh

        option = {"mesh": Mesh(np.asarray(jax.devices()[:2]), ("dp",))}
    with pytest.raises(ValueError, match="lane.*does not yet support"):
        _engine(**option)


@pytest.mark.parametrize("block_tokens", [0, -1, 24],
                         ids=["zero", "negative", "non-divisor"])
def test_block_tokens_must_be_a_positive_divisor_of_max_seq(block_tokens):
    """One message for every family (the dense one:
    ``tests/test_paged_serving.py``)."""
    with pytest.raises(ValueError, match="positive divisor of max_seq"):
        _engine(block_tokens=block_tokens)


# -- (g) names, counters, snapshot -------------------------------------------

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

    fn = jax.jit(hybrid.build_prefill(CFG, attention_fn=flash))
    text = fn.lower(PARAMS, jax.ShapeDtypeStruct((1, 32), jnp.int32),
                    lengths=jax.ShapeDtypeStruct((1,), jnp.int32)).as_text(
                        debug_info=True)
    assert "module @jit_prefill" in text and "nns.prefill" in text
    for scope in ("ssm_in", "ssm_conv", "ssm_scan", "ssm_out", "qkv",
                  "attend", "router", "experts", "shared_ffn", "logits"):
        assert _has_scope(text, scope), scope
    assert "nns_flash_prefill" in text


def test_counters_and_pool_snapshot_cover_the_new_state():
    eng = _engine().start()
    try:
        for name in hybrid.COUNTERS:
            assert eng.stats[name] == 0 and type(eng.stats[name]) is int
        snap = eng._pool.snapshot()
        assert snap["state_slots"] == 4 and snap["state_slots_live"] == 0
        per_lane = 3 * (16 * 8 * 16 * 4 + 3 * CFG.conv_dim * 4)
        assert snap["state_bytes"] == 4 * per_lane
        assert snap["nbytes"] == snap["state_bytes"] + int(
            eng._pool.arena["kv"].nbytes)
        stream = eng.submit(_prompt(20), max_new_tokens=13)
        while not eng.stats["dispatches"]:
            pass
        live = eng._pool.snapshot()["state_slots_live"]
        stream.result(timeout=300)
    finally:
        eng.stop()
    assert live == 1
    stats = eng.stats
    steps = stats["dispatches"] * eng.K
    assert stats["moe_layer_steps"] == steps * CFG.n_layers
    # one live lane: three choices a layer and step, one expert at most each
    assert stats["moe_tokens_held"] + stats["moe_tokens_absent"] \
        == 3 * stats["moe_layer_steps"]
    assert stats["moe_expert_load_max"] <= stats["moe_layer_steps"]
    assert stats["moe_experts_hit"] == stats["moe_tokens_held"]


def test_pool_accounts_both_arenas():
    from nnstreamer_tpu.tensors import memory

    acct = memory.activate(1 << 30)
    try:
        eng = _engine()
        used = acct.snapshot()["used_by_category"]["kvcache"]
        assert used == eng._pool.nbytes > eng._pool.state_bytes > 0
    finally:
        memory.deactivate()


def test_dense_family_is_the_first_member_and_counts_nothing():
    from nnstreamer_tpu.models.transformer import (
        DENSE,
        TransformerConfig,
        init_params,
    )

    cfg = TransformerConfig(vocab=97, d_model=64, n_heads=4, n_layers=2,
                            d_ff=128, max_seq=64, dtype=jnp.float32)
    assert cfg.family is DENSE and DENSE.lane_state(cfg) is None
    eng = ContinuousBatchingEngine(cfg, init_params(cfg, 3), max_streams=2,
                                   steps_per_dispatch=4, block_tokens=8)
    assert not eng._lane_state and eng._counters == ()
    snap = eng._pool.snapshot()
    assert snap["state_slots"] == 0 and snap["state_bytes"] == 0
    assert not isinstance(eng._pool.arena, dict)
    _, _, shapes = engine_mod._DECODE_PROGRAMS[eng.obs_name]
    assert len(eng._dispatch.lower(*shapes).out_info) == 6


# -- grouped queries and the stated scale ------------------------------------

def _qkv(b, s, hq, hk, d, seed=0):
    rng = np.random.default_rng(seed)
    return (jnp.asarray(rng.standard_normal((b, s, hq, d)), jnp.float32),
            jnp.asarray(rng.standard_normal((b, s, hk, d)), jnp.float32),
            jnp.asarray(rng.standard_normal((b, s, hk, d)), jnp.float32))


@pytest.mark.parametrize("hq,hk", [(4, 4), (4, 2), (8, 1)])
def test_flash_kernel_takes_fewer_kv_heads_and_a_scale(hq, hk):
    q, k, v = _qkv(2, 32, hq, hk, 16)
    want = attention_reference(q, jnp.repeat(k, hq // hk, axis=2),
                               jnp.repeat(v, hq // hk, axis=2), scale=0.3)
    got = flash_attention(q, k, v, block_q=16, block_k=16, force="pallas",
                          scale=0.3)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 1e-5
    assert np.abs(np.asarray(attention_reference(q, k, v, scale=0.3))
                  - np.asarray(want)).max() < 1e-6


def test_cached_attention_groups_query_heads_over_kv_heads():
    q, k, v = _qkv(3, 24, 4, 2, 8, seed=1)
    mask = jnp.arange(24)[None, None, None, :] <= jnp.asarray(
        [5, 23, 11])[:, None, None, None]
    got = _attend_cache(q[:, :1], k, v, mask, 8, jnp.float32, scale=0.4)
    want = _attend_cache(q[:, :1], jnp.repeat(k, 2, axis=2),
                         jnp.repeat(v, 2, axis=2), mask, 8, jnp.float32,
                         scale=0.4)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 1e-6
    # the default scale is the dense block's
    a = _attend_cache(q[:, :1], jnp.repeat(k, 2, axis=2),
                      jnp.repeat(v, 2, axis=2), mask, 8, jnp.float32)
    b = _attend_cache(q[:, :1], jnp.repeat(k, 2, axis=2),
                      jnp.repeat(v, 2, axis=2), mask, 8, jnp.float32,
                      scale=8 ** -0.5)
    assert np.array_equal(np.asarray(a), np.asarray(b))


def test_config_refuses_a_pattern_of_one_kind_and_an_empty_share():
    with pytest.raises(ValueError, match="layer_types"):
        HybridConfig(layer_types=("mamba", "mamba"))
    with pytest.raises(ValueError, match="experts_held"):
        HybridConfig(experts_held=(4, 4))
    with pytest.raises(ValueError, match="n_kv_heads"):
        HybridConfig(n_heads=6, n_kv_heads=4)
