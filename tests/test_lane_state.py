"""The recurrent mixers' lane-state update (ops/lane_state.py).

The kernel walks (lane, head block) as a Pallas grid over the whole state
arena, aliased to its output; the reference slices the layer out, runs the
rule's step under a ``where`` and sets the layer back. Off a TPU the
kernel runs through the Pallas interpreter when forced, which is how these
tests hold it to the reference: in a call of its own, as the carry of a
K-step scan, and inside a hybrid engine with the mixer, the pool and the
hand-over round it. The shapes are cut-down twins of the two recurrent
cells': many heads of ``[8, 128]`` (Mamba-2), few of ``[16, 128]`` (the
gated delta rule) and state-major ``[16, channels]`` (Mamba-1: one channel
block a lane, as its cell has it, and sixteen), whole lanes a grid step and
split ones. What ``auto``
builds is the reference wherever the kernel does not run, and says why
once.

Tolerance: both forms are float32 elementwise and differ only in the order
of the float32 sums behind an output (and in where a product is rounded
into a sum), so 1e-6 of the size of what is compared holds; a dead lane's
slot and every other layer are equal bit for bit.
"""

import logging

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from nnstreamer_tpu.models import hybrid  # noqa: E402
from nnstreamer_tpu.models.gated_delta import l2norm  # noqa: E402
from nnstreamer_tpu.models.hybrid import HybridConfig  # noqa: E402
from nnstreamer_tpu.ops import lane_state as ls  # noqa: E402
from nnstreamer_tpu.serving import ContinuousBatchingEngine  # noqa: E402

#: rule, arena [layers, lanes, heads, rows, cols], heads a grid step (None:
#: the rule's, whole lanes here)
CASES = pytest.mark.parametrize("rule,shape,hb", [
    (ls.MAMBA2, (3, 4, 16, 8, 128), None),
    (ls.MAMBA2, (3, 4, 16, 8, 128), 8),
    (ls.GATED_DELTA, (3, 4, 8, 16, 128), None),
    (ls.GATED_DELTA, (3, 4, 16, 16, 128), 8),
    (ls.MAMBA1, (3, 4, 1, 16, 256), None),
    (ls.MAMBA1, (3, 4, 16, 16, 128), 8),
], ids=["mamba2_whole", "mamba2_split", "delta_whole", "delta_split",
        "mamba1_whole", "mamba1_split"])
LANES = pytest.mark.parametrize("lanes", ["all", "half", "none"])


def _operands(rule, shape, rng):
    """A step's operands at the sizes a mixer hands over: steps and write
    strengths in (0, 1), decays below one, keys and queries of length one."""
    _, lanes, heads, rows, cols = shape

    def normal(*dims):
        return jnp.asarray(rng.standard_normal(dims), jnp.float32)

    if rule == ls.MAMBA2:
        return (normal(lanes, heads, rows),
                jax.nn.softplus(normal(lanes, heads)),
                -jnp.exp(0.3 * normal(heads)), normal(lanes, cols),
                normal(lanes, cols))
    if rule == ls.MAMBA1:   # the tile is state-major: rows are the state
        return (normal(lanes, heads, cols),
                jax.nn.softplus(normal(lanes, heads, cols)),
                -jnp.exp(0.3 * normal(heads, rows, cols)),
                normal(lanes, rows), normal(lanes, rows))
    return (l2norm(normal(lanes, heads, rows)) * rows ** -0.5,
            l2norm(normal(lanes, heads, rows)), normal(lanes, heads, cols),
            -jax.nn.softplus(normal(lanes, heads)),
            jax.nn.sigmoid(normal(lanes, heads)))


def _live(lanes, n):
    return {"all": jnp.ones(n, bool), "half": jnp.arange(n) % 2 == 0,
            "none": jnp.zeros(n, bool)}[lanes]


def _split(monkeypatch, hb):
    """The block rule answers ``hb`` heads a grid step (a split lane)."""
    if hb is not None:
        real = ls.head_block
        monkeypatch.setattr(ls, "head_block", lambda heads, tile, vmem=0:
                            (hb, real(hb, tile)[1]))


def _close(got, want, what):
    got, want = np.asarray(got), np.asarray(want)
    assert got.shape == want.shape and np.isfinite(got).all(), what
    np.testing.assert_allclose(got, want, rtol=0, err_msg=what,
                               atol=1e-6 * max(np.abs(want).max(initial=0),
                                               1e-3))


@LANES
@CASES
def test_kernel_equals_the_reference_and_touches_only_its_lanes_and_layer(
        monkeypatch, rule, shape, hb, lanes):
    rng = np.random.default_rng(shape[2])
    arena = jnp.asarray(rng.standard_normal(shape), jnp.float32)
    ops = _operands(rule, shape, rng)
    live = _live(lanes, shape[1])
    want_o, want = ls.update(rule, ls.LaneSlot(arena, 1), live, ops,
                             force="reference")
    _split(monkeypatch, hb)
    assert ls.head_block(shape[2], shape[3] * shape[4] * 4)[0] \
        == (hb or shape[2])
    got_o, got = ls.update(rule, ls.LaneSlot(arena, 1), live, ops,
                           force="pallas")
    assert got.layer == want.layer == 1
    alive = np.asarray(live)
    _close(got.arena[1][alive], want.arena[1][alive], "the live lanes")
    _close(got_o[alive], want_o[alive], "their outputs")
    assert got_o.shape == (shape[1], shape[2],
                           shape[3] if rule == ls.MAMBA2 else shape[4])
    # a dead lane's slot and every other layer: bit for bit what they were
    for layer in (0, 2):
        np.testing.assert_array_equal(np.asarray(got.arena[layer]),
                                      np.asarray(arena[layer]))
    np.testing.assert_array_equal(np.asarray(got.arena[1])[~alive],
                                  np.asarray(arena[1])[~alive])
    np.testing.assert_array_equal(np.asarray(want.arena[1])[~alive],
                                  np.asarray(arena[1])[~alive])
    if lanes != "none":
        assert np.abs(np.asarray(want.arena[1] - arena[1])[alive]).max() > .1


@CASES
def test_eight_steps_in_a_scan_carry_equal_eight_reference_steps(
        monkeypatch, rule, shape, hb):
    """The arena as the carry of the K-step scan, every layer updated each
    step (as the decode program holds it) against eight sequential steps
    of the reference; lane 2 is empty at the odd steps."""
    rng = np.random.default_rng(8)
    arena = jnp.asarray(0.5 * rng.standard_normal(shape), jnp.float32)
    steps = [_operands(rule, shape, rng) for _ in range(8)]
    xs = tuple(jnp.stack(x) for x in zip(*steps))
    lives = jnp.stack([jnp.arange(shape[1]) != 2 * (t % 2) for t in range(8)])

    def run(force):
        def body(arena, x):
            live, ops = x
            outs = []
            for layer in range(shape[0]):
                o, slot = ls.update(rule, ls.LaneSlot(arena, layer), live,
                                    ops, force=force)
                arena = slot.arena
                outs.append(o)
            return arena, jnp.stack(outs)
        return jax.jit(lambda a: jax.lax.scan(body, a, (lives, xs)))(arena)

    want, want_o = arena, []
    for t in range(8):
        outs = []
        for layer in range(shape[0]):
            o, slot = ls.lane_state_reference(
                rule, ls.LaneSlot(want, layer), lives[t], steps[t])
            want = slot.arena
            outs.append(o)
        want_o.append(jnp.stack(outs))
    _split(monkeypatch, hb)
    got, got_o = run("pallas")
    _close(got, want, "the arena after eight steps")
    alive = np.asarray(lives)[:, None, :, None, None]
    _close(np.where(alive, got_o, 0), np.where(alive, jnp.stack(want_o), 0),
           "the outputs of eight steps")


# -- which form runs, and how the engine says so ------------------------------

REJECTS = [("not float32", (2, 2, 8, 8, 128), jnp.bfloat16),
           ("128 lanes", (2, 2, 8, 8, 16), jnp.float32),
           ("8 sublanes", (2, 2, 8, 4, 128), jnp.float32)]


def test_auto_builds_the_reference_off_a_tpu():
    assert jax.default_backend() == "cpu"
    shape = (2, 2, 8, 8, 128)
    arena = jnp.zeros(shape, jnp.float32)
    ops = _operands(ls.MAMBA2, shape, np.random.default_rng(0))
    assert ls._pallas_reject(ls.MAMBA2, arena, ops) is None
    assert ls.state_update_form(ls.MAMBA2, arena) == "reference"
    text = jax.jit(lambda a, live, ops: ls.update(
        ls.MAMBA2, ls.LaneSlot(a, 0), live, ops)).lower(
            arena, jnp.ones(2, bool), ops).as_text()
    assert "select" in text and "nns_lane_state" not in text
    with pytest.raises(ValueError, match="no rule"):
        ls.update("lstm", ls.LaneSlot(arena, 0), jnp.ones(2, bool), ops,
                  force="pallas")


@pytest.mark.parametrize("rule", [ls.MAMBA2, ls.GATED_DELTA])
@pytest.mark.parametrize("why,shape,dtype", REJECTS,
                         ids=["bf16_state", "odd_width", "odd_rows"])
def test_each_reject_reason_names_itself_once_in_the_log(
        monkeypatch, rule, why, shape, dtype):
    rng = np.random.default_rng(1)
    arena = jnp.asarray(rng.standard_normal(shape), dtype)
    ops = _operands(rule, shape, rng)
    args = (rule, ls.LaneSlot(arena, 1), jnp.asarray([True, False]), ops)
    with pytest.raises(ValueError, match=why):
        ls.update(*args, force="pallas")
    # on a TPU auto gives way to the reference for such arenas, and says why
    records = []
    handler = logging.Handler()
    handler.emit = records.append
    ls.log.addHandler(handler)
    ls._log_reference_choice.cache_clear()
    monkeypatch.setattr(ls.jax, "default_backend", lambda: "tpu")
    try:
        assert ls.state_update_form(rule, arena) == "reference"
        want_o, want = ls.update(*args, force="reference")
        for _ in range(2):
            got_o, got = ls.update(*args)
            np.testing.assert_array_equal(np.asarray(got_o),
                                          np.asarray(want_o))
            np.testing.assert_array_equal(
                np.asarray(got.arena.astype(jnp.float32)),
                np.asarray(want.arena.astype(jnp.float32)))
    finally:
        ls.log.removeHandler(handler)
        ls._log_reference_choice.cache_clear()
    assert got.arena.dtype == dtype
    said = [r.getMessage() for r in records]
    assert len(said) == 1 and why in said[0] and "reference" in said[0]


def test_block_rule_takes_a_mebibyte_of_tiles_a_step_on_a_v5e():
    # both cells at a v5e's 128 MiB: 32 tiles of 32 KiB, 16 of 64 KiB
    assert ls.head_block(128, 64 * 128 * 4)[0] == 32
    assert ls.head_block(32, 128 * 128 * 4)[0] == 16
    # a thirty-second of the VMEM holds the block four times over
    hb, limit = ls.head_block(128, 64 * 128 * 4, vmem_bytes=64 << 20)
    assert hb == 16 and 4 * hb * 64 * 128 * 4 <= (64 << 20) // 32 < limit
    # whole lanes where they are small, whole sublanes where they divide
    assert ls.head_block(12, 64 << 10)[0] == 12
    assert ls.head_block(24, 128 << 10)[0] == 8
    assert ls.head_block(4, 4 << 20)[0] == 4    # nothing smaller divides


MAMBA_CFG = HybridConfig(
    vocab=97, d_model=64, layer_types=("mamba", "attention", "mamba"),
    n_heads=4, n_kv_heads=2, head_dim=16, ssm_heads=8, ssm_head_dim=8,
    ssm_state=128, ssm_chunk=16, num_experts=8, experts_per_token=2,
    expert_width=32, shared_width=32, experts_held=(0, 4), max_seq=64,
    dtype=jnp.float32, param_dtype=jnp.float32)
DELTA_CFG = HybridConfig(
    vocab=97, d_model=64,
    layer_types=("linear_attention", "linear_attention", "attention"),
    n_heads=4, n_kv_heads=2, head_dim=16, attention_scale=0.25,
    rotary_dim=4, rope_theta=1e4, qk_norm=True, attn_gate=True,
    la_key_heads=2, la_value_heads=4, la_key_dim=8, la_value_dim=128,
    la_conv=4, la_chunk=8, num_experts=8, experts_per_token=2,
    expert_width=32, shared_width=32, shared_gate=True, experts_held=(0, 4),
    tie_embeddings=False, max_seq=64, dtype=jnp.float32,
    param_dtype=jnp.float32)
PROMPTS = ([3, 5, 7, 11, 13], [2, 4, 6, 8, 10, 12, 14, 16, 18], [90, 1])


def _weights(cfg, seed):
    return jax.tree.map(
        lambda a: a * 8 if a.ndim >= 2 and a.shape[0] != cfg.vocab else a,
        hybrid.init_params(cfg, seed))


def _serve(cfg, params):
    """Three requests on four lanes (one stays empty), seven tokens each:
    ``(tokens, the slots of the lanes that served them)``."""
    eng = ContinuousBatchingEngine(cfg, params, max_streams=4,
                                   steps_per_dispatch=4,
                                   block_tokens=16).start()
    try:
        streams = [eng.submit(p, max_new_tokens=7) for p in PROMPTS]
        tokens = [s.result(timeout=300) for s in streams]
        slots = [jax.tree.map(np.asarray, eng._pool.lane_state(s.lane))
                 for s in streams]
    finally:
        eng.stop()
    return tokens, slots, eng


@pytest.mark.parametrize("cfg,rule", [(MAMBA_CFG, ls.MAMBA2),
                                      (DELTA_CFG, ls.GATED_DELTA)],
                         ids=["mamba", "linear_attention"])
def test_engine_serves_the_same_with_the_kernel_and_with_the_reference(
        monkeypatch, cfg, rule):
    params = _weights(cfg, 4)
    assert hybrid.state_update(cfg, 4) == "reference"
    want_tokens, want_slots, eng = _serve(cfg, params)
    assert eng.state_update == eng.stats["state_update"] == "reference"
    calls = []
    real = ls.update

    def forced(rule_, slot, live, operands, force=None):
        calls.append((rule_, tuple(slot.arena.shape)))
        return real(rule_, slot, live, operands, force="pallas")

    monkeypatch.setattr(ls, "update", forced)
    got_tokens, got_slots, _ = _serve(cfg, params)
    layers = sum(k != "attention" for k in cfg.layer_types)
    shape, _ = hybrid.lane_state(cfg)["ssm"]
    assert set(calls) == {(rule, (layers, 4) + tuple(shape))}
    assert got_tokens == want_tokens and len(got_tokens[0]) == 7
    for got, want in zip(got_slots, want_slots):
        assert np.abs(want["ssm"]).max() > 1e-2
        _close(got["ssm"], want["ssm"], "a served lane's state")
        _close(got["conv"], want["conv"], "its convolution's tail")


def test_a_bfloat16_state_takes_the_reference_through_the_same_call():
    import dataclasses

    cfg = dataclasses.replace(MAMBA_CFG, ssm_state_dtype=jnp.bfloat16)
    arena = jax.ShapeDtypeStruct((2, 4, 8, 8, 128), jnp.bfloat16)
    assert "not float32" in ls._pallas_reject(ls.MAMBA2, arena)
    tokens, slots, eng = _serve(cfg, _weights(cfg, 4))
    assert eng.state_update == "reference" and len(tokens[0]) == 7
    assert slots[0]["ssm"].dtype == jnp.bfloat16


@pytest.mark.parametrize("family", ["dense", "latent"])
def test_an_engine_without_lane_state_has_no_state_update(family):
    if family == "dense":
        from nnstreamer_tpu.models.transformer import (
            TransformerConfig,
            init_params,
        )

        cfg = TransformerConfig(vocab=97, d_model=64, n_heads=4, n_layers=2,
                                d_ff=128, max_seq=64, dtype=jnp.float32)
        params = init_params(cfg, 3)
    else:
        from nnstreamer_tpu.models import mla

        cfg = mla.MLAConfig(
            vocab=97, d_model=64, n_layers=2, n_heads=4, qk_nope_dim=16,
            qk_rope_dim=8, v_head_dim=24, kv_lora_rank=128,
            rope_original_max=32, rope_factor=4.0, dense_width=96,
            num_experts=8, experts_per_token=2, expert_width=32,
            shared_width=32, experts_held=(0, 4), max_seq=64,
            dtype=jnp.float32, param_dtype=jnp.float32)
        params = mla.init_params(cfg, 3)
    assert cfg.family.state_update is None
    eng = ContinuousBatchingEngine(cfg, params, max_streams=2,
                                   steps_per_dispatch=4, block_tokens=8)
    assert eng.state_update is None and "state_update" not in eng.stats
