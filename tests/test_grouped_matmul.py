"""The routed experts' grouped matmul (ops/grouped_matmul.py).

The kernel walks the expert-sorted tiles as a Pallas grid; the tile loop
walks them as a ``fori_loop``. Off a TPU the kernel runs through the
Pallas interpreter when forced, which is how these tests hold it to the
loop: on the same sorted buffer in a call of its own, and inside
``moe_ffn`` with the routing, the gather back and the counters round it.
The shapes are cut-down twins of the two expert cells': many small experts
at 8-row tiles, few large ones at 32 and 128. What ``auto`` builds is the
loop wherever the kernel does not run, and says why once.

Tolerance: the two forms differ only in the order of float32 sums (the
kernel multiplies the ``a`` and ``b`` halves apart, and chunks of ``f``
where it chunks), so float32 holds 1e-5 and bfloat16 2e-2 of the output's
size.
"""

import functools
import logging

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from nnstreamer_tpu.models import hybrid  # noqa: E402
from nnstreamer_tpu.models.hybrid import HybridConfig  # noqa: E402
from nnstreamer_tpu.ops import grouped_matmul as gm  # noqa: E402
from nnstreamer_tpu.serving import ContinuousBatchingEngine  # noqa: E402

D = 256
DTYPES = pytest.mark.parametrize(
    "dtype,tol", [(jnp.float32, 1e-5), (jnp.bfloat16, 2e-2)],
    ids=["f32", "bf16"])
#: held experts, f, tile, columns of f a grid step takes (None: the rule's,
#: whole here): the qwen cell's twin, the granite cell's in decode and in
#: its largest prefill
SHAPES = pytest.mark.parametrize("n_held,f,tile,f_chunk", [
    (32, 128, 8, None), (8, 256, 32, None), (8, 256, 32, 128),
    (8, 256, 128, None), (8, 256, 128, 128)],
    ids=["32x128_tile8", "8x256_tile32", "8x256_tile32_chunked",
         "8x256_tile128", "8x256_tile128_chunked"])


def _sorted_buffer(counts, tile, spare_tiles, rng):
    """What ``moe_ffn`` hands over for ``counts [n_held]`` rows an expert:
    ``(x [rows, D] float32, tile_expert, live tiles)``; padding rows zero,
    ``spare_tiles`` whole tiles past the live ones."""
    counts = np.asarray(counts)
    padded = -(-counts // tile) * tile
    ends = np.cumsum(padded)
    rows = int(ends[-1]) + spare_tiles * tile
    x = np.zeros((rows, D), np.float32)
    for e, (n, end, pad) in enumerate(zip(counts, ends, padded)):
        x[end - pad:end - pad + n] = rng.standard_normal((n, D))
    tile_expert = np.minimum(
        (ends[None, :] <= (np.arange(rows // tile) * tile)[:, None]).sum(1),
        len(counts) - 1)
    return x, jnp.asarray(tile_expert, jnp.int32), int(ends[-1]) // tile


def _counts(case, n_held, tile, rng):
    if case == "all_absent":
        return np.zeros(n_held, int)
    counts = rng.integers(1, tile + 1, n_held)
    counts[1] = 0                    # an expert nobody chose
    counts[2] = 2 * tile + 3         # one that spans three tiles
    counts[-1] = 0                   # and the last holds none either
    return counts


@pytest.mark.parametrize("case", ["spread", "all_absent"])
@DTYPES
@SHAPES
def test_kernel_equals_the_tile_loop_on_one_sorted_buffer(
        n_held, f, tile, f_chunk, dtype, tol, case):
    rng = np.random.default_rng([n_held, tile, f_chunk or 0])
    x, tile_expert, n_live = _sorted_buffer(
        _counts(case, n_held, tile, rng), tile, spare_tiles=3, rng=rng)
    x = jnp.asarray(x, dtype)
    w_in = jnp.asarray(rng.standard_normal((n_held, D, 2 * f)) * 0.06, dtype)
    w_out = jnp.asarray(rng.standard_normal((n_held, f, D)) * 0.06, dtype)
    want = gm.expert_tiles(x, tile_expert, jnp.int32(n_live), w_in, w_out,
                           tile, force="reference")
    if f_chunk is None:
        got = gm.expert_tiles(x, tile_expert, jnp.int32(n_live), w_in,
                              w_out, tile, force="pallas")
    else:
        got = gm._expert_tiles(
            x, tile_expert, jnp.int32(n_live), w_in, w_out, tile=tile,
            f_chunk=f_chunk, vmem_limit_bytes=gm.VMEM_BYTES, interpret=True)
    assert got.shape == want.shape == x.shape and got.dtype == jnp.float32
    want, got = np.asarray(want), np.asarray(got)
    if case == "all_absent":
        assert n_live == 0 and not got.any() and not want.any()
        return
    assert np.abs(want).max() > 0.5  # the comparison has something to hold
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=tol * np.abs(want).max())
    # rows past the live tiles are ZERO, not whatever the buffer held
    assert not got[n_live * tile:].any()


def test_rows_past_the_live_tiles_come_out_zero_whatever_x_holds():
    """Pairs on an absent expert read such a row under a gate of 0.0, and
    0 x NaN is NaN: the kernel may not leave them as they come."""
    rng = np.random.default_rng(3)
    x, tile_expert, n_live = _sorted_buffer([5, 0, 9, 2], 8, 4, rng)
    x[n_live * 8:] = np.nan
    w_in = jnp.asarray(rng.standard_normal((4, D, 256)), jnp.float32)
    w_out = jnp.asarray(rng.standard_normal((4, 128, D)), jnp.float32)
    got = np.asarray(gm.expert_tiles(
        jnp.asarray(x), tile_expert, jnp.int32(n_live), w_in, w_out, 8,
        force="pallas"))
    assert np.isfinite(got).all() and not got[n_live * 8:].any()
    assert got[:n_live * 8].any()


def test_block_rule_follows_the_shapes_and_the_vmem():
    """Whole experts where two of them fit half the VMEM (both cells on a
    v5e), chunks of ``f`` where they do not, never under one lane tile."""
    vmem = 128 * 1024 * 1024
    assert gm.expert_blocks(2048, 512, 8, jnp.bfloat16, vmem)[0] == 512
    assert gm.expert_blocks(4096, 768, 128, jnp.bfloat16, vmem)[0] == 768
    chunk, limit = gm.expert_blocks(4096, 768, 32, jnp.bfloat16, vmem // 2)
    assert chunk == 384 and limit <= vmem // 2
    assert gm.expert_blocks(4096, 768, 32, jnp.bfloat16, 1 << 20)[0] == 128
    for d, f, tile in [(2048, 512, 8), (4096, 768, 32), (4096, 768, 128)]:
        chunk, limit = gm.expert_blocks(d, f, tile, jnp.bfloat16, vmem)
        assert 2 * 3 * d * chunk * 2 < limit <= vmem


# -- inside moe_ffn: routing, gather back and counters round the kernel -------

def _moe_case(num_experts, held, f, dtype, seed=0):
    cfg = HybridConfig(
        vocab=64, d_model=D, layer_types=("mamba", "attention"),
        num_experts=num_experts, experts_per_token=4, expert_width=f,
        experts_held=held, dtype=dtype, param_dtype=dtype)
    rng = np.random.default_rng(seed)
    n_held = held[1] - held[0]
    lp = {"router": jnp.asarray(rng.standard_normal((D, num_experts)), dtype),
          "w_in": jnp.asarray(
              rng.standard_normal((n_held, D, 2 * f)) * 0.06, dtype),
          "w_out": jnp.asarray(
              rng.standard_normal((n_held, f, D)) * 0.06, dtype)}
    return cfg, lp, rng


def _moe_both(monkeypatch, cfg, lp, h, live):
    want = hybrid.moe_ffn(h, lp, cfg, live)
    with monkeypatch.context() as m:
        m.setattr(gm, "expert_tiles", functools.partial(
            gm.expert_tiles, force="pallas"))
        got = hybrid.moe_ffn(h, lp, cfg, live)
    return want, got


@pytest.mark.parametrize("lanes", ["all", "half", "none"])
@DTYPES
@pytest.mark.parametrize("num_experts,held,f,t,tile", [
    (64, (16, 48), 128, 16, 8),      # many small experts, 8-row tiles
    (16, (0, 8), 256, 64, 32),       # few large ones
    (16, (8, 16), 256, 256, 128),    # a prefill's tile
], ids=["64x128_tile8", "16x256_tile32", "16x256_tile128"])
def test_moe_ffn_with_the_kernel_equals_moe_ffn_with_the_loop(
        monkeypatch, num_experts, held, f, t, tile, dtype, tol, lanes):
    cfg, lp, rng = _moe_case(num_experts, held, f, dtype, seed=t)
    assert hybrid.expert_tile(cfg, t)[0] == tile
    h = jnp.asarray(rng.standard_normal((t, D)), dtype)
    live = {"all": None, "half": jnp.arange(t) % 2 == 0,
            "none": jnp.zeros(t, bool)}[lanes]
    (want, want_n), (got, got_n) = _moe_both(monkeypatch, cfg, lp, h, live)
    want, got = np.asarray(want), np.asarray(got)
    assert np.isfinite(got).all() and got.shape == (t, D)
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=tol * max(np.abs(want).max(), 1e-3))
    counts = {k: int(v) for k, v in got_n.items()}
    assert counts == {k: int(v) for k, v in want_n.items()}
    assert set(counts) == set(hybrid.COUNTERS)
    assert 0 <= counts["moe_tiles_live"] <= counts["moe_tiles_grid"] \
        == hybrid.expert_tile(cfg, t)[1] // tile
    if lanes == "none":
        # every pair absent: no live tile, and y finite and ZERO
        assert counts["moe_tiles_live"] == 0 == counts["moe_tokens_held"]
        assert not got.any() and not want.any()
    else:
        assert counts["moe_tiles_live"] >= counts["moe_experts_hit"] > 0
        assert np.abs(want).max() > 0.1
    if lanes == "half":
        assert not got[1::2].any()       # a masked lane gets nothing


def test_moe_ffn_stays_finite_when_no_pair_is_held(monkeypatch):
    """The NaN trap end to end: every choice falls on an absent expert,
    every pair reads row ``rows - 1`` of ``out`` under a gate of 0.0."""
    cfg, lp, rng = _moe_case(16, (0, 8), 128, jnp.float32)
    # the router prefers the absent half (ids 8-15) for every token
    lp["router"] = jnp.tile(jnp.where(jnp.arange(16) < 8, -1.0,
                                      jnp.arange(16.0)), (D, 1))
    h = jnp.asarray(rng.uniform(0.5, 1.5, (24, D)), jnp.float32)
    (want, want_n), (got, got_n) = _moe_both(monkeypatch, cfg, lp, h, None)
    assert int(got_n["moe_tokens_held"]) == 0 == int(got_n["moe_tiles_live"])
    assert int(got_n["moe_tokens_absent"]) == 24 * 4
    assert np.isfinite(np.asarray(got)).all() and not np.asarray(got).any()
    assert not np.asarray(want).any()


# -- which form runs, and how the engine says so ------------------------------

def _shapes(d=D, f=128, tile=8, dtype=jnp.bfloat16, n=4, rows=64):
    return (jax.ShapeDtypeStruct((rows, d), dtype),
            jax.ShapeDtypeStruct((n, d, 2 * f), dtype),
            jax.ShapeDtypeStruct((n, f, d), dtype), tile)


def _zeros(**kw):
    x, w_in, w_out, tile = _shapes(**kw)
    return (*(jnp.zeros(s.shape, s.dtype) for s in (x, w_in, w_out)), tile)


REJECTS = [("model width", dict(d=192)), ("expert width", dict(f=64)),
           ("neither bfloat16 nor float32", dict(dtype=jnp.float16)),
           ("sublanes", dict(tile=4))]


def test_auto_builds_the_tile_loop_off_a_tpu():
    assert jax.default_backend() == "cpu"
    assert gm._pallas_reject(*_shapes()) is None
    assert gm.expert_matmul_form(*_shapes()) == "tile_loop"
    x, w_in, w_out, tile = _zeros()
    text = jax.jit(functools.partial(gm.expert_tiles, tile=tile)).lower(
        x, jnp.zeros(8, jnp.int32), jnp.int32(3), w_in, w_out).as_text()
    assert "while" in text and "nns_expert_tiles" not in text


@pytest.mark.parametrize("why,kw", REJECTS,
                         ids=[w.split()[0] for w, _ in REJECTS])
def test_each_reject_reason_names_itself_once_in_the_log(
        monkeypatch, why, kw):
    x, w_in, w_out, tile = _zeros(**kw)
    args = (x, jnp.zeros(x.shape[0] // tile, jnp.int32), jnp.int32(2),
            w_in, w_out, tile)
    with pytest.raises(ValueError, match=why):
        gm.expert_tiles(*args, force="pallas")
    assert gm.expert_matmul_form(x, w_in, w_out, tile) == "tile_loop"
    # on a TPU auto gives way to the loop for such shapes, and says why
    records = []
    handler = logging.Handler()
    handler.emit = records.append
    gm.log.addHandler(handler)
    gm._log_reference_choice.cache_clear()
    monkeypatch.setattr(gm.jax, "default_backend", lambda: "tpu")
    try:
        assert gm.expert_matmul_form(x, w_in, w_out, tile) == "tile_loop"
        want = gm.expert_tiles(*args, force="reference")
        for _ in range(2):
            np.testing.assert_array_equal(
                np.asarray(gm.expert_tiles(*args)), np.asarray(want))
    finally:
        gm.log.removeHandler(handler)
        gm._log_reference_choice.cache_clear()
    said = [r.getMessage() for r in records]
    assert len(said) == 1 and why in said[0] and "tile loop" in said[0]


def test_engine_says_which_form_it_built_and_how_much_of_the_grid_worked():
    cfg = HybridConfig(
        vocab=97, d_model=128, layer_types=("mamba", "attention"),
        n_heads=4, n_kv_heads=2, head_dim=16, ssm_heads=8, ssm_head_dim=8,
        ssm_state=16, ssm_chunk=16, num_experts=8, experts_per_token=2,
        expert_width=128, shared_width=128, experts_held=(0, 4), max_seq=64,
        dtype=jnp.float32, param_dtype=jnp.float32)
    assert hybrid.expert_matmul(cfg, 2) == "tile_loop"
    eng = ContinuousBatchingEngine(
        cfg, hybrid.init_params(cfg, 1), max_streams=2,
        steps_per_dispatch=2, block_tokens=16).start()
    try:
        assert eng.expert_matmul == eng.stats["expert_matmul"] == "tile_loop"
        assert eng.stats["moe_tiles_live"] == 0 == eng.stats["moe_tiles_grid"]
        eng.generate([3, 5, 7, 11, 13], max_new_tokens=5, timeout=300)
    finally:
        eng.stop()
    stats = eng.stats
    tile, rows = hybrid.expert_tile(cfg, 2)
    assert stats["moe_tiles_grid"] == stats["moe_layer_steps"] * rows // tile
    # one live lane, two choices a layer and step, an 8-row tile each at most
    assert stats["moe_experts_hit"] == stats["moe_tiles_live"] \
        <= stats["moe_tiles_grid"]
    assert type(stats["moe_tiles_live"]) is int


def test_dense_engine_has_no_expert_matmul():
    from nnstreamer_tpu.models.transformer import (
        TransformerConfig,
        init_params,
    )

    cfg = TransformerConfig(vocab=97, d_model=64, n_heads=4, n_layers=2,
                            d_ff=128, max_seq=64, dtype=jnp.float32)
    eng = ContinuousBatchingEngine(cfg, init_params(cfg, 3), max_streams=2,
                                   steps_per_dispatch=4, block_tokens=8)
    assert eng.expert_matmul is None
    assert not {"expert_matmul", "moe_tiles_live",
                "moe_tiles_grid"} & set(eng.stats)
