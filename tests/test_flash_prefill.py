"""Flash attention wired into prefill (round-5 VERDICT #4).

The Pallas kernel (ops/flash_attention.py) now backs the O(s²) prompt
pass: the serving engine's ``attention="auto"`` builds prefill with the
kernel (TPU, tileable shapes) and XLA attention elsewhere. Off-TPU the
kernel runs in interpret mode when forced — these tests pin exactness
against the materialized math, including a ≥2k-token prompt, so the
TPU fast path computes the same function the fallback does.
"""

import functools
import importlib
import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from nnstreamer_tpu.models.transformer import (
    TransformerConfig,
    build_decode_step,
    build_prefill,
    init_params,
)
from nnstreamer_tpu.ops import flash_attention

pytestmark = pytest.mark.filterwarnings("ignore::DeprecationWarning")

# the module: ``nnstreamer_tpu.ops.flash_attention`` the attribute is the
# function of that name
fa = importlib.import_module("nnstreamer_tpu.ops.flash_attention")


def _flash_forced(q, k, v):
    # force="pallas" runs the REAL kernel (interpret mode off-TPU), so
    # CPU CI exercises the exact program the TPU fast path compiles
    return flash_attention(q, k, v, causal=True, force="pallas")


CFG = TransformerConfig(vocab=256, d_model=64, n_heads=2, n_layers=2,
                        d_ff=128, max_seq=64, dtype=jnp.float32)


class TestPrefillExactness:
    def test_flash_prefill_matches_reference_math(self):
        params = init_params(CFG, seed=0)
        toks = jnp.asarray(
            np.random.default_rng(0).integers(1, CFG.vocab, (2, 32)),
            jnp.int32)
        ref_logits, ref_cache = build_prefill(CFG)(params, toks)
        fl_logits, fl_cache = build_prefill(
            CFG, attention_fn=_flash_forced)(params, toks)
        np.testing.assert_allclose(np.asarray(fl_logits),
                                   np.asarray(ref_logits),
                                   rtol=2e-4, atol=2e-4)
        for a, b in zip(jax.tree_util.tree_leaves(fl_cache),
                        jax.tree_util.tree_leaves(ref_cache)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-4)

    def test_flash_prefill_greedy_continuation_token_exact(self):
        """The whole point of the numeric contract: greedy decode seeded
        by a flash prefill emits the same tokens as one seeded by the
        reference prefill."""
        params = init_params(CFG, seed=1)
        toks = jnp.asarray(
            np.random.default_rng(1).integers(1, CFG.vocab, (1, 16)),
            jnp.int32)
        step = jax.jit(build_decode_step(CFG))

        def rollout(prefill_fn, n=12):
            logits, cache = prefill_fn(params, toks)
            last = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            pos = jnp.full((1,), toks.shape[1], jnp.int32)
            out = [int(last[0])]
            for _ in range(n - 1):
                logits, cache = step(params, last, cache, pos)
                last = jnp.argmax(logits[:, :], axis=-1).astype(jnp.int32)
                pos = pos + 1
                out.append(int(last[0]))
            return out

        ref = rollout(jax.jit(build_prefill(CFG)))
        fl = rollout(jax.jit(build_prefill(CFG,
                                           attention_fn=_flash_forced)))
        assert fl == ref

    def test_flash_prefill_right_padded_lengths(self):
        """Bucket padding contract survives the kernel: padded rows'
        logits come from the true last position and match the unpadded
        prefill."""
        params = init_params(CFG, seed=2)
        rng = np.random.default_rng(2)
        true = rng.integers(1, CFG.vocab, (1, 11))
        padded = np.zeros((1, 16), np.int64)
        padded[:, :11] = true
        # s=11 does not tile — the reference path scores the exact
        # prompt; the PADDED s=16 call runs through the kernel
        exact_logits, _ = build_prefill(CFG)(
            params, jnp.asarray(true, jnp.int32))
        pad_logits, _ = build_prefill(CFG, attention_fn=_flash_forced)(
            params, jnp.asarray(padded, jnp.int32),
            jnp.asarray([11], jnp.int32))
        np.testing.assert_allclose(np.asarray(pad_logits),
                                   np.asarray(exact_logits),
                                   rtol=2e-4, atol=2e-4)


class TestLongPrompt:
    def test_2k_token_prefill_through_the_kernel(self):
        """≥2k-token prompt through the REAL kernel (interpret off-TPU):
        the long-context path the kernel exists for, verified against
        materialized attention."""
        cfg = TransformerConfig(vocab=128, d_model=64, n_heads=2,
                                n_layers=1, d_ff=64, max_seq=2048,
                                dtype=jnp.float32)
        params = init_params(cfg, seed=3)
        toks = jnp.asarray(
            np.random.default_rng(3).integers(1, cfg.vocab, (1, 2048)),
            jnp.int32)
        fl_logits, fl_cache = build_prefill(
            cfg, attention_fn=_flash_forced)(params, toks)
        ref_logits, ref_cache = build_prefill(cfg)(params, toks)
        np.testing.assert_allclose(np.asarray(fl_logits),
                                   np.asarray(ref_logits),
                                   rtol=5e-4, atol=5e-4)
        ck_fl = jax.tree_util.tree_leaves(fl_cache)[0]
        ck_ref = jax.tree_util.tree_leaves(ref_cache)[0]
        np.testing.assert_allclose(np.asarray(ck_fl), np.asarray(ck_ref),
                                   rtol=5e-4, atol=5e-4)


class TestEngineAuto:
    def test_engine_auto_equals_reference_attention(self):
        """attention='auto' (kernel on TPU, XLA fallback here) generates
        the same tokens as attention='reference'."""
        from nnstreamer_tpu.serving import ContinuousBatchingEngine

        params = init_params(CFG, seed=4)
        prompt = np.random.default_rng(4).integers(
            1, CFG.vocab, 12).tolist()
        outs = {}
        for mode in ("auto", "reference"):
            eng = ContinuousBatchingEngine(
                CFG, params, max_streams=2, steps_per_dispatch=4,
                temperature=0.0, attention=mode).start()
            try:
                outs[mode] = eng.generate(prompt, max_new_tokens=16,
                                          timeout=120)
            finally:
                eng.stop()
        assert outs["auto"] == outs["reference"]

    def test_engine_rejects_unknown_attention(self):
        from nnstreamer_tpu.serving import ContinuousBatchingEngine

        with pytest.raises(ValueError, match="attention"):
            ContinuousBatchingEngine(CFG, init_params(CFG),
                                     attention="fast")


class TestBand:
    """``window=``: the band mask ``0 <= i - j < window``, its dead key
    tiles skipped by the grid."""

    @staticmethod
    def _qkv(s, hq=4, hk=2, d=16, seed=0, dtype=jnp.float32):
        keys = jax.random.split(jax.random.PRNGKey(seed), 3)
        return [jax.random.normal(k, (2, s, h, d), dtype)
                for k, h in zip(keys, (hq, hk, hk))]

    @staticmethod
    def _masked(q, k, v, window):
        """The band written out over the whole score matrix."""
        group = q.shape[2] // k.shape[2]
        k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * q.shape[-1] ** -0.5
        i = jnp.arange(q.shape[1])[:, None]
        j = jnp.arange(k.shape[1])[None, :]
        p = jax.nn.softmax(jnp.where((i >= j) & (i - j < window), s, -1e30),
                           axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v)

    @pytest.mark.parametrize("s,window,bq,bk", [
        (64, 1, 8, 8), (64, 8, 16, 16), (64, 20, 16, 8), (128, 33, 32, 16),
        (48, 16, 16, 16), (64, 17, 8, 32), (64, 64, 16, 16),
        (64, 100, 16, 16)],
        ids=lambda x: str(x))
    def test_skipped_tiles_give_the_masked_result(self, s, window, bq, bk):
        q, k, v = self._qkv(s, seed=s + window)
        want = self._masked(q, k, v, window)
        ref = flash_attention(q, k, v, force="reference", window=window)
        got = flash_attention(q, k, v, block_q=bq, block_k=bk,
                              force="pallas", window=window)
        np.testing.assert_allclose(np.asarray(ref), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)
        if window >= s:   # a window wider than the prompt is plain causal
            np.testing.assert_allclose(
                np.asarray(got), np.asarray(flash_attention(
                    q, k, v, block_q=bq, block_k=bk, force="pallas")),
                rtol=1e-6, atol=1e-6)

    def test_the_grid_has_the_bands_tiles_only(self):
        """At 16 tiles of keys and a window of two tiles, a q-tile's k axis
        has 4 steps (the band can touch 3 tiles; one spare), not 16."""
        from nnstreamer_tpu.ops.flash_attention import _flash_bhsd

        q, k, v = (x.swapaxes(1, 2) for x in self._qkv(256))

        def grid_of(**kw):
            return str(jax.make_jaxpr(lambda a, b, c: _flash_bhsd(
                a, b, c, True, 16, 16, interpret=True, **kw))(q, k, v))

        band = grid_of(window=32)
        assert "nns_band_flash_prefill" in band and "(2, 4, 16, 4)" in band
        assert "(2, 4, 16, 16)" in grid_of()

    def test_bfloat16_band_stays_near_the_float32_one(self):
        q, k, v = self._qkv(128, dtype=jnp.bfloat16, seed=3)
        want = self._masked(*(x.astype(jnp.float32) for x in (q, k, v)), 40)
        got = flash_attention(q, k, v, block_q=32, block_k=32,
                              force="pallas", window=40)
        assert got.dtype == jnp.bfloat16
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want), rtol=3e-2, atol=3e-2)

    @pytest.mark.parametrize("kw", [dict(window=0), dict(window=-3),
                                    dict(window=8, causal=False)])
    def test_a_window_goes_with_causal_and_is_positive(self, kw):
        q, k, v = self._qkv(32)
        with pytest.raises(ValueError, match="window"):
            flash_attention(q, k, v, **kw)


class TestTilePlan:
    """The tiles come from the shapes (``tile_plan``), a live tile that no
    edge of the mask crosses takes the step without a mask, and the dead
    steps of the plain causal grid fetch nothing (PR 38)."""

    _qkv = staticmethod(TestBand._qkv)
    _masked = staticmethod(TestBand._masked)

    @pytest.mark.parametrize("s,window,bq,bk", [
        (128, 24, 16, 64),      # a key tile wider than the window
        (128, 40, 32, 64),      # the window's edge inside a tile
        (128, 64, 32, 64),      # ... on a tile boundary
        (128, 33, 64, 16),      # q tiles wider than k tiles
        (96, 33, 8, 48),
        (128, 200, 16, 64),     # window >= s
        (128, 128, 32, 128),    # one key tile, the window the prompt
        (192, 64, 64, 192)],
        ids=lambda x: str(x))
    def test_wide_key_tiles_give_the_masked_result(self, s, window, bq, bk):
        q, k, v = self._qkv(s, seed=s + window + bk)
        got = flash_attention(q, k, v, block_q=bq, block_k=bk,
                              force="pallas", window=window)
        np.testing.assert_allclose(np.asarray(got),
                                   np.asarray(self._masked(q, k, v, window)),
                                   rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("s,window", [
        (3072, None), (3072, 2100), (3072, 2048), (1280, None), (1280, 1280),
        (2048, 4096)],
        ids=lambda x: str(x))
    def test_the_plans_tiles_give_the_masked_result(self, s, window):
        """No blocks given: 1024 x 1024 tiles (640 x 640 at 1280), masked
        and unmasked steps both taken."""
        q, k, v = (x[:1] for x in self._qkv(s, hq=2, hk=1, seed=s))
        plan = fa.tile_plan(s, s, 16, 16, window, q.dtype)
        assert plan.block_q == plan.block_k == (640 if s == 1280 else 1024)
        assert plan.masked >= 2 and plan.unmasked >= 1
        got = flash_attention(q, k, v, force="pallas", window=window)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(self._masked(q, k, v, window or s)),
            rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("hq,hk,d,dv,s", [
        (4, 2, 192, 128, 1280), (4, 2, 64, 64, 2048), (4, 1, 256, 256, 1280),
        (2, 2, 192, 128, 96), (4, 2, 64, 64, 64), (4, 1, 256, 256, 128)],
        ids=lambda x: str(x))
    def test_grouped_heads_and_other_widths_under_the_plan(self, hq, hk, d,
                                                           dv, s):
        keys = jax.random.split(jax.random.PRNGKey(d + s), 3)
        q, k, v = (jax.random.normal(key, (1, s, h, w), jnp.float32)
                   for key, h, w in zip(keys, (hq, hk, hk), (d, d, dv)))
        got = flash_attention(q, k, v, force="pallas")
        assert got.shape == (1, s, hq, dv)
        np.testing.assert_allclose(
            np.asarray(got),
            np.asarray(flash_attention(q, k, v, force="reference")),
            rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("window", [None, 40, 64])
    def test_both_steps_equal_the_all_masked_kernel(self, monkeypatch,
                                                    window):
        """With every live tile sent through the masked step the kernel
        gives the same numbers: the unmasked step leaves out only what
        changes nothing."""
        plan = fa.tile_plan(128, 128, 16, 16, window, jnp.float32, 16, 16)
        assert plan.masked >= 4 and plan.unmasked >= 4
        q, k, v = (x.swapaxes(1, 2) for x in self._qkv(128, seed=9))
        run = functools.partial(
            fa._flash_bhsd.__wrapped__, q, k, v, True, 16, 16,
            interpret=True, window=window)
        both = run()
        monkeypatch.setattr(fa, "_edge_crosses",
                            lambda *a: jnp.bool_(True))
        np.testing.assert_array_equal(np.asarray(run()), np.asarray(both))

    @pytest.mark.parametrize("bq,bk,nq,nk", [
        (16, 16, 8, 8), (16, 64, 8, 2), (64, 16, 2, 8), (32, 128, 4, 1)])
    def test_dead_steps_of_the_causal_grid_name_the_last_live_tile(
            self, bq, bk, nq, nk):
        """The index map of keys and values: a live step names its own
        tile, every step past the diagonal's the diagonal's again, so
        nothing is fetched for it."""
        q, k, v = (x.swapaxes(1, 2) for x in self._qkv(128))
        program = jax.make_jaxpr(lambda a, b, c: fa._flash_bhsd(
            a, b, c, True, bq, bk, interpret=True))(q, k, v)
        call, = program.eqns[0].params["jaxpr"].eqns
        mapping = call.params["grid_mapping"]
        assert mapping.grid == (2, 4, nq, nk)
        for keys_or_values in mapping.block_mappings[1:3]:
            index_map = keys_or_values.index_map_jaxpr
            for iq in range(nq):
                last = ((iq + 1) * bq - 1) // bk
                named = [int(jax.core.eval_jaxpr(
                    index_map.jaxpr, index_map.consts, 1, 3, iq, ik)[2])
                    for ik in range(nk)]
                assert named == [min(ik, last) for ik in range(nk)]

    @pytest.mark.parametrize("s,window,bq,bk", [
        (12288, 4096, None, None), (12288, None, None, None),
        (6144, 4096, None, None), (12288, 4096, 256, 256),
        (12288, None, 512, 2048), (3072, None, None, None),
        (1536, None, None, None), (1536, 1000, 256, 768)],
        ids=lambda x: str(x))
    def test_the_plan_counts_what_the_mask_says(self, s, window, bq, bk):
        """Live, masked and unmasked steps against a count made over the
        mask itself, tile by tile."""
        plan = fa.tile_plan(s, s, 128, 128, window, jnp.bfloat16, bq, bk)
        if bq is None:
            want = 768 if s == 1536 else 1024
            assert (plan.block_q, plan.block_k) == (want, want)
        i = np.arange(s)[:, None]
        j = np.arange(s)[None, :]
        seen = (i >= j) if window is None else (i >= j) & (i - j < window)
        tiles = seen.reshape(s // plan.block_q, plan.block_q,
                             s // plan.block_k, plan.block_k)
        live = tiles.any(axis=(1, 3))
        whole = tiles.all(axis=(1, 3))
        assert plan.unmasked == int(whole.sum())
        assert plan.masked == int((live & ~whole).sum())
        assert plan.k_steps >= int(live.sum(axis=1).max())
        assert plan.dead == plan.q_tiles * plan.k_steps - int(live.sum())

    def test_the_plan_is_logged_once_a_shape(self):
        records = []
        handler = logging.Handler()
        handler.emit = records.append
        level = fa.log.level
        fa.log.addHandler(handler)
        fa.log.setLevel(logging.INFO)
        fa._log_tile_plan.cache_clear()
        try:
            q, k, v = self._qkv(64)
            for _ in range(2):
                flash_attention(q, k, v, block_q=16, block_k=32,
                                force="pallas", window=24)
        finally:
            fa.log.removeHandler(handler)
            fa.log.setLevel(level)
            fa._log_tile_plan.cache_clear()
        said = [r.getMessage() for r in records]
        plan = fa.tile_plan(64, 64, 16, 16, 24, jnp.dtype(jnp.float32), 16,
                            32)
        assert len(said) == 1 and "tiles 16 x 32" in said[0]
        assert (f"{plan.unmasked} unmasked, {plan.masked} masked, "
                f"{plan.dead} dead") in said[0]

    def test_float32_heads_of_256_get_half_the_rows(self):
        """The one shape whose 1024 x 1024 step Mosaic refused for VMEM."""
        plan = fa.tile_plan(2048, 2048, 256, 256, None, jnp.float32)
        assert (plan.block_q, plan.block_k) == (512, 1024)
        assert fa.tile_plan(2048, 2048, 256, 256, None,
                         jnp.bfloat16)[:2] == (1024, 1024)
