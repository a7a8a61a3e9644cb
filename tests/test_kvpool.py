"""Paged KV-cache allocator (serving/kvpool.py): block bookkeeping,
arena invariants, and HBM accounting — the pool in isolation, before the
engine builds continuous batching on top of it."""

import dataclasses

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from nnstreamer_tpu.models.transformer import (  # noqa: E402
    TransformerConfig,
    build_prefill,
    init_params,
)
from nnstreamer_tpu.serving import kvpool  # noqa: E402
from nnstreamer_tpu.tensors import memory  # noqa: E402

CFG = TransformerConfig(vocab=97, d_model=64, n_heads=4, n_layers=2,
                        d_ff=128, max_seq=64, dtype=jnp.float32)
PARAMS = init_params(CFG, seed=3)
T = 8


@pytest.fixture(autouse=True)
def _no_budget():
    memory.deactivate()
    yield
    memory.deactivate()


def test_alloc_is_all_or_nothing_and_lifo():
    pool = kvpool.BlockPool(CFG, 4, T)
    ids = pool.alloc(3)
    assert len(ids) == 3 and pool.free_blocks == 1
    assert pool.alloc(2) is None          # 1 free: all-or-nothing
    assert pool.free_blocks == 1          # failed alloc took nothing
    pool.release(ids)
    assert pool.free_blocks == 4 and pool.live_blocks() == 0
    # LIFO recycling: the most recently released block comes back first
    again = pool.alloc(1)
    assert again[0] == ids[-1]


def test_refcounts_guard_shared_blocks():
    pool = kvpool.BlockPool(CFG, 4, T)
    ids = pool.alloc(2)
    pool.retain(ids)                      # second owner (COW prefix)
    pool.release(ids)
    assert pool.live_blocks() == 2        # still held by the retainer
    pool.release(ids)
    assert pool.live_blocks() == 0
    with pytest.raises(RuntimeError):
        pool.release(ids)                 # over-release
    with pytest.raises(RuntimeError):
        pool.retain(ids)                  # retain of a dead block


def test_scatter_prefill_and_zero_block_stay_exact():
    pool = kvpool.BlockPool(CFG, 6, T)
    prefill = jax.jit(build_prefill(CFG, CFG.max_seq))
    toks = jnp.asarray(
        np.random.default_rng(0).integers(1, CFG.vocab, (1, 16)), jnp.int32)
    _, cache1 = prefill(PARAMS, toks)
    want = np.asarray(jax.tree_util.tree_leaves(cache1)[0])  # [L,2,1,S,...]
    ids = pool.alloc(2)
    pool.scatter_prefill(cache1, ids)
    got = np.asarray(jax.tree_util.tree_leaves(pool.arena)[0])
    # block i holds prompt slots [i*T, (i+1)*T): 4 heads, so heads-major
    assert pool.heads_major and got.shape[3:5] == (CFG.n_heads, T)
    for i, b in enumerate(ids):
        np.testing.assert_array_equal(
            got[:, b], np.swapaxes(want[:, :, 0, i * T:(i + 1) * T], 2, 3))
    # the permanent zero block is untouched (sentinel writes dropped)
    assert not np.any(got[:, pool.num_blocks])


def test_copy_block_duplicates_one_block():
    pool = kvpool.BlockPool(CFG, 6, T)
    prefill = jax.jit(build_prefill(CFG, CFG.max_seq))
    toks = jnp.asarray(
        np.random.default_rng(1).integers(1, CFG.vocab, (1, 16)), jnp.int32)
    _, cache1 = prefill(PARAMS, toks)
    src_dst = pool.alloc(2)
    pool.scatter_prefill(cache1, src_dst[:1])
    pool.copy_block(src_dst[0], src_dst[1])
    for leaf in jax.tree_util.tree_leaves(pool.arena):
        a = np.asarray(leaf)
        np.testing.assert_array_equal(a[:, src_dst[0]], a[:, src_dst[1]])


def test_reset_returns_every_block():
    pool = kvpool.BlockPool(CFG, 4, T)
    pool.alloc(3)
    pool.reset()
    assert pool.free_blocks == 4 and pool.live_blocks() == 0
    snap = pool.snapshot()
    assert snap["num_blocks"] == 4 and snap["free_blocks"] == 4
    assert snap["nbytes"] == pool.nbytes > 0


def test_arena_registers_kvcache_bytes():
    budget = memory.activate(1 << 30)
    pool = kvpool.BlockPool(CFG, 4, T)
    assert budget.snapshot()["used_by_category"].get("kvcache", 0) == \
        pool.nbytes
    del pool
    import gc

    gc.collect()
    assert budget.snapshot()["used_by_category"].get("kvcache", 0) == 0


def test_bad_sizes_rejected():
    with pytest.raises(ValueError):
        kvpool.BlockPool(CFG, 0, T)
    with pytest.raises(ValueError):
        kvpool.BlockPool(CFG, 4, 0)


# -- one code path for both token entries ------------------------------------

def _latent_case():
    from nnstreamer_tpu.models.mla import MLAConfig, build_prefill as mla_pre

    cfg = MLAConfig(
        vocab=97, d_model=32, n_layers=2, n_heads=2, qk_nope_dim=8,
        qk_rope_dim=4, v_head_dim=8, kv_lora_rank=12, dense_width=16,
        num_experts=4, experts_per_token=2, expert_width=8, shared_width=8,
        experts_held=(0, 4), max_seq=64, dtype=jnp.float32,
        param_dtype=jnp.float32)
    # 12 + 4 columns, held at the next multiple of 128 lanes
    return cfg, cfg.family.init_params(cfg, 3), mla_pre, (2, 7, 1, T, 128)


def _dense_case(heads=4):
    """``heads`` key-value heads of ``64 / heads``: under 8 the arena is
    heads-major ``[.., heads, T, dh]``, from 8 on token-major."""
    def case():
        cfg = dataclasses.replace(CFG, n_heads=heads)
        dh = cfg.head_dim
        return cfg, init_params(cfg, seed=3), build_prefill, \
            ((2, 7, 2, heads, T, dh) if heads < 8 else (2, 7, 2, T, heads, dh))
    return case


def _blocks_as_tokens(pool, blocks):
    """``[.., T, heads, dh]`` of arena blocks in either order."""
    return np.swapaxes(blocks, -3, -2) if pool.heads_major else blocks


@pytest.mark.parametrize("case", [
    _dense_case(4), _latent_case, _dense_case(1), _dense_case(2),
    _dense_case(8), _dense_case(16)],
    ids=["two_parts_of_heads", "one_latent_row", "one_head_heads_major",
         "two_heads_heads_major", "eight_heads_token_major",
         "sixteen_heads_token_major"])
def test_one_pool_serves_both_token_entries(case):
    """Alloc, retain, release, the prefill's scatter, the copy-on-write
    block copy, the sentinel and the read of a stream's rows: the same
    ``BlockPool`` code whether a token's entry is keys and values per head
    or one latent row (``ModelFamily.kv_entry``), and whether a block's
    rows lie heads-major (fewer than 8 heads) or token-major."""
    cfg, params, prefill_of, shape = case()
    pool = kvpool.BlockPool(cfg, 6, T)
    leaf = jax.tree_util.tree_leaves(pool.arena)[0]
    assert leaf.shape == shape and pool.nbytes == leaf.nbytes
    layers, parts, entry = cfg.family.kv_entry(cfg)
    assert pool.heads_major == (len(entry) == 2 and entry[0] < 8)
    assert pool.snapshot()["heads_major"] == int(pool.heads_major)
    assert _blocks_as_tokens(pool, np.asarray(leaf)).shape \
        == (layers, 7, parts, T) + entry

    ids = pool.alloc(3)
    pool.retain(ids[:1])
    pool.release(ids)
    assert pool.live_blocks() == 1 and pool.free_blocks == 5
    pool.release(ids[:1])
    assert pool.live_blocks() == 0
    assert pool.alloc(7) is None                    # all or nothing

    toks = jnp.asarray(
        np.random.default_rng(0).integers(1, cfg.vocab, (1, 16)), jnp.int32)
    _, cache1 = jax.jit(prefill_of(cfg, cfg.max_seq))(params, toks)
    want = np.asarray(jax.tree_util.tree_leaves(cache1)[0])
    assert want.shape[:3] == (layers, parts, 1)     # [L, parts, 1, S, ...]
    ids = pool.alloc(3)
    pool.scatter_prefill(cache1, ids[:2])           # the table's third
    got = np.asarray(jax.tree_util.tree_leaves(pool.arena)[0])
    for i, b in enumerate(ids[:2]):                 # entry: the sentinel
        np.testing.assert_array_equal(
            _blocks_as_tokens(pool, got[:, b]),
            want[:, :, 0, i * T:(i + 1) * T])
    assert not got[:, ids[2]].any()                 # never written
    assert not got[:, pool.num_blocks].any()        # the zero block: zeros
    # a stream's rows, in table order, are the prefill's
    rows = pool.stream_rows(ids[:2], 13)
    np.testing.assert_array_equal(rows, want[:, :, 0, :13])
    # copy-on-write: one block duplicated across every layer
    pool.copy_block(ids[1], ids[2])
    got = np.asarray(jax.tree_util.tree_leaves(pool.arena)[0])
    np.testing.assert_array_equal(got[:, ids[2]], got[:, ids[1]])
    # a table entry at the sentinel reads the zero block: exact zeros
    from nnstreamer_tpu.models.transformer import _paged_gather

    bt = jnp.asarray([[ids[0], pool.SENTINEL]], jnp.int32)
    g = np.asarray(_paged_gather(pool.arena, 0, bt, pool.heads_major))
    assert g.shape == (1, parts, 2 * T) + entry and not g[:, :, T:].any()
    np.testing.assert_array_equal(g[0, :, :T], want[0, :, 0, :T])
    pool.reset()
    assert pool.free_blocks == 6 and not np.asarray(pool.arena).any()
    assert pool.lane_state(0) == {}


# -- the order of the rows inside a block: one rule, stated, never inferred ---

@pytest.mark.parametrize("entry,heads_major,tail", [
    ((1, 128), True, (1, 16, 128)),
    ((2, 256), True, (2, 16, 256)),
    ((4, 128), True, (4, 16, 128)),
    ((8, 128), False, (16, 8, 128)),
    ((10, 128), True, (10, 16, 128)),
    ((16, 128), False, (16, 16, 128)),
    ((640,), False, (16, 640)),
], ids=["1_head", "2_heads", "4_heads", "8_heads", "10_heads", "16_heads",
        "latent_row"])
@pytest.mark.parametrize("codec", ["raw", "int8"])
def test_the_rule_fewer_heads_than_a_tiles_rows_are_heads_major(
        entry, heads_major, tail, codec):
    """``kv_heads_major`` reads the entry's shape and nothing else; the
    codec it is handed to shapes both leaves by it and SAYS which order
    it made (``[2, 16, dh]`` is 2 heads of 16 tokens and 2 tokens of 16
    heads: nobody can read it back)."""
    from nnstreamer_tpu.models import transformer as tr

    assert tr.kv_heads_major(entry) is heads_major
    made = (tr._RawKVCodec(jnp.bfloat16, heads_major) if codec == "raw"
            else tr._Int8KVCodec(heads_major))
    assert made.heads_major is heads_major
    arena = jax.eval_shape(lambda: made.paged_init(
        3, 9, 16, *entry, parts=len(entry)))
    if codec == "raw":
        assert arena.shape == (3, 9, len(entry)) + tail
    else:
        assert arena["q"].shape == (3, 9, len(entry)) + tail
        assert arena["scale"].shape == (3, 9, len(entry)) + tail[:-1]
    # the default is today's order: a codec made without the word
    assert not tr._RawKVCodec(jnp.bfloat16).heads_major
    assert not tr._Int8KVCodec().heads_major


@pytest.mark.parametrize("name,driver,builder,shape,heads_major", [
    ("pythia_1p4b", "lm", "transformer_config",
     (24, 1025, 2, 16, 16, 128), False),
    ("granite_4p0_h_small_ep2", "lm_hybrid", "hybrid_config",
     (1, 4097, 2, 16, 8, 128), False),
    ("qwen3_next_80b_a3b_ep2", "lm_qwen3_next", "qwen3_next_config",
     (1, 16385, 2, 2, 16, 256), True),
    ("deepseek_v2_lite_ep2", "lm_deepseek_v2", "deepseek_v2_config",
     (14, 16385, 1, 16, 640), False),
])
def test_the_registered_configurations_arenas(name, driver, builder, shape,
                                              heads_major):
    """The arena each registered cell's pool makes, as the exact shape
    (shapes only: nothing that size is allocated here): three stay what
    they were before PR 34, Qwen3-Next's two key-value heads go first."""
    import importlib
    import json
    import os

    from nnstreamer_tpu.models.transformer import _kv_codec

    root = os.path.join(os.path.dirname(__file__), "..", "benchmark")
    with open(os.path.join(root, "configs", name + ".json")) as f:
        config = json.load(f)
    cfg = getattr(importlib.import_module("benchmark.drivers." + driver),
                  builder)(config)
    layers, parts, entry = cfg.family.kv_entry(cfg)
    codec = _kv_codec(cfg, None)
    assert codec.heads_major is heads_major
    T = config["block_tokens"]
    ntot = config["max_streams"] * (cfg.max_seq // T) + 1
    arena = jax.eval_shape(lambda: codec.paged_init(
        layers, ntot, T, *entry, parts=parts))
    assert arena.shape == shape and arena.dtype == jnp.bfloat16


@pytest.mark.parametrize("heads,spec", [
    (4, (None, "dp", None, "tp", None, None)),
    (8, (None, "dp", None, None, "tp", None))],
    ids=["heads_major", "token_major"])
def test_tp_shards_the_head_axis_wherever_the_order_puts_it(heads, spec):
    from nnstreamer_tpu.parallel.mesh import make_mesh

    cfg = dataclasses.replace(CFG, n_heads=heads)
    pool = kvpool.BlockPool(cfg, 7, T, mesh=make_mesh([("dp", 2),
                                                       ("tp", 2)]))
    assert tuple(pool.arena.sharding.spec) == spec
    assert pool.arena.shape[spec.index("tp")] == heads


# -- a second arena for window layers -----------------------------------------

def _window_cfg(kv_heads=2):
    from nnstreamer_tpu.models.afmoe import FULL, SLIDING, AfmoeConfig

    return AfmoeConfig(
        vocab=97, d_model=64, layer_types=(SLIDING, FULL, SLIDING, SLIDING),
        n_heads=8, n_kv_heads=kv_heads, head_dim=16, window=12,
        num_experts=8, experts_per_token=2, expert_width=16, shared_width=16,
        experts_held=(0, 8), max_seq=64, dtype=jnp.float32,
        param_dtype=jnp.float32)


@pytest.mark.parametrize("kv_heads,heads_major", [(2, True), (8, False)])
def test_window_arena_has_its_own_blocks_sentinel_and_zero_block(
        kv_heads, heads_major):
    cfg = _window_cfg(kv_heads)
    budget = memory.activate(1 << 30)
    pool = kvpool.BlockPool(cfg, 10, T, window_blocks=6)
    block = (kv_heads, T) if heads_major else (T, kv_heads)
    assert pool.heads_major == heads_major
    assert pool.arena["kv"].shape == (1, 11, 2) + block + (16,)
    assert pool.arena["win"].shape == (3, 7, 2) + block + (16,)
    assert (pool.SENTINEL, pool.win.SENTINEL) == (11, 7)
    # two free lists: a block id means something in its own arena only
    a, w = pool.alloc(10), pool.win.alloc(6)
    assert sorted(a) == list(range(10)) and sorted(w) == list(range(6))
    assert pool.alloc(1) is None and pool.win.alloc(1) is None
    pool.win.release(w[:2])
    assert (pool.free_blocks, pool.win.free_blocks) == (0, 2)
    with pytest.raises(RuntimeError, match="BlockPool.win.release"):
        pool.win.release(w[:1])
    snap = pool.snapshot()
    assert (snap["window_blocks"], snap["window_blocks_live"]) == (6, 4)
    assert (snap["num_blocks"], snap["live_blocks"]) == (10, 10)
    # bytes and the accountant's kvcache category cover both arenas
    both = sum(int(x.size) * 4 for x in jax.tree_util.tree_leaves(pool.arena))
    assert pool.nbytes == snap["nbytes"] == both
    assert snap["window_bytes"] == pool.window_bytes \
        == int(pool.arena["win"].size) * 4
    assert budget.snapshot()["used_by_category"]["kvcache"] == both
    pool.reset()
    assert (pool.free_blocks, pool.win.free_blocks) == (10, 6)
    assert "window_blocks" not in kvpool.BlockPool(CFG, 4, T).snapshot()


def test_window_arena_needs_its_size_and_no_mesh():
    with pytest.raises(ValueError, match="window_blocks"):
        kvpool.BlockPool(_window_cfg(), 10, T)
    with pytest.raises(ValueError, match="num_blocks must be positive"):
        kvpool.BlockPool(_window_cfg(), 10, T, window_blocks=-1)


@pytest.mark.parametrize("kv_heads", [2, 8])
def test_scatter_hands_the_last_blocks_to_the_window_arena_and_all_to_the_full(
        kv_heads):
    """A prompt of 29 tokens under a window of 12: the first decode step
    reads positions 18.. of a window layer, so the window arena is handed
    the prompt's blocks 2 and 3 (of 8 tokens) and the full arena all four;
    ``stream_rows`` reads either back in the order of the tokens."""
    cfg = _window_cfg(kv_heads)
    pool = kvpool.BlockPool(cfg, 10, T, window_blocks=6)
    rng = np.random.default_rng(kv_heads)
    cache1 = {"kv": jnp.asarray(rng.standard_normal(
                  (1, 2, 1, 32, kv_heads, 16)), jnp.float32),
              "win": jnp.asarray(rng.standard_normal(
                  (3, 2, 1, 32, kv_heads, 16)), jnp.float32)}
    blocks, wblocks = pool.alloc(5), pool.win.alloc(3)  # + the decode block
    first = (29 - 12 + 1) // T
    assert first == 2
    pool.scatter_prefill(cache1, blocks[:4], window_ids=wblocks[:2],
                         window_first=first)
    np.testing.assert_array_equal(
        pool.stream_rows(blocks[:4], 29), np.asarray(cache1["kv"])[:, :, 0, :29])
    np.testing.assert_array_equal(
        pool.stream_rows(wblocks[:2], 29 - first * T, window=True),
        np.asarray(cache1["win"])[:, :, 0, first * T:29])
    # nothing else of the window arena was written: its other blocks and
    # both zero blocks are zeros still
    rest = [b for b in range(7) if b not in wblocks[:2]]
    assert not np.asarray(pool.arena["win"])[:, rest].any()
    assert not np.asarray(pool.arena["kv"])[:, 10].any()
