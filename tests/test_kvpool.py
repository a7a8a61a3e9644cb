"""Paged KV-cache allocator (serving/kvpool.py): block bookkeeping,
arena invariants, and HBM accounting — the pool in isolation, before the
engine builds continuous batching on top of it."""

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
    # block i holds prompt slots [i*T, (i+1)*T)
    for i, b in enumerate(ids):
        np.testing.assert_array_equal(
            got[:, b], np.moveaxis(
                want[:, :, 0, i * T:(i + 1) * T], 1, 1).reshape(got[:, b].shape))
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
