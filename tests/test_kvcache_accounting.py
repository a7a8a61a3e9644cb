"""Prefix-cache entries are arena bytes: what the accountant and the pool
see of them.

The engine keeps keys and values in one place, the block arena, which the
pool registers ONCE with the HBM accountant under ``kvcache``
(test_kvpool.py). A prefix entry is a reference on blocks of that arena:
it adds no bytes, LRU turnover gives its blocks back to the free list,
and under block exhaustion entries are the first thing to go (the evict
rung of the pressure ladder, ``_evict_prefix_paged``), before a request
is deferred or a stream shed."""

import pytest

jax = pytest.importorskip("jax")

from nnstreamer_tpu.serving import ContinuousBatchingEngine  # noqa: E402
from nnstreamer_tpu.tensors import memory  # noqa: E402
from tests.test_serving import CFG, PARAMS, reference_greedy  # noqa: E402

T = 8


@pytest.fixture(autouse=True)
def _budget():
    memory.deactivate()
    budget = memory.activate(1 << 30)
    # the budget's counters are registry-global singletons; tests
    # elsewhere assert their ABSOLUTE values, so put back every tick
    # these tests add
    flat = [budget._m["evictions"], budget._m["prefetches"],
            *budget._m["pressure"].values()]
    saved = [c.value for c in flat]
    yield budget
    memory.deactivate()
    for c, v in zip(flat, saved):
        c._value = v


def _kv_bytes(budget):
    return budget.snapshot()["used_by_category"].get("kvcache", 0)


def _evict_rung(budget):
    return budget._m["pressure"]["evict"].value


# 17 tokens: two whole blocks of 8 and a tail, three blocks an entry
PROMPT_A = [7, 3, 9, 1, 4, 6, 2, 8, 5, 11, 13, 17, 19, 23, 29, 27, 25]
PROMPT_B = [13, 17, 19, 23, 29, 31, 37, 41, 2, 4, 6, 8, 10, 12, 14, 16, 18]
PROMPT_C = [2, 4, 6, 8, 10, 12, 14, 16, 18]


def engine(**kw):
    kw.setdefault("max_streams", 2)
    kw.setdefault("steps_per_dispatch", 4)
    kw.setdefault("temperature", 0.0)
    kw.setdefault("block_tokens", T)
    kw.setdefault("prefix_cache", 2)
    return ContinuousBatchingEngine(CFG, PARAMS, **kw).start()


def test_prefix_entry_adds_no_bytes_to_the_kvcache_category(_budget):
    eng = engine()
    try:
        arena = _kv_bytes(_budget)
        assert arena == eng._pool.nbytes > 0
        eng.generate(PROMPT_A, max_new_tokens=4, timeout=120)
        assert len(eng._prefix) == 1
        assert eng._pool.live_blocks() == 3    # the entry holds its blocks
        assert _kv_bytes(_budget) == arena     # ... of the same arena
        assert not [u for u in _budget.residency.snapshot()["units"]
                    if "prefix" in u["label"]]
    finally:
        eng.stop()


def test_lru_turnover_returns_the_evicted_entrys_blocks(_budget):
    eng = engine(prefix_cache=1)
    try:
        eng.generate(PROMPT_A, max_new_tokens=4, timeout=120)
        baseline = eng._pool.snapshot()        # one entry, no stream
        assert baseline["live_blocks"] == 3
        for p in (PROMPT_B, PROMPT_A, PROMPT_B):
            eng.generate(p, max_new_tokens=4, timeout=120)
            assert len(eng._prefix) == 1
            assert eng._pool.snapshot() == baseline
        assert eng.stats["prefix_hits"] == 0   # each turned the other out
    finally:
        eng.stop()


def test_block_exhaustion_evicts_prefix_entries_before_it_defers(_budget):
    eng = engine(kv_blocks=6, prefix_cache=4)
    try:
        for p in (PROMPT_A, PROMPT_B):
            eng.generate(p, max_new_tokens=4, timeout=120)
        assert eng._pool.snapshot()["free_blocks"] == 0  # two entries hold all
        before = _evict_rung(_budget)
        got = eng.generate(PROMPT_C, max_new_tokens=6, timeout=120)
        assert _evict_rung(_budget) == before + 1
        assert tuple(PROMPT_A) not in eng._prefix       # the LRU one went
        assert tuple(PROMPT_B) in eng._prefix
        assert eng.stats["kv_defers"] == 0 and eng.stats["kv_sheds"] == 0
    finally:
        eng.stop()
    assert got == reference_greedy(PROMPT_C, 6)


def test_recovery_drops_the_entries_with_the_arena_and_keeps_the_bytes(
        _budget):
    """A failed dispatch rebuilds the arena: entries point into the dead
    allocation map and go with it; the accountant sees the same bytes."""
    eng = engine()
    try:
        arena = _kv_bytes(_budget)
        eng.generate(PROMPT_A, max_new_tokens=4, timeout=120)
        real = eng._dispatch

        def failing(*args):
            eng._dispatch = real
            raise RuntimeError("injected device failure")

        eng._dispatch = failing
        s = eng.submit(PROMPT_B, max_new_tokens=6)
        s.result(timeout=120)
        assert s.finish_reason == "error: injected device failure"
        # the repeat is served by the recovered loop: a miss, and exact
        got = eng.generate(PROMPT_A, max_new_tokens=6, timeout=120)
        assert eng.stats["prefix_hits"] == 0
        assert list(eng._prefix) == [tuple(PROMPT_A)]
        assert eng._pool.live_blocks() == 3    # the new entry's, no other
        assert _kv_bytes(_budget) == arena
    finally:
        eng.stop()
    assert got == reference_greedy(PROMPT_A, 6)
