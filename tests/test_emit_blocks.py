"""A dispatch's tokens reach each stream as ONE block (serving/engine.py
``_hand_over``, ``GenerationStream._emit_block``).

The bar: what a stream receives, how it finishes, what the engine counts
and what goes back to the pool are what a loop over the tokens one at a
time gives (written out here as ``per_token``); a stream that goes on
gets its block once the next dispatch is queued (``_wake_streams``), one
that ends before its end mark; a reader still sees one token at a time,
in order; and on a running engine a stream gets exactly one queue item a
dispatch it took part in, one for its first token and its end mark,
which ``engine.stats["emit_blocks"]`` counts.
"""

import math
import queue
import time

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from nnstreamer_tpu.serving import ContinuousBatchingEngine  # noqa: E402
from nnstreamer_tpu.serving import engine as engine_mod  # noqa: E402
from nnstreamer_tpu.serving.engine import GenerationStream  # noqa: E402
from tests.test_serving import CFG, PARAMS  # noqa: E402

EOS = 7
SLOTS = (0, 2, 3, 5)  # lanes 1 and 4 stay empty: rows are lanes, not order


def per_token(toks, lps, rows, counts, budgets, eos_id):
    """A token at a time: each kept token counts, lowers the budget, and
    may end the stream (end-of-sequence before length)."""
    out = []
    for row, count, budget in zip(rows, counts, budgets):
        got, got_lp, reason = [], [], None
        for j in range(count):
            tok = int(toks[row, j])
            got.append(tok)
            got_lp.append(float(lps[row, j]))
            budget -= 1
            if eos_id is not None and tok == eos_id:
                reason = "eos"
                break
            if budget <= 0:
                reason = "length"
                break
        out.append((got, got_lp, reason, budget))
    return out


def _engine(eos_id=EOS):
    return ContinuousBatchingEngine(CFG, PARAMS, max_streams=6,
                                    steps_per_dispatch=8, block_tokens=8,
                                    eos_id=eos_id)


def _bind(eng, sid, slot, budget):
    """A decoding stream on ``slot``, as an admission leaves it."""
    stream = GenerationStream(sid, 5)
    stream.submit_t = stream.admit_t = time.monotonic()
    stream._emit_block([1], [-0.5])  # its first token
    state = {"sid": sid, "stream": stream, "blocks": eng._pool.alloc(2),
             "pos": 5, "last": 1, "key": np.zeros(2, np.uint32),
             "budget": budget, "deadline_t": stream.submit_t + 60.0,
             "slot": slot}
    eng._sstate[sid] = state
    eng._lane[slot] = sid
    return state


def _cases():
    out = []
    for n in (1, 8):
        for eos in sorted({None, 0, n // 2, n - 1}, key=str):
            for counts in ("block", "speculative"):
                if n == 1 and counts == "speculative":
                    continue  # one token a row either way
                out.append(pytest.param(n, eos, counts,
                                        id=f"n{n}-eos{eos}-{counts}"))
    return out


@pytest.mark.parametrize("n,eos_at,counts_kind", _cases())
def test_a_block_hand_over_is_the_per_token_loop(n, eos_at, counts_kind):
    rng = np.random.default_rng(1000 * n + (eos_at or 0))
    eng = _engine()
    toks = rng.integers(0, CFG.vocab, (eng.B, n)).astype(np.int32)
    toks[toks == EOS] = EOS + 1
    lps = rng.normal(-2.0, 1.0, (eng.B, n)).astype(np.float32)
    keys = rng.integers(0, 2 ** 32, (eng.B, 2), dtype=np.uint64).astype(
        np.uint32)
    # budgets below, at and above the block, and one stream with no
    # end-of-sequence in its row beside three with one at ``eos_at``
    budgets = [max(1, n - 3), n, n + 3, n + 3]
    if eos_at is not None:
        for slot in SLOTS[:3]:
            toks[slot, eos_at] = EOS
    if counts_kind == "block":
        counts = [n] * len(SLOTS)
    else:  # what a verify round accepts: 1 .. n a lane
        counts = [1, n // 2, n, 3]
    run = [_bind(eng, 10 + i, slot, budget)
           for i, (slot, budget) in enumerate(zip(SLOTS, budgets))]
    held = {st["sid"]: list(st["blocks"]) for st in run}
    before = dict(eng.stats)
    want = per_token(toks, lps, SLOTS, counts, budgets, EOS)

    eng._hand_over(run, list(SLOTS), toks, lps,
                   n if counts_kind == "block" else np.asarray(counts),
                   keys=keys)

    kept = sum(len(w[0]) for w in want)
    assert eng.stats["tokens_generated"] - before["tokens_generated"] == kept
    assert eng.stats["active_slot_steps"] \
        - before["active_slot_steps"] == kept
    assert eng.stats["emit_blocks"] - before["emit_blocks"] == len(run)
    # a stream that ended has its block and end mark queued; one that goes
    # on gets its block when the next dispatch is queued
    held_back = [list(st["stream"]._q.queue) for st in run]
    eng._wake_streams()
    live = 0
    for st, slot, count, (got, got_lp, reason, budget), before_wake in zip(
            run, SLOTS, counts, want, held_back):
        s = st["stream"]
        assert s.tokens == [1] + got and s.logprobs == [-0.5] + got_lp
        # one item for the block, then the end mark if it ended
        items = list(s._q.queue)
        assert items[1:] == [got] + ([s._DONE] if reason else [])
        assert before_wake == (items if reason else items[:1])
        assert s.finish_reason == reason and s.finished == bool(reason)
        if reason:
            assert st["sid"] not in eng._sstate
            assert eng._lane[slot] is None
            assert (eng._bt[slot] == eng._pool.SENTINEL).all()
            assert list(s.blocks) == held[st["sid"]]
        else:
            assert eng._sstate[st["sid"]] is st and eng._lane[slot] == \
                st["sid"]
            assert st["budget"] == budget
            assert st["pos"] == 5 + count
            assert st["last"] == int(toks[slot, count - 1])
            assert (st["key"] == keys[slot]).all()
            live += len(held[st["sid"]])
    assert eng._pool.live_blocks() == live


def test_no_eos_id_ends_by_length_alone():
    eng = _engine(eos_id=None)
    toks = np.full((eng.B, 8), EOS, np.int32)
    lps = np.zeros((eng.B, 8), np.float32)
    run = [_bind(eng, 1, 0, 5), _bind(eng, 2, 3, 20)]
    eng._hand_over(run, [0, 3], toks, lps, 8)
    assert [st["stream"].finish_reason for st in run] == ["length", None]
    assert [len(st["stream"].tokens) for st in run] == [1 + 5, 1 + 8]


# -- the stream: blocks in, single tokens out --------------------------------

SPLITS = {"one_block": [8], "first_then_rest": [1, 7],
          "token_by_token": [1] * 8, "uneven": [1, 3, 4]}


@pytest.mark.parametrize("split", sorted(SPLITS))
def test_a_stream_gives_its_blocks_back_one_token_at_a_time(split):
    toks = list(range(100, 108))
    lps = [-0.25 * i for i in range(8)]

    def fed():
        s = GenerationStream(0, 4)
        at = 0
        for size in SPLITS[split]:
            s._emit_block(toks[at:at + size], lps[at:at + size])
            if at == 0:
                first_t = s.first_t
            at += size
        assert s.first_t == first_t  # stamped by the first block alone
        s._finish("length")
        return s

    s = fed()
    assert list(s) == toks
    assert s.tokens == toks and s.logprobs == lps
    assert fed().result(timeout=5) == toks
    # the reader sees each token, in order, as it iterates
    it = iter(fed())
    assert [next(it) for _ in range(3)] == toks[:3]


def test_a_held_back_block_goes_out_before_the_end_mark():
    s = GenerationStream(4, 4)
    s._emit_block([1], [0.0])
    s._emit_block([2, 3], [-1.0, -2.0], wake=False)
    assert list(s._q.queue) == [[1]] and s.tokens == [1, 2, 3]
    s._finish("eos")
    assert list(s._q.queue) == [[1], [2, 3], s._DONE]
    assert s.result(timeout=5) == [1, 2, 3]


def test_a_result_with_nothing_queued_still_times_out():
    s = GenerationStream(3, 4)
    with pytest.raises(TimeoutError):
        s.result(timeout=0.01)
    s._emit_block([5, 6], [0.0, 0.0])
    with pytest.raises(TimeoutError):  # a block, but no end mark
        s.result(timeout=0.01)


# -- it engages: one put a stream a dispatch ---------------------------------


class _CountingQueue(queue.Queue):
    def __init__(self):
        super().__init__()
        self.puts = []

    def put(self, item, *args, **kwargs):
        self.puts.append(item)
        return super().put(item, *args, **kwargs)


class _CountedStream(GenerationStream):
    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        self._q = _CountingQueue()


@pytest.mark.parametrize("kind", ["decode", "speculative"])
def test_each_stream_gets_one_put_a_dispatch(kind, monkeypatch):
    monkeypatch.setattr(engine_mod, "GenerationStream", _CountedStream)
    K, max_new = 4, 14
    options = {"speculate": 2} if kind == "speculative" else {}
    eng = ContinuousBatchingEngine(CFG, PARAMS, max_streams=3,
                                   steps_per_dispatch=K, block_tokens=8,
                                   **options).start()
    try:
        prompts = [np.arange(1, 6), np.arange(20, 32), np.arange(40, 47)]
        streams = [eng.submit(p, max_new_tokens=max_new) for p in prompts]
        outs = [s.result(timeout=120) for s in streams]
    finally:
        eng.stop()
    blocks = 0
    for s, out in zip(streams, outs):
        puts = s._q.puts
        assert puts[-1] is s._DONE and s._DONE not in puts[:-1]
        got = puts[:-1]
        assert [t for b in got for t in b] == out == s.tokens
        assert len(out) == max_new and all(got)
        assert len(got[0]) == 1  # the first token alone
        if kind == "decode":
            # every stream is in every dispatch until it ends: one put each
            assert len(got) == 1 + math.ceil((max_new - 1) / K)
            assert all(len(b) == K for b in got[1:-1])
        else:
            assert all(len(b) <= eng.speculate + 1 for b in got)
        blocks += len(got)
    stats = eng.stats
    assert stats["emit_blocks"] == blocks
    assert stats["admissions"] == len(streams)
    assert stats["tokens_generated"] == len(streams) * max_new
    per_block = (stats["tokens_generated"] - stats["admissions"]) \
        / (stats["emit_blocks"] - stats["admissions"])
    if kind == "decode":
        assert per_block == (max_new - 1) / math.ceil((max_new - 1) / K)


def test_clients_that_iterate_under_a_short_switch_interval_miss_nothing():
    """More client threads than lanes (and than cores here), each reading
    its stream a token at a time while the engine thread holds blocks back
    and hands them over: every client sees its stream's tokens, in order,
    and nothing after the end mark."""
    import sys
    import threading

    eng = ContinuousBatchingEngine(CFG, PARAMS, max_streams=3,
                                   steps_per_dispatch=4, block_tokens=8,
                                   kv_blocks=12 * 8 + 1).start()
    seen, errors = {}, []

    def client(i):
        try:
            for k in range(3):
                s = eng.submit(np.arange(1 + i, 6 + i + k), max_new_tokens=
                               5 + 3 * k)
                seen[(i, k)] = ([t for t in s], s)
        except Exception as e:  # noqa: BLE001 - reported below
            errors.append(e)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(12)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(interval)
        eng.stop()
    assert not errors
    assert len(seen) == 36
    for (i, k), (got, s) in seen.items():
        assert s.finished and s.finish_reason == "length"
        assert got == s.tokens and len(got) == 5 + 3 * k
        assert s._unsent == []
    assert eng._pool.live_blocks() == 0
