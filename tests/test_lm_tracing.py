"""The LM serving path measures itself (PR 25): request stamps, engine-loop
phase counters and spans, the stall note, one clock with the device trace,
and the names inside the programs that the benchmark's readers look for.
Since PR 35: the time the loop has nothing queued on the device, by phase;
what the first-token path counts of itself; the device's idle gaps put
down to the host spans over them.
"""

import json
import re
import threading
import time

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from nnstreamer_tpu.models.transformer import (  # noqa: E402
    TransformerConfig,
    build_prefill,
    init_params,
)
from nnstreamer_tpu.obs import get_registry, timeline  # noqa: E402
from nnstreamer_tpu.serving import ContinuousBatchingEngine  # noqa: E402
from nnstreamer_tpu.serving import engine as engine_mod  # noqa: E402

CFG = TransformerConfig(vocab=97, d_model=64, n_heads=4, n_layers=2,
                        d_ff=128, max_seq=64, dtype=jnp.float32)
PARAMS = init_params(CFG, seed=3)
LEAF_SCOPES = ("qkv", "kv_write", "kv_gather", "attend", "ffn", "logits",
               "sample")


@pytest.fixture(autouse=True)
def _no_installed_timeline():
    """These tests read the engine's own ledger, which gets the spans only
    while no process-wide timeline is installed. One that an earlier file
    of this worker left behind (a pipeline's flight recorder not yet
    retired) would catch them instead: start from none."""
    timeline.deactivate()
    yield


def _engine(**kw):
    kw.setdefault("max_streams", 2)
    kw.setdefault("steps_per_dispatch", 4)
    return ContinuousBatchingEngine(CFG, PARAMS, **kw)


def _prompt(n, start=1):
    return (np.arange(start, start + n) % CFG.vocab).astype(np.int32)


def _serve(eng, prompts, max_new=9):
    streams = [eng.submit(p, max_new_tokens=max_new) for p in prompts]
    for s in streams:
        s.result(timeout=120)
    return streams


def _idle_waits(eng, n=3):
    """Block until the loop has come back from ``n`` more idle waits: a
    state the loop reaches, however the scheduler treats its thread, and
    not a time that has passed. (Two waits between two looks count as
    one: it only waits longer.)"""
    deadline = time.monotonic() + 120
    seen, last = 0, eng.stats["phase_idle_us"]
    while seen < n:
        assert time.monotonic() < deadline, "the loop never went idle"
        time.sleep(0.005)
        now = eng.stats["phase_idle_us"]
        seen, last = seen + (now != last), now


# with the default block (16 tokens) and with blocks of 8
ADMISSIONS = {
    "blocks8": {"block_tokens": 8},
    "default": {},
    "chunked": {"prefill_chunk": 8},
    "chunked_blocks8": {"block_tokens": 8, "prefill_chunk": 8},
    "prefix_hit": {"prefix_cache": 4},
    "prefix_hit_blocks8": {"block_tokens": 8, "prefix_cache": 4},
}


@pytest.mark.parametrize("kind", sorted(ADMISSIONS))
def test_four_stamps_are_set_and_ordered_on_every_finished_stream(kind):
    eng = _engine(**ADMISSIONS[kind]).start()
    try:
        base = _prompt(20)
        # more requests than lanes (some queue), one prompt twice (an
        # exact prefix hit where the cache is on) and one that extends it
        streams = _serve(eng, [base, _prompt(5, 40)])
        streams += _serve(eng, [base, np.concatenate([base, _prompt(6, 50)]),
                                _prompt(12, 60)])
    finally:
        eng.stop()
    if "prefix_hit" in kind:
        assert eng.stats["prefix_hits"] >= 2
    if "chunked" in kind:
        assert eng.stats["prefill_chunks"] > 0
    for s in streams:
        assert s.finish_reason == "length" and len(s.tokens) == 9
        assert s.submit_t <= s.admit_t <= s.first_t <= s.finish_t, kind
    assert eng.stats["admissions"] == len(streams)
    assert eng.stats["admit_wait_us"] >= 0
    assert eng.stats["first_token_us"] > 0


def test_unadmitted_stream_gets_submit_and_finish_only():
    eng = _engine(block_tokens=8).start()
    try:
        running = eng.submit(_prompt(8), max_new_tokens=40)
        dropped = eng.submit(_prompt(8, 30), max_new_tokens=4)
        dropped.cancel()
        running.result(timeout=120)
        deadline = time.monotonic() + 30
        while not dropped.finished and time.monotonic() < deadline:
            time.sleep(0.01)
    finally:
        eng.stop()
    assert dropped.finished and dropped.finish_t >= dropped.submit_t
    if dropped.finish_reason == "cancelled" and not dropped.tokens:
        assert dropped.first_t is None


BLOCKS = pytest.mark.parametrize("blocks", [{"block_tokens": 8}, {}],
                                 ids=["blocks8", "default"])


@BLOCKS
def test_phase_counters_are_ints_from_the_start_and_tile_the_loop(blocks):
    eng = _engine(**blocks)
    keys = {"loop_us", "admissions", "admit_wait_us", "first_token_us",
            "stalls"} | {f"phase_{p}_us" for p in engine_mod.PHASES}
    assert keys <= set(eng.stats)
    assert all(type(v) is int for v in eng.stats.values())
    eng.start()
    try:
        t0 = time.monotonic()
        before = dict(eng.stats)
        _serve(eng, [_prompt(5), _prompt(20), _prompt(12)])
        _idle_waits(eng)
        after = dict(eng.stats)
        wall_us = (time.monotonic() - t0) * 1e6
    finally:
        eng.stop()
    assert all(type(v) is int for v in eng.stats.values())
    grew = {k: after[k] - before[k] for k in keys}
    phases = sum(grew[f"phase_{p}_us"] for p in engine_mod.PHASES)
    assert abs(phases - grew["loop_us"]) <= 0.01 * grew["loop_us"]
    # every instant of the loop thread is in some phase: the loop's
    # clock keeps up with the wall's, to the phase that was open at each
    # end (an idle wait of 50 ms, or less)
    assert 0.8 * wall_us <= grew["loop_us"] <= wall_us + 150_000
    assert grew["phase_dispatch_us"] > 0 and grew["phase_idle_us"] > 0


def _kinds(tl):
    out = {}
    for rec in tl._snapshot():
        out.setdefault(rec[1], []).append(rec)
    return out


def test_ledger_holds_one_dispatch_span_per_dispatch_and_each_request():
    eng = _engine(block_tokens=8).start()
    try:
        _idle_waits(eng)   # three waits or more with nothing between them
        streams = _serve(eng, [_prompt(5), _prompt(20), _prompt(12)])
        _idle_waits(eng)   # and again after the last request
    finally:
        eng.stop()
    kinds = _kinds(eng.ledger)
    assert len(kinds["lm_dispatch"]) == eng.stats["dispatches"] > 0
    for name in ("lm_admit", "lm_first_token", "lm_select", "lm_emit",
                 "lm_idle"):
        assert kinds.get(name), name
    # consecutive idle waits are one record: the waits before the first
    # request and those after the last are one span each, and wherever
    # else the loop found nothing to do (that is the scheduler's to
    # decide, so the spans are not counted) no idle span follows another
    spans = [r[1] for r in eng.ledger._snapshot() if r[4] is not None]
    assert spans[0] == spans[-1] == "lm_idle"
    assert ("lm_idle", "lm_idle") not in set(zip(spans, spans[1:]))
    for rec in kinds["lm_dispatch"]:
        _, _, seq, t0, t1, track, args = rec
        assert seq is None and t1 >= t0 and track == eng.obs_name
        assert "dispatch" in args
    admitted = {r[6]["stream"]: r[6]["prompt"] for r in kinds["lm_admit"]}
    assert admitted == {s.stream_id: s.prompt_len for s in streams}
    begins = [a for a in eng.ledger._async if a[0] == "b"]
    ends = [a for a in eng.ledger._async if a[0] == "e"]
    assert sorted(a[2] for a in begins) == sorted(a[2] for a in ends) \
        == sorted(s.stream_id for s in streams)
    assert len(kinds["lm_admitted"]) == len(kinds["lm_first"]) == 3
    # a dispatch number never lands in a frame's record
    assert eng.ledger.frame_ledger() == {}


def test_spans_go_to_the_installed_timeline_instead():
    eng = _engine(block_tokens=8)
    with timeline.tracing() as tl:
        eng.start()
        try:
            _serve(eng, [_prompt(5)])
        finally:
            eng.stop()
    assert len(_kinds(tl)["lm_dispatch"]) == eng.stats["dispatches"] > 0
    assert "lm_dispatch" not in _kinds(eng.ledger)


class _SteppedClock:
    """The engine's clock, which a test sets forward by thousands of
    seconds at chosen places: no loaded machine adds as much by itself,
    so where a step lands can be read off the counters' thousands."""

    def __init__(self):
        self.lost = 0.0

    def monotonic(self):
        return time.monotonic() + self.lost

    def step_before(self, holder, name, seconds, when=lambda: True):
        """``holder.name`` sets the clock forward, then runs."""
        inner = getattr(holder, name)

        def stepping(*args, **kwargs):
            if when():
                self.lost += seconds
            return inner(*args, **kwargs)

        setattr(holder, name, stepping)


def _thousands(us):
    """Whole thousands of seconds in a counter of microseconds."""
    return us // 1_000_000_000


@pytest.fixture
def stepped(monkeypatch):
    clock = _SteppedClock()
    monkeypatch.setattr(engine_mod, "_time", clock)
    notes = []
    monkeypatch.setattr(engine_mod.log, "warning",
                        lambda msg, *args: notes.append(msg % args))
    clock.notes = notes
    return clock


def test_stall_note_fires_once_with_the_phases(stepped, monkeypatch):
    # One dispatch sets the engine's clock forward by 1000 s: a stall of a
    # size no loaded machine makes by itself (every wait of these tests
    # gives up sooner), so that the thresholds can stand where only it
    # passes them and the count does not hang on the scheduler.
    eng = _engine(block_tokens=8).start()
    try:
        _serve(eng, [_prompt(5)], max_new=30)  # compiles; a median exists
        monkeypatch.setattr(engine_mod, "STALL_MIN_S", 500.0)
        monkeypatch.setattr(engine_mod, "STALL_FACTOR", 3.0)
        before = eng.stats["stalls"]
        stepped.step_before(eng, "_dispatch", 1000.0,
                            when=lambda: not stepped.lost)
        del stepped.notes[:]
        # the stalled iteration is the request's first of eight: the loop
        # has held it against the median long before the request ends
        _serve(eng, [_prompt(6, 9)], max_new=30)
    finally:
        eng.stop()
    notes = stepped.notes
    assert eng.stats["stalls"] - before == 1
    assert len(notes) == 1 and re.search(r"dispatch 1000\d\d\d\b", notes[0]), notes
    assert notes[0].startswith(f"serving: {eng.obs_name} iteration")


@BLOCKS
def test_token_stats_see_one_observation_per_finished_request(blocks):
    eng = _engine(**blocks).start()
    try:
        streams = _serve(eng, [_prompt(5), _prompt(20), _prompt(12)])
        one = _serve(eng, [_prompt(7)], max_new=1)  # no second token
    finally:
        eng.stop()
    q = eng._lm_stats._q
    assert q["token"]["p50"].count == len(streams)
    assert q["ttft"]["p50"].count == len(streams) + len(one)
    # what a client saw: (finish - first token) / (tokens - 1)
    seen = sorted((s.finish_t - s.first_t) / (len(s.tokens) - 1)
                  for s in streams)
    assert q["token"]["p50"].quantile() == pytest.approx(seen[1])


def test_collector_exports_the_phases_as_one_family():
    eng = _engine(block_tokens=8).start()
    try:
        _serve(eng, [_prompt(5)])
    finally:
        eng.stop()
    reg = get_registry()
    reg.snapshot()  # runs the collectors
    for phase in engine_mod.PHASES:
        c = reg.get("nns_serving_loop_phase_seconds_total",
                    engine=eng.obs_name, phase=phase)
        assert c is not None, phase
        assert c.value == pytest.approx(
            eng.stats[f"phase_{phase}_us"] / 1e6)


# -- starved time: the loop thread's time with nothing queued ---------------

STARVED_KEYS = ["starved_us"] + [f"starved_{p}_us"
                                 for p in engine_mod.STARVED_PHASES]
ENGINE_KINDS = {**ADMISSIONS, "speculative": {"speculate": 2}}


def _programs_of(eng):
    """``(holder, attribute or key)`` of every jitted program the loop
    thread of ``eng`` can call."""
    out = [(eng, "_dispatch"), (eng, "_prefill_jitted"),
           (eng, "_sample_first"), (eng._pool, "_jit_scatter"),
           (eng._pool, "_jit_copy")]
    if eng._paged_chunk_fn is not None:
        out += [(eng, "_paged_chunk_jitted")]
    if eng._spec is not None:
        out += [(eng._spec, k) for k in ("dispatch", "prefill", "insert")]
    return out


def _get(holder, key):
    return holder[key] if isinstance(holder, dict) else getattr(holder, key)


def _put(holder, key, value):
    if isinstance(holder, dict):
        holder[key] = value
    else:
        setattr(holder, key, value)


def _check_invariants(stats):
    assert all(type(stats[k]) is int for k in STARVED_KEYS)
    for p in engine_mod.STARVED_PHASES:
        assert 0 <= stats[f"starved_{p}_us"] <= stats[f"phase_{p}_us"], p
    assert sum(stats[f"starved_{p}_us"]
               for p in engine_mod.STARVED_PHASES) == stats["starved_us"]
    assert stats["starved_us"] <= stats["loop_us"]
    assert "starved_first_token_us" not in stats
    assert "starved_idle_us" not in stats
    assert sum(stats[f"phase_{p}_us"]
               for p in engine_mod.PHASES) == stats["loop_us"]
    assert 0 < stats["prefill_tokens"] <= stats["prefill_bucket_tokens"]
    assert 0 < stats["admit_boundaries"] <= stats["admissions"]
    assert 0 <= stats["prefill_programs"] <= stats["admissions"]


@pytest.mark.parametrize("kind", sorted(ENGINE_KINDS))
def test_starved_counters_hold_their_invariants_on_every_path(kind):
    eng = _engine(**ENGINE_KINDS[kind])
    assert set(STARVED_KEYS) | {"prefill_tokens", "prefill_bucket_tokens",
                                "admit_boundaries"} <= set(eng.stats)
    assert all(eng.stats[k] == 0 and type(eng.stats[k]) is int
               for k in STARVED_KEYS)
    # every program the loop thread calls goes through the one helper
    inside, called, inner = [], set(), eng._enqueue

    def enqueue(program, *args, **kwargs):
        inside.append(program)
        try:
            return inner(program, *args, **kwargs)
        finally:
            inside.pop()

    def guarded(holder, key):
        program = _get(holder, key)

        def call(*args, **kwargs):
            assert inside, f"{key} was called past _enqueue"
            called.add(key)
            return program(*args, **kwargs)

        _put(holder, key, call)

    eng._enqueue = enqueue
    for holder, key in _programs_of(eng):
        guarded(holder, key)
    eng.start()
    try:
        base = _prompt(20)
        _serve(eng, [base, _prompt(5, 40)])
        _serve(eng, [base, np.concatenate([base, _prompt(6, 50)]),
                     _prompt(12, 60)])
        _idle_waits(eng)
    finally:
        eng.stop()
    _check_invariants(eng.stats)
    assert eng.stats["starved_us"] > 0
    assert eng._dev_seen == eng._dev_enq > 0
    want = {"_sample_first"}
    want |= {"dispatch", "prefill", "insert"} if kind == "speculative" \
        else {"_dispatch"}
    # a chunked prompt is written into its blocks by the paged chunk
    # program; every other cold prompt's prefill is scattered into them
    want |= {"_paged_chunk_jitted"} if "chunked" in kind \
        else {"_prefill_jitted", "_jit_scatter"}
    if "prefix_hit" in kind:
        want |= {"_paged_chunk_jitted", "_jit_copy"}
    assert want <= called, (kind, called)


def test_an_engine_that_only_idles_books_no_starved_time():
    eng = _engine(block_tokens=8).start()
    try:
        _idle_waits(eng, 4)
    finally:
        eng.stop()
    assert eng.stats["phase_idle_us"] > 0
    assert all(eng.stats[k] == 0 for k in STARVED_KEYS)
    assert eng.stats["admit_boundaries"] == 0


def test_time_between_a_fetch_and_the_next_enqueue_is_starved_by_phase(
        stepped):
    eng = _engine(block_tokens=8).start()
    try:
        _serve(eng, [_prompt(5)], max_new=30)  # compiles
        before = dict(eng.stats)
        armed = []   # one dispatch of the next request, not its first

        def once(tag):
            def when():
                if armed and tag not in armed:
                    armed.append(tag)
                    return True
                return False
            return when

        # each in the phase its caller belongs to, and only after the
        # request's first dispatch has emptied the queue
        stepped.step_before(eng, "_end_iteration", 2000.0, once("other"))
        stepped.step_before(eng, "_topup", 4000.0, once("select"))
        stepped.step_before(eng, "_dispatch", 8000.0, once("launch"))
        stepped.step_before(eng, "_fetched", 16000.0, once("fetched"))
        stepped.step_before(eng, "_hand_over", 1000.0, once("emit"))
        inner = eng._fetched

        def arm(ticket):
            inner(ticket)
            if not armed and eng.stats["dispatches"] > before["dispatches"]:
                armed.append("armed")

        eng._fetched = arm
        del stepped.notes[:]
        _serve(eng, [_prompt(6, 9)], max_new=30)
    finally:
        eng.stop()
    assert set(armed) == {"armed", "other", "select", "launch", "fetched",
                          "emit"}
    grew = {k: eng.stats[k] - before[k] for k in before
            if type(before[k]) is int}
    assert _thousands(grew["starved_emit_us"]) == 1
    assert _thousands(grew["starved_other_us"]) == 2
    assert _thousands(grew["starved_select_us"]) == 4
    # the launch is starved, the wait and the bookkeeping after it are not
    assert _thousands(grew["starved_dispatch_us"]) == 8
    assert _thousands(grew["phase_dispatch_us"]) == 24
    assert _thousands(grew["starved_admit_us"]) == 0
    assert _thousands(grew["starved_us"]) == 15
    _check_invariants(eng.stats)
    # the stall note says which part of a long phase was starved
    assert any(re.search(r"dispatch 24\d{6} \(starved 8\d{6}\)", n)
               for n in stepped.notes), stepped.notes
    # and the spans carry what the counters sum
    spans = [r for r in eng.ledger._snapshot() if r[4] is not None]
    carried = sum((r[6] or {}).get("starved_us", 0) for r in spans)
    assert carried == eng.stats["starved_us"]
    assert all((r[6] or {}).get("starved_us", 1) > 0 for r in spans)
    assert not any("starved_us" in (r[6] or {}) for r in spans
                   if r[1] in ("lm_first_token", "lm_idle"))


def test_only_the_first_admission_of_a_boundary_is_starved(stepped):
    eng = _engine(block_tokens=8, max_streams=4)
    # a prompt a program (the rows rule at 1): each prefill is enqueued
    # before the next request's host work
    eng._balance = 0.0
    eng.start()
    try:
        # compile every shape, and leave a request decoding
        _serve(eng, [_prompt(5), _prompt(12, 30)], max_new=5)
        running = eng.submit(_prompt(7, 3), max_new_tokens=40)
        gate, held = threading.Event(), threading.Event()
        inner = eng._dispatch

        def hold(*args):
            if not gate.is_set():
                held.set()
                assert gate.wait(timeout=120)
            return inner(*args)

        eng._dispatch = hold
        assert held.wait(timeout=120)
        # the loop stands inside a dispatch: both land at one boundary
        before = dict(eng.stats)
        stepped.step_before(eng, "_prefill_jitted", 1000.0)
        stepped.step_before(eng, "_activate_commit_paged", 4000.0)
        pair = [eng.submit(_prompt(5, 50), max_new_tokens=5),
                eng.submit(_prompt(12, 70), max_new_tokens=5)]
        gate.set()
        for s in pair + [running]:
            s.result(timeout=120)
    finally:
        eng.stop()
    grew = {k: eng.stats[k] - before[k] for k in before
            if type(before[k]) is int}
    assert grew["admissions"] == 2 and grew["admit_boundaries"] == 1
    # two host halves of 1000 s each; the second ran with the first's
    # prefill queued
    assert _thousands(grew["phase_admit_us"]) == 2
    assert _thousands(grew["starved_admit_us"]) == 1
    # both waits for a first token, and none of it starved
    assert _thousands(grew["phase_first_token_us"]) == 8
    assert _thousands(grew["starved_us"]) == 1
    assert grew["prefill_programs"] == 2
    _check_invariants(eng.stats)


def test_a_boundary_of_cold_prompts_shares_one_program(stepped):
    eng = _engine(block_tokens=8, max_streams=4)
    # the rows rule at these shapes: both prompts' buckets take several
    assert eng._prefill_rows(16) >= 2
    eng.start()
    try:
        _serve(eng, [_prompt(5), _prompt(12, 30)], max_new=5)
        running = eng.submit(_prompt(7, 3), max_new_tokens=40)
        gate, held = threading.Event(), threading.Event()
        inner = eng._dispatch

        def hold(*args):
            if not gate.is_set():
                held.set()
                assert gate.wait(timeout=120)
            return inner(*args)

        eng._dispatch = hold
        assert held.wait(timeout=120)
        before = dict(eng.stats)
        stepped.step_before(eng, "_prefill_jitted", 1000.0)
        stepped.step_before(eng, "_activate_commit_paged", 4000.0)
        pair = [eng.submit(_prompt(5, 50), max_new_tokens=5),
                eng.submit(_prompt(12, 70), max_new_tokens=5)]
        gate.set()
        for s in pair + [running]:
            s.result(timeout=120)
    finally:
        eng.stop()
    grew = {k: eng.stats[k] - before[k] for k in before
            if type(before[k]) is int}
    assert grew["admissions"] == 2 and grew["admit_boundaries"] == 1
    # ONE program for both: one host half before it, all of it starved
    # (nothing is queued until the group's prefill), one wait for both
    # first tokens
    assert grew["prefill_programs"] == 1
    assert _thousands(grew["phase_admit_us"]) == 1
    assert _thousands(grew["starved_admit_us"]) == 1
    assert _thousands(grew["phase_first_token_us"]) == 4
    assert _thousands(grew["starved_us"]) == 1
    assert grew["prefill_bucket_tokens"] == eng._prefill_rows(16) * 16
    _check_invariants(eng.stats)


FIRST_TOKEN_LOADS = {
    # kind: (engine options, prompts served one after another,
    #        tokens given, the programs' widths, cold prefill programs);
    # a cold prefill computes its bucket's rows a prompt it can take
    # (``engine._prefill_rows``), the others one
    "cold": ({}, [_prompt(5), _prompt(20), _prompt(12)],
             5 + 20 + 12, [16, 32, 16], 3),
    "cold_min_bucket8": ({"min_bucket": 8},
                         [_prompt(5), _prompt(20), _prompt(9)],
                         5 + 20 + 9, [8, 32, 16], 3),
    # 20 cold; the same again is an exact hit (no program); 26 tokens of
    # which the first two blocks of 8 are shared: 10 left, in a bucket of 16
    "prefix_extension": ({"prefix_cache": 4, "block_tokens": 8},
                         [_prompt(20), _prompt(20),
                          np.concatenate([_prompt(20), _prompt(6, 50)])],
                         20 + 0 + 10, [32, 16], 1),
    # chunks of 8: 20 = 8 + 8 + 4, 5 = 5
    "chunked": ({"prefill_chunk": 8}, [_prompt(20), _prompt(5)],
                20 + 5, [8, 8, 8, 8], 0),
}


@pytest.mark.parametrize("kind", sorted(FIRST_TOKEN_LOADS))
def test_first_token_path_counts_tokens_rows_and_boundaries(kind):
    options, prompts, tokens, widths, programs = FIRST_TOKEN_LOADS[kind]
    eng = _engine(**options).start()
    try:
        for p in prompts:
            _serve(eng, [p], max_new=3)
    finally:
        eng.stop()
    assert eng.stats["prefill_tokens"] == tokens
    assert eng.stats["prefill_bucket_tokens"] == sum(
        eng._prefill_rows(w) * w for w in widths)
    assert eng.stats["prefill_programs"] == programs
    # one request at a time: every admission is a boundary of its own
    assert eng.stats["admit_boundaries"] == eng.stats["admissions"] \
        == len(prompts)


def test_collector_exports_the_starved_time_as_one_family():
    eng = _engine(block_tokens=8).start()
    try:
        _serve(eng, [_prompt(5)])
    finally:
        eng.stop()
    reg = get_registry()
    reg.snapshot()  # runs the collectors
    name = "nns_serving_loop_starved_seconds_total"
    for phase in engine_mod.STARVED_PHASES:
        c = reg.get(name, engine=eng.obs_name, phase=phase)
        assert c is not None, phase
        assert c.value == pytest.approx(
            eng.stats[f"starved_{phase}_us"] / 1e6)
    for phase in ("first_token", "idle", ""):
        assert reg.get(name, engine=eng.obs_name, phase=phase) is None


# -- one clock for the ledger and the device trace ---------------------------

def test_to_trace_ns_round_trip_and_the_pair_in_the_export():
    tl = timeline.Timeline(16)
    t, wall = time.monotonic(), time.time_ns()
    assert abs(tl.to_trace_ns(t) - wall) < 50e6
    assert tl.to_trace_ns(t + 1.5) - tl.to_trace_ns(t) == 1_500_000_000
    mono, unix = tl.clock
    assert tl.to_trace_ns(mono) == unix
    tl.span("lm_dispatch", None, t, t + 0.25, track="engine9", dispatch=3)
    doc = tl.to_chrome()
    clock = doc["metadata"]["clock"]
    assert (clock["monotonic_s"], clock["unix_ns"]) == tl.clock
    ev = next(e for e in doc["traceEvents"] if e.get("name") == "lm_dispatch")
    # a reader of the export gets the trace's clock from the pair
    back = clock["unix_ns"] + (clock["epoch_monotonic_s"]
                               - clock["monotonic_s"]) * 1e9 + ev["ts"] * 1e3
    assert abs(back - tl.to_trace_ns(t)) < 2000


def test_extend_last_lengthens_only_a_span_of_that_kind():
    tl = timeline.Timeline(16)
    assert not tl.extend_last("lm_idle", 2.0)
    tl.span("lm_idle", None, 1.0, 2.0, track="e")
    assert tl.extend_last("lm_idle", 3.0)
    tl.span("lm_admit", None, 3.0, 3.5, track="e")
    assert not tl.extend_last("lm_idle", 4.0)
    spans = [(r[1], r[3], r[4]) for r in tl._snapshot()]
    assert spans == [("lm_idle", 1.0, 3.0), ("lm_admit", 3.0, 3.5)]


def test_clock_differences_pair_kth_with_kth_across_a_cut_edge():
    device = [(k * 1_000, k * 1_000 + 400) for k in range(1, 5)]
    host = [(s - 10, e + 30) for s, e in device]
    assert timeline.clock_differences(host[1:], device) == [(-10, 30)] * 3
    assert timeline.clock_differences(host[:2], device) == [(-10, 30)] * 2
    assert timeline.clock_differences([], device) == []


def test_align_splits_the_slack_of_a_span_that_holds_its_device_event():
    tl = timeline.Timeline(16)
    tr = timeline.DeviceTrace("unused", tl)
    tr.window = (0.0, 10.0)
    base = tl.to_trace_ns(0.0)
    # the device's clock 2 ms ahead of the host's; each span opens 1 ms
    # before its program starts and closes 3 ms after it ends (true times)
    ahead = 2_000_000
    for k in range(1, 4):
        tl.span("lm_dispatch", None, k - 0.001, k + 0.4 + 0.003, track="e")
        tr.programs.append(("/device:TPU:0", "jit_dispatch",
                            base + k * 10**9 + ahead,
                            base + k * 10**9 + 400_000_000 + ahead))
    tr.programs.append(("/device:TPU:0", "jit_prefill", base, base + 5))
    tr._align("lm_dispatch", "jit_dispatch")
    check = tr.clock_check
    assert check["n"] == 3
    assert check["lead_ns"] == pytest.approx(-3_000_000, abs=2_000)
    assert check["lag_min_ns"] == pytest.approx(1_000_000, abs=2_000)
    # any offset from 3 ms to -1 ms keeps the event inside: the middle
    assert tr.offset_ns == pytest.approx(1_000_000, abs=2_000)
    assert tr.to_trace_ns(1.0) == tl.to_trace_ns(1.0) + tr.offset_ns


MS = 1_000_000  # ns
DEV = "/device:TPU:0"
IDLE_BY_SPAN = {
    # programs (device, start ms, end ms), spans (kind, start, end),
    # window, what comes back in ms (kinds that read 0 left out)
    "a_gap_wholly_under_one_span": (
        [(DEV, 0, 10), (DEV, 14, 20)], [("lm_emit", 9, 15)], (0, 20),
        {"lm_emit": 4, "gap_s": 4}),
    "a_gap_split_over_two_spans": (
        [(DEV, 0, 10), (DEV, 20, 30)],
        [("lm_emit", 8, 13), ("lm_other", 13, 14), ("lm_admit", 14, 25)],
        (0, 30), {"lm_emit": 3, "lm_other": 1, "lm_admit": 6, "gap_s": 10}),
    "a_gap_past_the_spans_end_is_unattributed": (
        [(DEV, 0, 10), (DEV, 20, 30)], [("lm_emit", 5, 12)], (0, 30),
        {"lm_emit": 2, "gap_s": 10, "unattributed_s": 8}),
    "two_devices_add_up": (
        [(DEV, 0, 10), (DEV, 14, 20), ("/device:TPU:1", 0, 12),
         ("/device:TPU:1", 13, 20)], [("lm_emit", 0, 20)], (0, 20),
        {"lm_emit": 5, "gap_s": 5}),
    "overlapping_programs_are_one_busy_stretch": (
        [(DEV, 0, 10), (DEV, 5, 12), (DEV, 15, 20)], [("lm_select", 0, 20)],
        (0, 20), {"lm_select": 3, "gap_s": 3}),
    "the_window_cuts_programs_and_leaves_its_edges_out": (
        [(DEV, 0, 10), (DEV, 14, 20), (DEV, 40, 50)],
        [("lm_emit", 0, 50)], (5, 17), {"lm_emit": 4, "gap_s": 4}),
    "overlapping_spans_each_get_the_gap_and_it_is_covered_once": (
        [(DEV, 0, 10), (DEV, 20, 30)],
        [("a", 8, 16), ("b", 12, 18)], (0, 30),
        {"a": 6, "b": 6, "gap_s": 10, "unattributed_s": 2}),
    "no_programs_no_gaps": ([], [("lm_idle", 0, 20)], (0, 20), {}),
}


@pytest.mark.parametrize("case", sorted(IDLE_BY_SPAN))
def test_idle_by_span_puts_each_gap_down_to_the_spans_over_it(case):
    programs, spans, window, want = IDLE_BY_SPAN[case]
    got = timeline.idle_by_span(
        [(d, "jit_dispatch", a * MS, b * MS) for d, a, b in programs],
        [(k, a * MS, b * MS) for k, a, b in spans],
        (window[0] * MS, window[1] * MS))
    assert set(got) == {k for k, _, _ in spans} | {"gap_s", "unattributed_s"}
    assert {k: round(v * 1e3, 6) for k, v in got.items() if v} == want


def test_the_offset_moves_a_boundary_between_two_spans():
    tl = timeline.Timeline(16)
    tl.span("lm_emit", None, 1.000, 1.004, track="engine7")
    tl.span("lm_other", None, 1.004, 1.010, track="engine7")
    tl.span("queue_wait", 5, 1.000, 1.010, track="q0")  # another thread
    tr = timeline.DeviceTrace("unused", tl, track="engine7")
    tr.window = (0.5, 2.0)
    base = tl.to_trace_ns(0.0)
    at = lambda ms: base + 10**9 + ms * MS  # noqa: E731
    tr.programs = [(DEV, "jit_dispatch", at(-50), at(2)),
                   (DEV, "jit_prefill", at(8), at(60))]
    tr._join()
    assert {k: round(v * 1e3, 3) for k, v in tr.idle_by_span.items()} == {
        "lm_emit": 2.0, "lm_other": 4.0, "gap_s": 6.0, "unattributed_s": 0.0}
    tr.offset_ns = -1 * MS  # the device's clock 1 ms behind the host's
    tr._join()
    assert {k: round(v * 1e3, 3) for k, v in tr.idle_by_span.items()} == {
        "lm_emit": 1.0, "lm_other": 5.0, "gap_s": 6.0, "unattributed_s": 0.0}


def test_device_trace_writes_the_ledger_on_the_traces_clock(tmp_path):
    eng = _engine(block_tokens=8).start()
    try:
        _serve(eng, [_prompt(5)])  # compile outside the trace
        with timeline.device_trace(str(tmp_path), eng.ledger,
                                   align=("lm_dispatch", "jit_dispatch")) \
                as tr:
            _serve(eng, [_prompt(9)])
    finally:
        eng.stop()
    assert tr.xplane and tr.xplane.endswith(".xplane.pb")
    doc = json.load(open(tr.ledger_path))
    spans = [e for e in doc["traceEvents"] if e.get("name") == "lm_dispatch"]
    assert spans
    now_us = time.time_ns() / 1e3
    assert all(now_us - 120e6 < e["ts"] <= now_us for e in spans)
    # the CPU's trace has no device plane: nothing to match, no correction
    if not tr.programs:
        assert tr.clock_check is None and tr.offset_ns == 0
    # the join is in the file, under every span kind the ledger holds
    assert doc["metadata"]["idle_by_span"] == tr.idle_by_span
    assert {"gap_s", "unattributed_s", "lm_dispatch", "lm_emit"} \
        <= set(tr.idle_by_span)
    if not tr.programs:
        assert not any(tr.idle_by_span.values())


# -- names inside the programs -----------------------------------------------

def _lowered_text(jitted, *shapes, **static):
    return jitted.lower(*shapes, **static).as_text(debug_info=True)


def _has_scope(text, scope):
    """A location of the lowered text lies under ``scope``: it starts
    the location's path or is a part of it."""
    return f'"{scope}/' in text or f"/{scope}/" in text


@BLOCKS
def test_decode_program_is_jit_dispatch_and_holds_every_scope(blocks):
    eng = _engine(**blocks)
    build, k, shapes = engine_mod._DECODE_PROGRAMS[eng.obs_name]
    assert build is eng._build_dispatch and k == eng.K
    text = _lowered_text(eng._dispatch, *shapes)
    assert "module @jit_dispatch" in text
    assert "nns.decode" in text
    for scope in LEAF_SCOPES:
        assert _has_scope(text, scope), scope
    # the optimized program's own text carries them on its instructions
    compiled = engine_mod.decode_program_text(eng.obs_name)
    assert 'op_name="jit(dispatch)/' in compiled
    for scope in LEAF_SCOPES:
        assert f"/{scope}/" in compiled, scope
    assert engine_mod.decode_program_text("no-such-engine") is None


def test_decode_program_registry_keeps_the_newest_few():
    engines = [_engine(block_tokens=8)
               for _ in range(engine_mod._DECODE_PROGRAMS_KEPT + 2)]
    names = list(engine_mod._DECODE_PROGRAMS)
    assert names == [e.obs_name
                     for e in engines[-engine_mod._DECODE_PROGRAMS_KEPT:]]


def test_prefill_program_is_jit_prefill_and_holds_every_scope():
    from nnstreamer_tpu.ops import flash_attention

    def flash(q, k, v):
        return flash_attention(q, k, v, block_q=16, block_k=16,
                               force="pallas")

    tokens = jax.ShapeDtypeStruct((1, 32), jnp.int32)
    text = _lowered_text(jax.jit(build_prefill(CFG, attention_fn=flash)),
                         PARAMS, tokens)
    assert "module @jit_prefill" in text
    assert "nns.prefill" in text
    for scope in ("qkv", "attend", "ffn", "logits"):
        assert _has_scope(text, scope), scope
    assert "nns_flash_prefill" in text


def test_scatter_program_holds_its_scope():
    from nnstreamer_tpu.models.transformer import init_cache

    eng = _engine(block_tokens=8)
    cache1 = jax.eval_shape(lambda: init_cache(CFG, 1, eng.S))
    arena = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), eng._pool.arena)
    text = _lowered_text(eng._pool._jit_scatter, arena, cache1,
                         jax.ShapeDtypeStruct((CFG.max_seq // 8,), jnp.int32),
                         heads_major=eng._pool.heads_major)
    assert "module @jit__scatter_prefill_impl" in text
    assert "nns.kv_scatter" in text


def test_fused_program_is_jit_composed_and_holds_its_scope():
    from nnstreamer_tpu import parse_launch

    pipe = parse_launch(
        "appsrc name=src ! tensor_transform mode=arithmetic "
        "option=typecast:float32,mul:2.0 ! tensor_transform mode=arithmetic "
        "option=add:1.0 ! tensor_sink name=sink")
    pipe.start()
    try:
        pipe.get("src").push([np.ones((8, 4), np.uint8)])
        pipe.get("src").end_of_stream()
        assert pipe.wait(timeout=60).kind == "eos"
        region = pipe._regions[0]
        consts, jitted, _ = region._compiled
        text = _lowered_text(
            jitted, consts, [jax.ShapeDtypeStruct((8, 4), jnp.uint8)])
    finally:
        pipe.stop()
    assert "module @jit_composed" in text
    assert "nns.fused" in text


@pytest.fixture
def compile_cache(tmp_path):
    """A persistent compile cache of the test's own that takes every
    program, put back as it was afterwards."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    before = {k: getattr(jax.config, k) for k in keys}
    for k, v in zip(keys, (str(tmp_path), 0, -1)):
        jax.config.update(k, v)
    cc.reset_cache()
    try:
        yield tmp_path
    finally:
        for k, v in before.items():
            jax.config.update(k, v)
        cc.reset_cache()


def test_decode_program_text_has_this_codes_names_past_a_stale_cache(
        compile_cache, monkeypatch):
    """The compile cache's key leaves names out, so the program an engine
    runs can be one that a commit without the scopes compiled (the parent's,
    on the chip in PR 25). The text is compiled under its own key."""
    import contextlib

    @contextlib.contextmanager
    def no_scope(name):
        yield

    with monkeypatch.context() as m:
        m.setattr(jax, "named_scope", no_scope)
        old = _engine(block_tokens=8)
        _, _, shapes = engine_mod._DECODE_PROGRAMS[old.obs_name]
        old._dispatch.lower(*shapes).compile()  # a commit before the scopes
    eng = _engine(block_tokens=8)
    ran = eng._dispatch.lower(*shapes).compile().as_text()
    assert "kv_gather" not in ran  # found in the cache, with the old names
    text = engine_mod.decode_program_text(eng.obs_name)
    for scope in LEAF_SCOPES:
        assert f"/{scope}/" in text, scope

    def instructions(t):
        return re.findall(r"^\s*(?:ROOT )?(%[^\s=]+) = ", t, re.M)

    assert instructions(text) == instructions(ran)


# -- a family with window layers: its scopes, counters and gauges -------------

def _window_engine(**kw):
    from nnstreamer_tpu.models.afmoe import FULL, SLIDING, AfmoeConfig

    cfg = AfmoeConfig(
        vocab=97, d_model=64, layer_types=(SLIDING, SLIDING, FULL),
        num_dense_layers=1, n_heads=8, n_kv_heads=2, head_dim=16, window=8,
        dense_width=96, num_experts=8, experts_per_token=2, expert_width=16,
        shared_width=16, experts_held=(0, 4), max_seq=64, dtype=jnp.float32,
        param_dtype=jnp.float32)
    return cfg, ContinuousBatchingEngine(
        cfg, cfg.family.init_params(cfg, 3), max_streams=2,
        steps_per_dispatch=2, block_tokens=4, min_bucket=8, **kw)


WINDOW_SCOPES = ("qkv", "kv_write", "kv_gather", "attend", "attend_window",
                 "attn_out", "dense_ffn", "router", "experts", "shared_ffn",
                 "logits", "sample")


def test_window_familys_programs_hold_every_scope_of_both_kinds():
    cfg, eng = _window_engine()
    _, _, shapes = engine_mod._DECODE_PROGRAMS[eng.obs_name]
    assert set(shapes[3]) == {"kv", "win"}  # both tables, by arena
    compiled = engine_mod.decode_program_text(eng.obs_name)
    for scope in WINDOW_SCOPES:
        assert f"/{scope}/" in compiled, scope
    text = _lowered_text(jax.jit(cfg.family.build_prefill(cfg)), eng.params,
                         jax.ShapeDtypeStruct((1, 16), jnp.int32))
    assert "module @jit_prefill" in text and "nns.prefill" in text
    for scope in set(WINDOW_SCOPES) - {"kv_write", "kv_gather", "sample"}:
        assert _has_scope(text, scope), scope


def test_window_counters_count_what_the_positions_say_and_a_dense_engine_has_none():
    _, eng = _window_engine()
    assert eng.stats["kv_window_blocks_live"] == 0 \
        and eng.stats["kv_window_blocks_released"] == 0
    assert eng.stats["decode_attention"] == "gather"
    assert type(eng.stats["kv_window_bytes_per_token"]) is int
    eng.start()
    try:
        out = _serve(eng, [_prompt(5)], max_new=13)
    finally:
        eng.stop()
    assert len(out[0].tokens) == 13
    # 6 dispatches of 2 steps from position 5: a window layer reads the
    # blocks (of 4) from the one that holds pos - 7 to the one that holds pos
    # (the other lane is empty: it reads its zero block, one a step, in
    # both counts, as ``kv_blocks_live`` always counted an empty lane)
    want_w = sum(p // 4 - max(0, p - 7) // 4 + 1 for p in range(5, 17)) + 12
    want = sum(p // 4 + 1 for p in range(5, 17)) + 12
    assert eng.stats["kv_window_blocks_live"] == want_w < want \
        == eng.stats["kv_blocks_live"]
    # after the last whole dispatch the lane stood at 17: blocks 0 and 1
    # (positions 0..7) lay wholly before 17 - 7 and went back, block 2 not
    assert eng.stats["kv_window_blocks_released"] == 2
    reg = get_registry()
    reg.snapshot()  # runs the collectors
    assert reg.get("nns_serving_kv_window_blocks",
                   engine=eng.obs_name).value == 2 * 4
    assert reg.get("nns_serving_kv_window_blocks_live",
                   engine=eng.obs_name).value == 0
    dense = _engine()
    assert not [k for k in dense.stats if "window" in k]
    assert "decode_attention" not in dense.stats
    assert all(type(v) is int for v in dense.stats.values())
    reg.snapshot()
    assert reg.get("nns_serving_kv_window_blocks",
                   engine=dense.obs_name) is None
