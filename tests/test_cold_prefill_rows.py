"""The cold admissions of one loop boundary share ONE prefill program a
bucket: the program of a bucket takes a fixed number of rows, set by the
model's weight bytes against its arithmetic a token
(``models/family.py prefill_rows``), and the engine fills them with the
boundary's prompts (``ContinuousBatchingEngine._prefill_groups``).

What is held here: the rows rule at the benchmark's six configurations'
full sizes, from shapes alone; and, at test sizes for the dense, Mamba-2
hybrid and gated-delta families, that a boundary's prompts prefilled
together give each stream what it gets admitted alone, that a row holding
no prompt writes nothing, how many programs a boundary takes, that a
deferral keeps FIFO order, and that with one row a bucket each prefill is
enqueued before the next request's host work, as before.
"""

import json
import math
import os
import queue
import threading

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from nnstreamer_tpu.models.family import (  # noqa: E402
    PREFILL_ROWS_MAX,
    balance_tokens,
    prefill_rows,
    serving_params,
    token_params,
)
from nnstreamer_tpu.models.hybrid import HybridConfig  # noqa: E402
from nnstreamer_tpu.models.transformer import TransformerConfig  # noqa: E402
from nnstreamer_tpu.serving import ContinuousBatchingEngine  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# -- the rows rule at the benchmark's configurations ---------------------------

#: configuration file -> (its module under ``benchmark/drivers``, the
#: function there that makes the config, the workload whose traffic it
#: serves)
BENCH = {
    "pythia_1p4b": ("lm", "transformer_config", "pythia_chat_closed"),
    "granite_4p0_h_small_ep2": ("lm_hybrid", "hybrid_config",
                                "granite_h_chat_closed"),
    "qwen3_next_80b_a3b_ep2": ("lm_qwen3_next", "qwen3_next_config",
                               "qwen3next_chat_closed"),
    "deepseek_v2_lite_ep2": ("lm_deepseek_v2", "deepseek_v2_config",
                             "dsv2lite_longctx_closed"),
    "trinity_large_ep8": ("lm_afmoe", "afmoe_config",
                          "trinity_longctx_closed"),
    "phi4_mini_flash_reasoning": ("lm_sambay", "sambay_config",
                                  "phi4flash_reason_closed"),
}


def _read(*path):
    with open(os.path.join(ROOT, "benchmark", *path)) as f:
        return json.load(f)


def _bench_rows(name):
    """{bucket: rows} over the buckets the cell's traffic reaches, from the
    configuration's shapes alone (``jax.eval_shape``: no weights)."""
    import importlib

    module, make, workload = BENCH[name]
    config = _read("configs", f"{name}.json")
    cfg = getattr(importlib.import_module(f"benchmark.drivers.{module}"),
                  make)(config)
    given = jax.eval_shape(lambda: cfg.family.init_params(cfg, 0))
    held = jax.eval_shape(lambda p: serving_params(cfg, p)[0], given)
    nbytes = sum(int(np.prod(a.shape)) * jnp.dtype(a.dtype).itemsize
                 for a in jax.tree_util.tree_leaves(held))
    balance = balance_tokens(cfg, held, nbytes)
    spec = _read("workloads", f"{workload}.json")["prompt_tokens"]

    def bucket(n):  # the engine's ``_bucket``
        b = int(config.get("min_bucket", 16))
        while b < n:
            b *= 2
        return min(b, cfg.max_seq)

    b, rows = bucket(spec["min"]), {}
    while b <= bucket(spec["max"]):
        rows[b] = prefill_rows(balance, b)
        b *= 2
    return balance, rows


@pytest.mark.parametrize("name", sorted(BENCH))
def test_rows_rule_at_the_benchmark_configurations(name):
    balance, rows = _bench_rows(name)
    assert rows
    for r in rows.values():  # a power of two, at most the cap
        assert 1 <= r <= PREFILL_ROWS_MAX and r & (r - 1) == 0
    if name == "qwen3_next_80b_a3b_ep2":
        # 7.98 GB held, 208 M parameters a token: a prompt of up to 256
        # tokens waits on its weights
        assert rows == {64: 8, 128: 4, 256: 2, 512: 1}, rows
    else:
        # granite (9.93 GB, 1.63 G a token), pythia (2.83 GB, 1.21 G) and
        # phi4flash (7.71 GB, 3.34 G): a prompt alone keeps the arithmetic
        # busy while the weights stream in; dsv2lite and trinity: long
        # prompts
        assert set(rows.values()) == {1}, rows


def test_rows_rule_takes_the_largest_power_of_two_under_the_balance():
    assert [prefill_rows(4096, b) for b in (64, 512, 1024, 2048, 4096)] \
        == [8, 8, 4, 2, 1]
    assert prefill_rows(2 * 300 - 1, 300) == 1
    assert prefill_rows(2 * 300, 300) == 2
    assert prefill_rows(0.0, 16) == 1


def test_token_params_read_a_routed_share_of_the_held_experts():
    cfg = HybridConfig(
        vocab=50, d_model=8, layer_types=("mamba", "attention"), n_heads=2,
        n_kv_heads=1, head_dim=4, ssm_heads=2, ssm_head_dim=4, ssm_state=4,
        num_experts=8, experts_per_token=2, expert_width=4, shared_width=4,
        experts_held=(0, 4), tie_embeddings=False)
    shapes = jax.eval_shape(lambda: cfg.family.init_params(cfg, 0))
    flat = jax.tree_util.tree_flatten_with_path(shapes)[0]
    whole = sum(int(np.prod(a.shape)) for p, a in flat if len(a.shape) >= 2
                and p[-1].key not in ("embed", "lm_head", "w_in", "w_out"))
    experts = sum(int(np.prod(a.shape)) for p, a in flat
                  if p[-1].key in ("w_in", "w_out"))
    # 2 of 8 router outputs a token: a quarter of the held experts' weights
    assert token_params(cfg, shapes) == pytest.approx(whole + experts / 4)


# -- the engine at test sizes ----------------------------------------------------

DENSE = TransformerConfig(vocab=4096, d_model=64, n_heads=4, n_layers=2,
                          d_ff=128, max_seq=64, dtype=jnp.float32)
MAMBA2 = HybridConfig(
    vocab=8192, d_model=64, layer_types=("mamba", "mamba", "attention"),
    n_heads=4, n_kv_heads=2, head_dim=16, attention_scale=0.2,
    ssm_heads=16, ssm_head_dim=8, ssm_state=16, ssm_conv=4, ssm_chunk=16,
    num_experts=8, experts_per_token=3, expert_width=32, shared_width=48,
    experts_held=(0, 4), embedding_multiplier=2.0, residual_multiplier=0.5,
    logits_scaling=0.125, max_seq=64, dtype=jnp.float32,
    param_dtype=jnp.float32)
DELTA = HybridConfig(
    vocab=4096, d_model=64, layer_types=("linear_attention",) * 2
    + ("attention",), n_heads=4, n_kv_heads=2, head_dim=16,
    attention_scale=0.25, rotary_dim=4, qk_norm=True, attn_gate=True,
    la_key_heads=2, la_value_heads=4, la_key_dim=8, la_value_dim=16,
    la_conv=4, la_chunk=8, num_experts=8, experts_per_token=3,
    expert_width=32, shared_width=32, shared_gate=True, experts_held=(0, 4),
    tie_embeddings=False, embedding_multiplier=1.0, residual_multiplier=1.0,
    logits_scaling=1.0, rms_eps=1e-6, max_seq=64, dtype=jnp.float32,
    param_dtype=jnp.float32)
FAMILIES = {"dense": DENSE, "mamba2": MAMBA2, "delta": DELTA}
_PARAMS = {}


def _params(cfg):
    if cfg not in _PARAMS:
        _PARAMS[cfg] = cfg.family.init_params(cfg, 5)
    return _PARAMS[cfg]


def _engine(cfg, one_row=False, **kw):
    kw.setdefault("max_streams", 8)
    kw.setdefault("steps_per_dispatch", 4)
    kw.setdefault("block_tokens", 8)
    eng = ContinuousBatchingEngine(cfg, _params(cfg), **kw)
    if one_row:  # every bucket's program at one row: a prompt a program
        eng._balance = 0.0
    return eng.start()


def _prompt(cfg, n, start):
    return ((np.arange(n) * 7 + start) % cfg.vocab).astype(np.int32)


def _one_boundary(eng, prompts, max_new=1):
    """Submit ``prompts`` while the loop can take none, then let it take
    them all: they are admitted at ONE loop boundary."""
    gate = threading.Event()
    pending = eng._pending
    real = pending.get_nowait

    def gated():
        if not gate.is_set():
            raise queue.Empty
        return real()

    pending.get_nowait = gated
    try:
        streams = [eng.submit(p, max_new_tokens=max_new) for p in prompts]
        gate.set()
        for s in streams:
            s.result(timeout=300)
    finally:
        del pending.get_nowait
    return streams


def _kept(eng, stream):
    """What the stream's prefill left: its rows in the blocks it held and
    its lane's state (read on an idle engine; nobody took them since)."""
    rows = eng._pool.stream_rows(stream.blocks, stream.prompt_len)
    state = eng._pool.lane_state(stream.lane) if stream.lane is not None \
        else {}
    return jax.tree.map(np.asarray, rows), state


LENGTHS = (5, 13, 20, 9, 27)  # buckets 16, 16, 32, 16, 32


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_one_program_gives_each_stream_what_it_gets_alone(family):
    cfg = FAMILIES[family]
    prompts = [_prompt(cfg, n, 3 * i + 1) for i, n in enumerate(LENGTHS)]
    alone = _engine(cfg, one_row=True)
    try:
        want = []
        for p in prompts:
            s, = _one_boundary(alone, [p])
            want.append((s.tokens[0], s.logprobs[0], *_kept(alone, s)))
        assert alone.stats["prefill_programs"] == len(prompts)
    finally:
        alone.stop()
    eng = _engine(cfg)
    try:
        assert eng._prefill_rows(32) >= len(prompts)
        streams = _one_boundary(eng, prompts)
        got = [(s.tokens[0], s.logprobs[0], *_kept(eng, s)) for s in streams]
    finally:
        eng.stop()
    assert eng.stats["prefill_programs"] == 1
    assert eng.stats["admissions"] == len(prompts)
    assert eng.stats["prefill_bucket_tokens"] == eng._prefill_rows(32) * 32
    for (tok, lp, rows, state), (tok1, lp1, rows1, state1) in zip(got, want):
        assert tok == tok1
        assert lp == pytest.approx(lp1, abs=1e-5)
        for a, b in zip(jax.tree_util.tree_leaves(rows),
                        jax.tree_util.tree_leaves(rows1)):
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)
        assert state.keys() == state1.keys()
        for name in state:
            np.testing.assert_allclose(state[name], state1[name],
                                       rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_rows_that_hold_no_prompt_write_no_block_and_no_lane(family):
    cfg = FAMILIES[family]
    eng = _engine(cfg)
    try:
        rows = eng._prefill_rows(16)
        assert rows >= 2
        s, = _one_boundary(eng, [_prompt(cfg, 11, 4)])
    finally:
        eng.stop()
    assert eng.stats["prefill_bucket_tokens"] == rows * 16
    arena = eng._pool.arena
    for kv in jax.tree_util.tree_leaves(eng._pool._kv(arena)):
        kv = np.asarray(kv)
        others = np.setdiff1d(np.arange(kv.shape[1]), s.blocks)
        assert np.abs(kv[:, list(s.blocks)]).max() > 0
        assert not np.any(kv[:, others])  # the zero block among them
    if eng._pool._lane_state:
        for leaf in arena["state"].values():
            leaf = np.asarray(leaf)
            assert np.abs(leaf[:, s.lane]).max() > 0
            assert not np.any(np.delete(leaf, s.lane, axis=1))


def _greedy_programs(rows_of, buckets):
    """The programs a boundary's cold prompts take: greedily, shortest
    bucket first, a program's bucket is that of its longest prompt and it
    takes up to that bucket's rows."""
    programs = []
    for b in sorted(buckets):
        if programs and len(programs[-1]) < rows_of(max(programs[-1] + [b])):
            programs[-1].append(b)
        else:
            programs.append([b])
    return len(programs)


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_a_boundary_takes_ceil_n_over_rows_programs(family):
    cfg = FAMILIES[family]
    eng = _engine(cfg, max_streams=32, kv_blocks=256)
    try:
        rows16 = eng._prefill_rows(16)
        same = [_prompt(cfg, 5 + i % 7, i) for i in range(2 * rows16 + 1)]
        _one_boundary(eng, same)
        assert eng.stats["prefill_programs"] == math.ceil(len(same) / rows16)
        before = eng.stats["prefill_programs"]
        mixed = [_prompt(cfg, n, i) for i, n in
                 enumerate((5, 40, 6, 7, 33, 9, 10, 11, 12, 3, 4, 50, 2))]
        _one_boundary(eng, mixed)
        took = eng.stats["prefill_programs"] - before
    finally:
        eng.stop()
    want = _greedy_programs(eng._prefill_rows,
                            [eng._bucket(p.size) for p in mixed])
    # a program per bucket's worth of rows, or more where buckets differ
    rows = max(eng._prefill_rows(eng._bucket(p.size)) for p in mixed)
    assert math.ceil(len(mixed) / rows) <= took == want < len(mixed)
    assert eng.stats["prefill_programs"] <= eng.stats["admissions"]


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_a_deferral_mid_boundary_keeps_fifo_order(family):
    cfg = FAMILIES[family]
    prompts = [_prompt(cfg, 20, 10 * i + 1) for i in range(4)]
    alone = _engine(cfg, one_row=True)
    try:
        want = [_one_boundary(alone, [p], max_new=3)[0].tokens
                for p in prompts]
    finally:
        alone.stop()
    # three blocks of 8 a prompt of 20: the pool covers two at once
    eng = _engine(cfg, kv_blocks=7)
    try:
        assert eng._prefill_rows(32) >= 2
        streams = _one_boundary(eng, prompts, max_new=3)
    finally:
        eng.stop()
    assert eng.stats["kv_defers"] >= 1
    assert [s.tokens for s in streams] == want
    assert all(s.finish_reason == "length" for s in streams)
    # admitted and first served in the order submitted
    firsts = [s.first_t for s in streams]
    assert firsts == sorted(firsts)
    admits = [s.admit_t for s in streams]
    assert admits == sorted(admits)
    assert eng.stats["prefill_programs"] < eng.stats["admissions"] == 4
    assert eng._pool.live_blocks() == 0


def _program_order(eng, prompts):
    """The loop's host events of one boundary: ``admit`` as it takes a
    request, then the name of each program it enqueues."""
    names = {id(eng._prefill_jitted): "prefill",
             id(eng._sample_first): "sample"}
    events = []
    begin, enqueue = eng._begin_admission, eng._enqueue

    def begin_admission(req):
        events.append("admit")
        return begin(req)

    def record(program, *args, **kwargs):
        name = names.get(id(program)) or getattr(program, "__name__", "")
        events.append("scatter" if name.startswith("scatter") else name)
        return enqueue(program, *args, **kwargs)

    eng._begin_admission, eng._enqueue = begin_admission, record
    try:
        _one_boundary(eng, prompts)
    finally:
        del eng._begin_admission, eng._enqueue
    return events[:events.index("dispatch")] if "dispatch" in events \
        else events


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_one_row_enqueues_each_prefill_before_the_next_admission(family):
    cfg = FAMILIES[family]
    prompts = [_prompt(cfg, 9, 1), _prompt(cfg, 12, 2)]
    one = _engine(cfg, one_row=True)
    try:
        events = _program_order(one, prompts)
    finally:
        one.stop()
    assert events == ["admit", "prefill", "scatter", "sample"] * 2
    assert one.stats["prefill_programs"] == one.stats["admissions"] == 2
    rows = _engine(cfg)
    try:
        events = _program_order(rows, prompts)
    finally:
        rows.stop()
    assert events == ["admit", "admit", "prefill", "scatter", "sample"]
    assert rows.stats["prefill_programs"] == 1


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_warmed_buckets_serve_a_boundary_with_no_new_program(family):
    cfg = FAMILIES[family]
    eng = _engine(cfg, max_streams=16, kv_blocks=128)
    try:
        buckets = (16, 32, 64)
        # the scatter's jit wraps one module-level function: its cache also
        # holds what other engines of this process compiled
        scatter0 = eng._pool._jit_scatter._cache_size()
        for b in buckets:  # one request a bucket, as the benchmark warms
            _one_boundary(eng, [_prompt(cfg, b - 8, b)])
        sizes = (eng._prefill_jitted._cache_size(),
                 eng._sample_first._cache_size(),
                 eng._pool._jit_scatter._cache_size() - scatter0)
        _one_boundary(eng, [_prompt(cfg, n, n) for n in
                            (3, 17, 40, 9, 30, 50, 6, 12, 25)])
        assert (eng._prefill_jitted._cache_size(),
                eng._sample_first._cache_size(),
                eng._pool._jit_scatter._cache_size() - scatter0) == sizes
    finally:
        eng.stop()
    # one prefill a bucket; one sample a number of rows; one scatter a
    # bucket or fewer (the dense cache has the same length at every bucket)
    assert sizes[0] == len(buckets)
    assert sizes[1] == len({eng._prefill_rows(b) for b in buckets})
    assert sizes[2] <= len(buckets)


def test_prefill_programs_reach_the_registry_and_hold_their_invariants():
    from nnstreamer_tpu.obs import get_registry

    cfg = DENSE
    one, rows = _engine(cfg, one_row=True), _engine(cfg)
    try:
        prompts = [_prompt(cfg, n, n) for n in (4, 9, 30, 11)]
        for eng in (one, rows):
            _one_boundary(eng, prompts)
    finally:
        one.stop()
        rows.stop()
    # a prompt a program where a bucket takes one; fewer where it takes more
    assert one.stats["prefill_programs"] == one.stats["admissions"] == 4
    assert rows.stats["prefill_programs"] < rows.stats["admissions"] == 4
    reg = get_registry()
    reg.snapshot()  # runs the collectors
    for eng in (one, rows):
        c = reg.get("nns_serving_prefill_programs_total",
                    engine=eng.obs_name)
        assert c is not None and c.value == eng.stats["prefill_programs"]
