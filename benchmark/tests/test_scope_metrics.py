"""CPU-only tests of what PR 25 added to the benchmark: the reduction of a
device trace by named scope, the mean of engine counters, and that the LM
driver's run carries the engine's new counters to them.

    python -m pytest benchmark/tests -q
"""

import time

import pytest

from benchmark import run as bench_run
from benchmark import scope_reduce, trace_reduce
from benchmark.readers import engine_stat_mean, scope_share
from benchmark.tests.test_benchmark import TINY_LM, TINY_LM_TRAFFIC

DEV = "/device:TPU:0"
LEAVES = ("qkv", "kv_write", "kv_gather", "attend", "ffn", "logits", "sample")
#: the optimized program's text, cut to what the reduction reads
TEXT = """
HloModule jit_dispatch, entry_computation_layout={()->()}

%body.1 (p: f32[8]) -> f32[8] {
  %fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop, calls=%f.1, metadata={op_name="jit(dispatch)/while/body/nns.decode/while/body/qkv/dot_general" stack_frame_id=4}
  %fusion.2 = f32[8]{0} fusion(f32[8]{0} %fusion.1), kind=kLoop, calls=%f.2, metadata={op_name="jit(dispatch)/while/body/nns.decode/while/body/kv_gather/gather"}
  ROOT %fusion.3 = f32[8]{0} fusion(f32[8]{0} %fusion.2), kind=kOutput, calls=%f.3, metadata={op_name="jit(dispatch)/while/body/nns.decode/while/body/attend/jit(softmax)/exp"}
}

ENTRY %main.9 (a: f32[8]) -> f32[8] {
  %while.2 = f32[8]{0} while(f32[8]{0} %a), condition=%cond.1, body=%body.0, metadata={op_name="jit(dispatch)/while"}
  %while.1 = f32[8]{0} while(f32[8]{0} %a), condition=%cond.1, body=%body.1, metadata={op_name="jit(dispatch)/while/body/nns.decode/while"}
  %copy.7 = f32[8]{0} copy(f32[8]{0} %while.1)
  ROOT %fusion.9 = f32[8]{0} fusion(f32[8]{0} %copy.7), kind=kLoop, calls=%f.9, metadata={op_name="jit(dispatch)/while/body/sample/argmax"}
}
"""


def _op(name, start, dur):
    return (DEV, f"{name} = f32[8]{{0}} fusion(f32[8]{{0}} %x)", start, dur)


def _execution(t):
    """One jit_dispatch execution of 1000 ns at ``t``: an outer while of 900
    holding an inner while of 600 (qkv 100, kv_gather 300, attend 150, and 50
    of its own), a compiler-made copy of 200 and the sample of 50 (so 50 of
    the outer loop's own); then a copy of 60 outside every loop."""
    return [
        _op("%while.2", t, 900), _op("%while.1", t + 10, 600),
        _op("%fusion.1", t + 20, 100), _op("%fusion.2", t + 130, 300),
        _op("%fusion.3", t + 440, 150), _op("%copy.7", t + 620, 200),
        _op("%fusion.9", t + 830, 50), _op("%copy.7", t + 920, 60)]


OPS = _execution(1000) + _execution(5000) + [
    # another program's operation: not inside an execution of jit_dispatch
    (DEV, "%fusion.1 = f32[8]{0} fusion()", 3000, 500)]
MODULES = [(DEV, "jit_dispatch(123)", 1000, 1000),
           (DEV, "jit_dispatch(123)", 5000, 1000),
           (DEV, "jit_prefill(9)", 3000, 500)]


def test_self_times_take_nested_events_out_once():
    got = dict(scope_reduce.self_times(
        [("outer", 0, 100), ("inner", 10, 60), ("leaf", 20, 30),
         ("next", 100, 5)]))
    assert got == {"outer": 40, "inner": 30, "leaf": 30, "next": 5}


def test_op_names_and_scope_of_read_the_programs_text():
    names = scope_reduce.op_names(TEXT)
    assert names["%fusion.2"].endswith("/kv_gather/gather")
    assert names["%copy.7"] == "" and "%body.1" not in names
    assert scope_reduce.instruction(OPS[0][1]) == "%while.2"
    assert scope_reduce.scope_of(names["%fusion.3"], LEAVES) == "attend"
    assert scope_reduce.scope_of(names["%while.1"], LEAVES) == "unscoped"
    assert scope_reduce.scope_of("", LEAVES) == "unscoped"


def test_by_scope_adds_up_to_the_union_and_the_shares_to_100():
    found = scope_reduce.by_scope(OPS, MODULES, "jit_dispatch",
                                  scope_reduce.op_names(TEXT), LEAVES)
    assert found["executions"] == 2
    inside = [(s, s + d) for _, _, s, d in OPS if not 3000 <= s < 3500]
    union = sum(b - a for a, b in trace_reduce.union(inside)) / 1e9
    assert found["total_s"] == pytest.approx(union) == pytest.approx(1920e-9)
    assert found["known_s"] == pytest.approx(found["total_s"])
    ns = {k: round(v * 1e9) for k, v in found["seconds"].items()}
    assert ns == {"qkv": 200, "kv_gather": 600, "attend": 300, "sample": 100,
                  # the loops' own time and the two copies
                  "unscoped": 2 * (50 + 50 + 200 + 60)}
    share = {k: 100 * v / found["total_s"] for k, v in found["seconds"].items()}
    compute = sum(share.get(s, 0) for s in
                  ("qkv", "attend", "ffn", "logits", "sample"))
    move = sum(share.get(s, 0) for s in ("kv_write", "kv_gather"))
    assert compute + move + share["unscoped"] == pytest.approx(100.0)
    # a text of another program names too little: the caller can tell
    other = scope_reduce.by_scope(OPS, MODULES, "jit_dispatch",
                                  {"%while.2": ""}, LEAVES)
    assert other["known_s"] < 0.1 * other["total_s"]


def test_scope_share_reads_nothing_where_there_is_nothing(tmp_path,
                                                          monkeypatch):
    args = dict(program="jit_dispatch", leaves=list(LEAVES), count=["qkv"])
    assert scope_share.read({}, **args) is None            # an untraced run
    monkeypatch.setattr(bench_run, "WORKDIR", str(tmp_path))
    assert scope_share.read({"trace": {"programs": {}}}, **args) is None
    assert scope_reduce.newest_xplane(str(tmp_path)) is None


def test_engine_stat_mean_divides_growth_and_returns_nothing_on_nothing():
    stats = {"loop_us": 50_000_000, "phase_dispatch_us": 42_000_000,
             "phase_first_token_us": 5_000_000, "phase_idle_us": 2_000_000,
             "dispatches": 100, "admit_wait_us": 900_000, "admissions": 90}
    run = {"engine_stats": stats}
    host = dict(plus=["loop_us"], per="dispatches", scale=0.001, minus=[
        "phase_dispatch_us", "phase_first_token_us", "phase_idle_us"])
    assert engine_stat_mean.read(run, **host) == pytest.approx(10.0)
    assert engine_stat_mean.read(run, ["admit_wait_us"], "admissions",
                                 scale=0.001) == pytest.approx(10.0)
    assert engine_stat_mean.read(
        {"engine_stats": {**stats, "dispatches": 0}}, **host) is None
    # a commit before PR 25 has no such counters
    assert engine_stat_mean.read({"engine_stats": {"dispatches": 7}},
                                 **host) is None
    assert engine_stat_mean.read({}, **host) is None


def test_the_lm_cell_has_ten_per_layer_metrics():
    cell = bench_run.load_cell("pythia_chat_closed")
    assert len(cell["per_layer"]) == 10
    added = [m for m in cell["per_layer"]
             if m["reader"] in ("scope_share", "engine_stat_mean")]
    assert len(added) == 5
    leaves = {tuple(m["args"]["leaves"]) for m in added
              if m["reader"] == "scope_share"}
    assert leaves == {LEAVES}  # one list, or the three shares do not sum
    counted = sorted(s for m in added if m["reader"] == "scope_share"
                     for s in m["args"]["count"])
    assert counted == sorted(LEAVES + ("unscoped",))


def test_lm_driver_run_carries_the_engines_new_counters(tmp_path):
    from benchmark.drivers import lm

    out = lm.run_cell(TINY_LM, TINY_LM_TRAFFIC, 11, 1.5, False,
                      t0=time.monotonic(), workdir=str(tmp_path))
    stats = out["engine_stats"]
    assert stats == out["detail"]["engine_stats"]
    phases = [k for k in stats if k.startswith("phase_")]
    assert len(phases) == 7
    assert sum(stats[k] for k in phases) == stats["loop_us"]
    # the loop's clock grew by the window's length
    assert stats["loop_us"] == pytest.approx(1.5e6, rel=0.1)
    assert stats["admissions"] > 0 and stats["admit_wait_us"] > 0
    cell = bench_run.load_cell("pythia_chat_closed")
    values = bench_run.read_layer_metrics(cell, {**out, "config": TINY_LM})
    assert values["engine_host_ms_per_dispatch.closed"]["value"] > 0
    assert values["admit_wait_ms.closed"]["value"] > 0
    assert "kv_move_share.closed" not in values  # no trace was taken
