"""CPU-only tests of what the hybrid cell adds to the benchmark: its driver
on a tiny dict, its configuration file against the public catalog entry, its
byte counts, its readers on a fixture and on nothing.

    python -m pytest benchmark/tests -q
"""

import json
import os
import time

import pytest

from benchmark import run as bench_run
from benchmark import scope_reduce, work_hybrid
from benchmark.readers import scope_roofline

ROOT = bench_run.ROOT
CELL = "granite_h_chat_closed"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
TINY = dict(
    name="tiny", vocab_size=211, hidden_size=64,
    layer_types=["mamba", "mamba", "attention", "mamba"],
    num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    attention_multiplier=0.25, mamba_n_heads=16, mamba_d_head=8,
    mamba_d_state=16, mamba_d_conv=4, mamba_chunk_size=16, mamba_n_groups=1,
    mamba_expand=2, router_outputs=8, num_experts_per_tok=3,
    intermediate_size=32, shared_intermediate_size=48, experts_held=[0, 4],
    embedding_multiplier=2, residual_multiplier=0.5, logits_scaling=0.125,
    rms_norm_eps=1e-5, max_position_embeddings=256, dtype="float32",
    param_dtype="float32", ssm_state_dtype="float32", max_streams=4,
    block_tokens=16, steps_per_dispatch=8, temperature=0.0, attention="auto",
    prefix_cache=0)
TINY_TRAFFIC = dict(
    loop="closed", clients=4, requests=64, warm_requests=4,
    prompt_tokens=dict(distribution="log_uniform", min=8, max=64),
    output_tokens=dict(distribution="log_uniform", min=8, max=32),
    check_prompt_tokens=[12, 40], check_new_tokens=16, check_pad_to=64,
    logprob_tol=1e-4, argmax_tol=1e-4, state_tol=1e-4, first_state_tol=1e-4,
    conv_tol=1e-4,
    request_timeout_s=60,
    trace_seconds=0.3)
LEAVES = ("ssm_in", "ssm_conv", "ssm_update", "ssm_out", "qkv", "kv_write",
          "kv_gather", "attend", "router", "experts", "shared_ffn", "logits",
          "sample")


@pytest.fixture(scope="module")
def tiny_run(tmp_path_factory):
    from benchmark.drivers import lm_hybrid

    return lm_hybrid.run_cell(TINY, TINY_TRAFFIC, 2147483659, 1.5, False,
                              t0=time.monotonic(),
                              workdir=str(tmp_path_factory.mktemp("work")))


def test_hybrid_driver_runs_a_tiny_dict(tiny_run):
    out = tiny_run
    assert {"correct", "attempted", "failed", "end_to_end", "detail"} \
        <= set(out)
    assert out["correct"] and out["failed"] == 0 and out["attempted"] > 0
    assert out["end_to_end"]["lm_tokens_per_s"] > 0
    check = out["detail"]["check"]
    assert check["ok"] and check["tokens_each"] == 16
    assert check["max_logprob_diff"] < 1e-4
    # two check prompts in the lanes two cancelled fillers left (1 and 3 of
    # 4), and the filler beside each: the state of the first two is held
    assert check["lanes"] == [1, 3, 2, 0] and check["requests"] == 4
    assert 0 < check["max_state_diff"] < 1e-4
    assert 0 < check["max_conv_diff"] < 1e-4
    assert "check_s" in out["detail"]["setup_phases"]
    assert check["reference_s"] > 0  # after the window, outside setup_s
    pool = out["detail"]["pool"]
    assert pool["state_slots"] == 4 and pool["state_bytes"] > 0
    assert json.dumps(out["detail"])  # the detail line is plain data


def test_hybrid_driver_carries_the_expert_counters(tiny_run):
    stats = tiny_run["engine_stats"]
    steps = stats["dispatches"] * TINY["steps_per_dispatch"]
    assert stats["moe_layer_steps"] == steps * len(TINY["layer_types"])
    # a lane counts for all the steps of a dispatch it was bound in, also
    # those after its stream's last token
    routed = (stats["moe_tokens_held"] + stats["moe_tokens_absent"]) \
        // (3 * len(TINY["layer_types"]))
    assert stats["active_slot_steps"] <= routed <= stats["slot_steps"]
    assert 0 < stats["moe_experts_hit"] <= 4 * stats["moe_layer_steps"]
    cell = bench_run.load_cell(CELL)
    values = bench_run.read_layer_metrics(cell, {**tiny_run, "config": TINY})
    # held experts are 36 in the cell's own file: the reader's scale is 1/36
    assert values["expert_tokens_per_step.closed"]["value"] == pytest.approx(
        stats["moe_tokens_held"] / stats["moe_layer_steps"] / 36)
    assert values["engine_occupancy.closed"]["value"] > 0
    for name in ("ssm_share.closed", "moe_hbm_share.closed",
                 "decode_hbm_share.closed", "kv_move_share.closed"):
        assert name not in values  # no trace was taken


@pytest.mark.parametrize("control, limit", [
    ("none", None), ("renormalised_gates", "logprob_tol"),
    ("bf16_state", "first_state_tol"), ("state_unchanged", "conv_tol")])
def test_the_check_passes_the_program_and_refuses_each_control(control,
                                                               limit):
    """The comparison that decides ``correct`` tells the program from its
    nearest wrong neighbours (``benchmark/controls_hybrid.py``, which the
    chip runs at the cell's size): the expert layer with its gates
    renormalised over the held experts, a recurrent state one precision
    lower, a decode step that leaves the state alone."""
    from benchmark import controls_hybrid

    out = controls_hybrid.run_control({**TINY, "max_streams": 8},
                                      TINY_TRAFFIC, 5, control)
    assert out["refused"] == (control != "none")
    assert out["lanes"] == [2, 6, 3, 7]  # other lanes than the first
    if limit is None:
        assert out["max_logprob_diff"] < 1e-5 and not out["bad"]
    else:
        assert limit in {b["limit"] for b in out["bad"]}


def test_config_file_holds_the_catalog_entrys_numbers():
    if not os.path.exists(CATALOG):
        pytest.skip("the public catalog is not on this machine")
    with open(CATALOG, encoding="utf-8") as f:
        entry = next(e for e in map(json.loads, f)
                     if e["name"] == "granite-4.0-h-small")
    bench = bench_run.load_json(ROOT, "BENCHMARK.json")
    conf = next(c for c in bench["configs"]
                if c["name"] == "granite_4p0_h_small_ep2")
    mine = bench_run.load_json(ROOT, conf["file"])
    assert conf["source"] == mine["source"] == entry["source_url"]
    assert set(conf["reduced"]) == {"num_hidden_layers", "layer_types",
                                    "num_local_experts",
                                    "max_position_embeddings"}
    for key, value in entry["config"].items():
        if key not in conf["reduced"]:
            assert mine[key] == value, key
    period = entry["config"]["layer_types"][:10]
    assert mine["layer_types"] == period  # one whole period
    assert entry["config"]["layer_types"] == period * 4
    assert mine["num_hidden_layers"] == len(period) == 10
    assert mine["router_outputs"] == entry["config"]["num_local_experts"]
    lo, hi = mine["experts_held"]
    assert hi - lo == mine["num_local_experts"] == 36
    assert mine["published"]["num_local_experts"] == 72


def test_configuration_builds_the_published_widths():
    from benchmark.drivers import lm_hybrid

    cfg = lm_hybrid.hybrid_config(
        bench_run.load_cell(CELL)["config"])
    assert (cfg.d_model, cfg.vocab, cfg.n_layers) == (4096, 100352, 10)
    assert (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state, cfg.ssm_conv,
            cfg.ssm_chunk) == (128, 64, 128, 4, 256)
    assert (cfg.d_inner, cfg.conv_dim) == (8192, 8448)
    assert (cfg.n_heads, cfg.n_kv_heads, cfg.head_dim) == (32, 8, 128)
    assert cfg.attention_scale == 0.0078125
    assert (cfg.num_experts, cfg.experts_per_token, cfg.expert_width,
            cfg.shared_width, cfg.experts_held) == (72, 10, 768, 1536,
                                                    (0, 36))
    assert (cfg.embedding_multiplier, cfg.residual_multiplier,
            cfg.logits_scaling, cfg.rms_eps) == (12.0, 0.22, 16.0, 1e-5)
    assert (cfg.ssm_layers, cfg.attn_layers) == (9, 1)


def test_bytes_of_the_real_configuration_are_the_issues_arithmetic():
    import jax

    from benchmark.drivers import lm_hybrid

    cfg = lm_hybrid.hybrid_config(bench_run.load_cell(CELL)["config"])
    shapes = jax.eval_shape(lambda: cfg.family.init_params(cfg, 0))
    parts = work_hybrid.param_bytes(shapes)
    assert parts["one_expert"] == 2 * (4096 * 1536 + 768 * 4096)
    assert parts["experts"] == 10 * 36 * parts["one_expert"]
    assert parts["head"] == 2 * 100352 * 4096
    assert parts["attn"] == 2 * (2 * 4096 ** 2 + 2 * 4096 * 1024)
    weights = sum(parts[k] for k in ("ssm", "attn", "moe_fixed", "experts",
                                     "head", "norms"))
    assert weights == pytest.approx(9.93e9, rel=0.005)
    lane = work_hybrid.state_bytes_per_lane(cfg)
    assert lane == 9 * (128 * 64 * 128 * 4 + 3 * 8448 * 2)
    assert work_hybrid.kv_bytes_per_token(cfg) == 4096
    work = work_hybrid.decode_bytes_per_step(
        shapes, cfg, lanes_live=64, experts_hit_per_layer=36,
        live_tokens=64 * 400)
    assert work["moe_bytes_per_step"] == parts["moe_fixed"] + parts["experts"]
    assert work["ssm_bytes_per_step"] == parts["ssm"] + 2 * 64 * lane
    assert work["decode_bytes_per_step"] == pytest.approx(
        weights + 2 * 64 * lane + 4096 * 64 * 400)
    half = work_hybrid.decode_bytes_per_step(
        shapes, cfg, lanes_live=32, experts_hit_per_layer=18, live_tokens=0)
    assert half["moe_bytes_per_step"] == parts["moe_fixed"] \
        + parts["experts"] / 2
    assert half["ssm_bytes_per_step"] == parts["ssm"] + 64 * lane


def test_the_hybrid_cell_has_fifteen_per_layer_metrics_and_pythia_its_ten():
    cell = bench_run.load_cell(CELL)
    assert len(cell["per_layer"]) == 15
    assert {m["name"] for m in cell["end_to_end"]} == {"lm_tokens_per_s",
                                                       "setup_s"}
    # the whole step's memory roofline and the share that moves keys and
    # values are the dense cell's own metrics, read here as they are there
    shared = {m["name"]: m for m in cell["per_layer"]}
    assert shared["decode_hbm_share.closed"]["args"]["work"] \
        == "decode_bytes_per_step"
    assert shared["kv_move_share.closed"]["args"]["count"] \
        == ["kv_write", "kv_gather"]
    scoped = [m for m in cell["per_layer"]
              if m["reader"] in ("scope_share", "scope_roofline")
              and m["name"] != "kv_move_share.closed"]
    assert {tuple(m["args"]["leaves"]) for m in scoped} == {LEAVES}
    shares = sorted(s for m in scoped if m["reader"] == "scope_share"
                    for s in m["args"]["count"])
    # with logits and sample they are every leaf and what lies under none
    assert shares == sorted(set(LEAVES) - {"logits", "sample"}
                            | {"unscoped"})
    pythia = bench_run.load_cell("pythia_chat_closed")
    assert len(pythia["per_layer"]) == 10
    assert not {m["name"] for m in pythia["per_layer"]} & {
        "ssm_share.closed", "moe_hbm_share.closed"}
    assert cell["entry"]["chips"] == 1
    assert cell["workload"]["clients"] == cell["config"]["max_streams"] == 64


# -- the reader of a roofline share under scopes -----------------------------

TEXT = """
  %fusion.1 = f32[8]{0} fusion(%p), kind=kLoop, metadata={op_name="jit(dispatch)/nns.decode/experts/dot"}
  %fusion.2 = f32[8]{0} fusion(%p), kind=kLoop, metadata={op_name="jit(dispatch)/nns.decode/ssm_update/mul"}
  %copy.3 = f32[8]{0} copy(%p)
"""


def _events(starts, whole=1000, cut=None):
    """Executions of jit_dispatch at ``starts`` (ns), each with 600 ns
    under ``experts``, 300 under ``ssm_update`` and 100 unscoped; ``cut``
    executions are seen only in part."""
    ops, modules = [], []
    for i, s in enumerate(starts):
        dur = whole if cut is None or i not in cut else whole // 2
        modules.append((0, "jit_dispatch(123)", s, dur))
        for name, at, d in (("%fusion.1 = f32[8]{0} fusion(%p)", 0, 600),
                            ("%fusion.2 = f32[8]{0} fusion(%p)", 600, 300),
                            ("%copy.3 = f32[8]{0} copy(%p)", 900, 100)):
            if at + d <= dur:
                ops.append((0, name, s + at, d))
    return ops, modules


def test_seconds_by_execution_sum_the_scopes_of_each_run():
    ops, modules = _events([0, 2000, 4000])
    runs = scope_roofline.seconds_by_execution(
        ops, modules, "jit_dispatch", scope_reduce.op_names(TEXT),
        ("experts", "ssm_update"), frozenset(["experts"]))
    assert runs == [pytest.approx((600e-9, 1000e-9, 1000e-9))] * 3


def _read(monkeypatch, tmp_path, ops, modules, text=TEXT, work=1200.0):
    from benchmark import trace_reduce
    from nnstreamer_tpu.serving import engine

    monkeypatch.setattr(scope_reduce, "newest_xplane",
                        lambda workdir: str(tmp_path / "x.pb"))
    monkeypatch.setattr(trace_reduce, "read_events",
                        lambda path: (ops, modules, None))
    monkeypatch.setattr(engine, "decode_program_text", lambda: text)
    run = {"trace": {"moe_bytes_per_step": work},
           "config": {"steps_per_dispatch": 2},
           "peaks": {"hbm_bytes_per_s": 8e9}}
    return scope_roofline.read(
        run, program="jit_dispatch", leaves=["experts", "ssm_update"],
        count=["experts"], work="moe_bytes_per_step", peak="hbm_bytes_per_s",
        steps="steps_per_dispatch")


def test_scope_roofline_takes_the_median_execution(monkeypatch, tmp_path):
    # 600 ns under the scope over 2 steps = 300 ns a step; 1200 B a step
    # over 300 ns = 4e9 B/s = 50 % of the peak; the two executions the
    # trace cut short (first and last) do not pull the time down
    ops, modules = _events([0, 2000, 4000, 6000, 8000], cut={0, 4})
    assert _read(monkeypatch, tmp_path, ops, modules) == pytest.approx(50.0)


def test_scope_roofline_reads_nothing_where_there_is_nothing(monkeypatch,
                                                              tmp_path):
    ops, modules = _events([0, 2000, 4000])
    assert _read(monkeypatch, tmp_path, ops, modules) == pytest.approx(50.0)
    # no work, too few executions, a text that is another program's, none
    assert _read(monkeypatch, tmp_path, ops, modules, work=0) is None
    assert _read(monkeypatch, tmp_path, *_events([0, 2000])) is None
    assert _read(monkeypatch, tmp_path, ops, modules,
                 text="%other.9 = f32[] add()") is None
    assert _read(monkeypatch, tmp_path, ops, modules, text=None) is None
    assert scope_roofline.read(
        {}, program="jit_dispatch", leaves=[], count=[], work="w",
        peak="hbm_bytes_per_s", steps="steps_per_dispatch") is None


def test_parent_without_the_family_reads_nothing_and_does_not_raise():
    """The driver lays these files over the parent's checkout too: a
    program with no hybrid counters leaves the new metrics out."""
    cell = bench_run.load_cell(CELL)
    run = {"engine_stats": {"dispatches": 7, "slot_steps": 10,
                            "active_slot_steps": 9},
           "detail": {}, "config": cell["config"], "peaks": {}}
    values = bench_run.read_layer_metrics(cell, run)
    assert set(values) == {"engine_occupancy.closed"}
