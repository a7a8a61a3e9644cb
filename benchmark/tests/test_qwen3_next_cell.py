"""CPU-only tests of what the Qwen3-Next cell adds to the benchmark: its
driver and its controls on a tiny dict, its configuration file against the
public catalog entry, its byte counts, and the data of its metrics.

    python -m pytest benchmark/tests -q
"""

import json
import os
import time

import pytest

from benchmark import run as bench_run
from benchmark import work_qwen3_next

ROOT = bench_run.ROOT
CELL = "qwen3next_chat_closed"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
TINY = dict(
    name="tiny", vocab_size=211, hidden_size=64, num_hidden_layers=4,
    full_attention_interval=4, num_attention_heads=4, num_key_value_heads=2,
    head_dim=16, partial_rotary_factor=0.25, rope_theta=10000,
    rope_scaling=None, use_sliding_window=False, hidden_act="silu",
    linear_num_key_heads=2, linear_num_value_heads=4, linear_key_head_dim=8,
    linear_value_head_dim=16, linear_conv_kernel_dim=4, linear_chunk_size=8,
    router_outputs=8, num_experts_per_tok=3, norm_topk_prob=True,
    moe_intermediate_size=32, shared_expert_intermediate_size=32,
    mlp_only_layers=[], decoder_sparse_step=1, experts_held=[0, 4],
    tie_word_embeddings=False, rms_norm_eps=1e-6,
    max_position_embeddings=256, dtype="float32", param_dtype="float32",
    ssm_state_dtype="float32", max_streams=4, block_tokens=16,
    steps_per_dispatch=8, temperature=0.0, attention="auto", prefix_cache=0)
TINY_TRAFFIC = dict(
    loop="closed", clients=4, requests=64, warm_requests=4,
    prompt_tokens=dict(distribution="log_uniform", min=8, max=64),
    output_tokens=dict(distribution="log_uniform", min=8, max=32),
    check_prompt_tokens=[12, 40], check_new_tokens=16, check_pad_to=64,
    logprob_tol=1e-4, logprob_max_tol=1e-4, argmax_tol=1e-4, state_tol=1e-4,
    first_state_tol=1e-4, conv_tol=1e-4,
    request_timeout_s=60,
    trace_seconds=0.3)
LEAVES = ("la_in", "la_conv", "la_update", "la_out", "qkv", "kv_write",
          "kv_gather", "attend", "router", "experts", "shared_ffn", "logits",
          "sample")


@pytest.fixture(scope="module")
def tiny_run(tmp_path_factory):
    from benchmark.drivers import lm_qwen3_next

    return lm_qwen3_next.run_cell(TINY, TINY_TRAFFIC, 2147483659, 1.5, False,
                                  t0=time.monotonic(),
                                  workdir=str(tmp_path_factory.mktemp("work")))


def test_driver_runs_a_tiny_dict(tiny_run):
    out = tiny_run
    assert out["correct"] and out["failed"] == 0 and out["attempted"] > 0
    assert out["end_to_end"]["lm_tokens_per_s"] > 0
    check = out["detail"]["check"]
    assert check["ok"] and check["tokens_each"] == 16
    assert check["mean_logprob_diff"] <= check["max_logprob_diff"] < 1e-4
    assert check["lanes"] == [1, 3, 2, 0] and check["requests"] == 4
    # every reading of every request; the state for the check lanes alone
    assert [r["lane"] for r in check["by_request"]] == check["lanes"]
    assert ["state" in r for r in check["by_request"]] == [True, True,
                                                           False, False]
    assert 0 < check["max_state_diff"] < 1e-4
    assert 0 < check["max_conv_diff"] < 1e-4
    assert check["reference_s"] > 0  # after the window, outside setup_s
    pool = out["detail"]["pool"]
    assert pool["state_slots"] == 4
    assert pool["state_bytes"] == 4 * 3 * (4 * 8 * 16 * 4 + 3 * 96 * 4)
    assert json.dumps(out["detail"])  # the detail line is plain data


def test_driver_carries_the_counters_its_metrics_read(tiny_run):
    stats = tiny_run["engine_stats"]
    steps = stats["dispatches"] * TINY["steps_per_dispatch"]
    assert stats["moe_layer_steps"] == steps * TINY["num_hidden_layers"]
    assert 0 < stats["moe_experts_hit"] <= 4 * stats["moe_layer_steps"]
    cell = bench_run.load_cell(CELL)
    values = bench_run.read_layer_metrics(cell, {**tiny_run, "config": TINY})
    # held experts are 256 in the cell's own file: the reader's scale
    assert values["experts_hit_share.closed"]["value"] == pytest.approx(
        100 * stats["moe_experts_hit"] / stats["moe_layer_steps"] / 256)
    assert values["engine_occupancy.closed"]["value"] > 0
    for name in ("linattn_share.closed", "linattn_hbm_share.closed",
                 "moe_hbm_share.closed", "decode_hbm_share.closed"):
        assert name not in values  # no trace was taken
    assert 0 < tiny_run["detail"]["experts_hit_per_layer"] <= 4


@pytest.mark.parametrize("control, limit", [
    ("none", None), ("renormalised_gates", "logprob_tol"),
    ("beta_one", "first_state_tol"), ("full_rotary", "logprob_tol"),
    ("bf16_state", "first_state_tol"), ("state_unchanged", "conv_tol")])
def test_the_check_passes_the_program_and_refuses_each_control(control,
                                                               limit):
    """The comparison that decides ``correct`` tells the program from its
    nearest wrong neighbours (``benchmark/controls_qwen3_next.py``, which
    the chip runs at the cell's size)."""
    from benchmark import controls_qwen3_next

    out = controls_qwen3_next.run_control({**TINY, "max_streams": 8},
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
                     if e["name"] == "Qwen3-Next-80B-A3B-Instruct")
    bench = bench_run.load_json(ROOT, "BENCHMARK.json")
    conf = next(c for c in bench["configs"]
                if c["name"] == "qwen3_next_80b_a3b_ep2")
    mine = bench_run.load_json(ROOT, conf["file"])
    assert conf["source"] == mine["source"] == entry["source_url"]
    assert conf["reduced"] == mine["reduced"] == [
        "num_hidden_layers", "num_experts", "max_position_embeddings"]
    for key, value in entry["config"].items():
        if key not in conf["reduced"]:
            assert mine[key] == value, key
    assert mine["num_hidden_layers"] == mine["full_attention_interval"] == 4
    assert mine["router_outputs"] == entry["config"]["num_experts"] == 512
    lo, hi = mine["experts_held"]
    assert hi - lo == mine["num_experts"] == 256
    assert mine["published"] == {
        "num_hidden_layers": 48, "num_experts": 512,
        "max_position_embeddings": 262144,
        "layer_types": mine["published"]["layer_types"]}


def test_configuration_builds_the_published_widths():
    from benchmark.drivers import lm_qwen3_next

    cfg = lm_qwen3_next.qwen3_next_config(bench_run.load_cell(CELL)["config"])
    assert (cfg.d_model, cfg.vocab, cfg.n_layers) == (2048, 151936, 4)
    assert cfg.layer_types == ("linear_attention",) * 3 + ("attention",)
    assert (cfg.la_key_heads, cfg.la_value_heads, cfg.la_key_dim,
            cfg.la_value_dim, cfg.la_conv, cfg.la_chunk) \
        == (16, 32, 128, 128, 4, 64)
    assert (cfg.la_key_width, cfg.la_value_width, cfg.la_conv_dim) \
        == (2048, 4096, 8192)
    assert (cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.rotary_dim,
            cfg.rope_theta, cfg.attention_scale) \
        == (16, 2, 256, 64, 1e7, 1 / 16)
    assert cfg.qk_norm and cfg.attn_gate and cfg.shared_gate \
        and not cfg.tie_embeddings
    assert (cfg.num_experts, cfg.experts_per_token, cfg.expert_width,
            cfg.shared_width, cfg.experts_held) == (512, 10, 512, 512,
                                                    (0, 256))
    assert (cfg.embedding_multiplier, cfg.residual_multiplier,
            cfg.logits_scaling, cfg.rms_eps) == (1.0, 1.0, 1.0, 1e-6)
    assert (cfg.la_layers, cfg.attn_layers, cfg.ssm_layers) == (3, 1, 0)
    state = cfg.family.lane_state(cfg)
    assert state["layers"] == 3 and state["ssm"][0] == (32, 128, 128)
    assert state["conv"][0] == (3, 8192)


def test_bytes_of_the_real_configuration_are_the_issues_arithmetic():
    import jax

    from benchmark.drivers import lm_qwen3_next

    cfg = lm_qwen3_next.qwen3_next_config(bench_run.load_cell(CELL)["config"])
    shapes = jax.eval_shape(lambda: cfg.family.init_params(cfg, 0))
    parts = work_qwen3_next.param_bytes(shapes)
    assert parts["one_expert"] == 2 * 3 * 2048 * 512
    assert parts["experts"] == 4 * 256 * parts["one_expert"]
    assert parts["head"] == parts["embed"] == 2 * 151936 * 2048
    # mixers: 3 x 33.7 M and 27.3 M parameters, bfloat16 but for the
    # float32 convolution, per-head vectors and norm scales
    la = 2048 * 12288 + 2048 * 64 + 4096 * 2048
    assert parts["linattn"] == 3 * (2 * la + 4 * (4 * 8192 + 32 + 32 + 128))
    assert parts["attn"] == 2 * (2 * 2048 * 4096 + 2 * 2048 * 512
                                 + 4096 * 2048) + 4 * 2 * 256
    assert parts["moe_fixed"] == 4 * 2 * (2048 * 512 + 3 * 2048 * 512 + 2048)
    weights = sum(parts[k] for k in ("linattn", "attn", "moe_fixed",
                                     "experts", "head", "embed", "norms"))
    assert weights == pytest.approx(7.98e9, rel=0.005)
    lane = work_qwen3_next.state_bytes_per_lane(cfg)
    assert lane == 3 * (32 * 128 * 128 * 4 + 3 * 8192 * 2)
    assert 128 * lane == pytest.approx(0.81e9 + 0.02e9, rel=0.01)
    work = work_qwen3_next.decode_bytes_per_step(
        shapes, cfg, lanes_live=128, experts_hit_per_layer=233,
        live_tokens=128 * 500)
    assert work["moe_bytes_per_step"] == parts["moe_fixed"] \
        + 4 * 233 * parts["one_expert"]
    assert work["linattn_bytes_per_step"] == parts["linattn"] + 2 * 128 * lane
    by_part = work["decode_bytes_by_part"]
    assert by_part["kv"] == 2048 * 128 * 500
    assert work["decode_bytes_per_step"] == pytest.approx(
        sum(by_part.values())) == pytest.approx(8.6e9, rel=0.02)
    # an expert nobody chose is not counted, in either share
    none = work_qwen3_next.decode_bytes_per_step(
        shapes, cfg, lanes_live=0, experts_hit_per_layer=0, live_tokens=0)
    assert none["moe_bytes_per_step"] == parts["moe_fixed"]
    assert none["decode_bytes_per_step"] == sum(
        parts[k] for k in ("linattn", "attn", "moe_fixed", "head", "norms"))


def test_the_cell_has_fifteen_per_layer_metrics_and_the_others_keep_theirs():
    cell = bench_run.load_cell(CELL)
    names = [m["name"] for m in cell["per_layer"]]
    assert len(names) == 15 and names[-4:] == [
        "linattn_share.closed", "linattn_unscoped_share.closed",
        "linattn_hbm_share.closed", "experts_hit_share.closed"]
    assert {m["name"] for m in cell["end_to_end"]} == {"lm_tokens_per_s",
                                                       "setup_s"}
    mine = {m["name"]: m for m in cell["per_layer"]}
    new = [mine[n] for n in names[-4:-1]]
    assert {tuple(m["args"]["leaves"]) for m in new} == {LEAVES}
    # with moe_share, attn_share, logits and sample: every leaf and what
    # lies under none, once
    shares = sorted(s for n in ("moe_share.closed", "attn_share.closed",
                                "linattn_share.closed",
                                "linattn_unscoped_share.closed")
                    for s in mine[n]["args"]["count"])
    assert shares == sorted(set(LEAVES) - {"logits", "sample"}
                            | {"unscoped"})
    assert mine["linattn_hbm_share.closed"]["args"]["work"] \
        == "linattn_bytes_per_step"
    assert mine["experts_hit_share.closed"]["args"]["scale"] == 100 / 256
    assert cell["entry"]["chips"] == 1
    assert cell["workload"]["clients"] == cell["config"]["max_streams"] == 128
    assert cell["workload"]["requests"] == 256
    assert len(bench_run.load_cell("granite_h_chat_closed")["per_layer"]) == 15
    assert len(bench_run.load_cell("pythia_chat_closed")["per_layer"]) == 10
