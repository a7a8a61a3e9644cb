"""CPU-only tests of what the DeepSeek-V2 cell adds to the benchmark: its
driver and its controls on a tiny dict, its configuration file against the
public catalog entry, its byte counts, and the data of its metrics.

    python -m pytest benchmark/tests -q
"""

import json
import os
import time

import pytest

from benchmark import run as bench_run
from benchmark import work_deepseek_v2

ROOT = bench_run.ROOT
CELL = "dsv2lite_longctx_closed"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
TINY = dict(
    name="tiny", vocab_size=211, hidden_size=64, num_hidden_layers=3,
    num_attention_heads=4, num_key_value_heads=4, q_lora_rank=None,
    kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    attention_bias=False, hidden_act="silu", rope_theta=10000,
    rope_scaling=dict(type="yarn", factor=4, beta_fast=32, beta_slow=1,
                      mscale=0.707, mscale_all_dim=0.707,
                      original_max_position_embeddings=32),
    first_k_dense_replace=1, intermediate_size=96, moe_layer_freq=1,
    moe_intermediate_size=32, n_shared_experts=2, router_outputs=8,
    num_experts_per_tok=3, norm_topk_prob=False, routed_scaling_factor=1,
    scoring_func="softmax", topk_method="greedy", n_group=1, topk_group=1,
    experts_held=[0, 4], tie_word_embeddings=False, rms_norm_eps=1e-6,
    max_position_embeddings=256, dtype="float32", param_dtype="float32",
    max_streams=4, block_tokens=16, steps_per_dispatch=8, temperature=0.0,
    attention="auto", prefix_cache=0, min_bucket=16)
TINY_TRAFFIC = dict(
    loop="closed", clients=4, requests=64, warm_requests=4,
    prompt_tokens=dict(distribution="log_uniform", min=8, max=64),
    output_tokens=dict(distribution="log_uniform", min=8, max=32),
    check_prompt_tokens=[12, 40], check_new_tokens=16, check_pad_to=128,
    logprob_tol=1e-4, logprob_max_tol=1e-4, argmax_tol=1e-4, rows_tol=1e-4,
    first_rows_tol=1e-4, request_timeout_s=60, trace_seconds=0.3)
LEAVES = ("mla_q", "mla_kv", "kv_write", "kv_gather", "attend", "mla_out",
          "dense_ffn", "router", "experts", "shared_ffn", "logits", "sample")


@pytest.fixture(scope="module")
def tiny_run(tmp_path_factory):
    from benchmark.drivers import lm_deepseek_v2

    return lm_deepseek_v2.run_cell(
        TINY, TINY_TRAFFIC, 2147483659, 1.5, False, t0=time.monotonic(),
        workdir=str(tmp_path_factory.mktemp("work")))


def test_driver_runs_a_tiny_dict(tiny_run):
    out = tiny_run
    assert out["correct"] and out["failed"] == 0 and out["attempted"] > 0
    assert out["end_to_end"]["lm_tokens_per_s"] > 0
    check = out["detail"]["check"]
    assert check["ok"] and check["tokens_each"] == 16
    # two check prompts beside busy lanes, the filler beside each, and the
    # two once more alone, with their rows
    assert check["requests"] == 6 and check["rows_read"] == 2
    assert [r["alone"] for r in check["by_request"]] == [False] * 4 + [True] * 2
    assert check["mean_logprob_diff"] <= check["max_logprob_diff"] < 1e-4
    assert 0 < check["max_first_rows_diff"] < 1e-4
    assert 0 < check["max_rows_diff"] < 1e-4
    assert check["reference_s"] > 0  # after the window, outside setup_s
    pool = out["detail"]["pool"]
    assert pool["state_slots"] == 0
    # 40 columns held at 128, float32, three layers
    assert out["detail"]["kv_bytes_per_token_held"] == 3 * 128 * 4
    assert out["detail"]["engine_form"]["decode_attention"] == "gather"
    assert json.dumps(out["detail"])  # the detail line is plain data


def test_driver_carries_the_counters_its_metrics_read(tiny_run):
    stats = tiny_run["engine_stats"]
    steps = stats["dispatches"] * TINY["steps_per_dispatch"]
    assert stats["moe_layer_steps"] == steps * 2  # the first layer is dense
    assert 0 < stats["moe_experts_hit"] <= 4 * stats["moe_layer_steps"]
    assert tiny_run["detail"]["blocks_read_per_step"] == pytest.approx(
        stats["kv_blocks_live"] / steps)
    cell = bench_run.load_cell(CELL)
    values = bench_run.read_layer_metrics(cell, {**tiny_run, "config": TINY})
    assert values["engine_occupancy.closed"]["value"] > 0
    for name in ("mla_share.closed", "mla_hbm_share.closed",
                 "mla_kernel_hbm_share.closed", "moe_hbm_share.closed",
                 "decode_hbm_share.closed"):
        assert name not in values  # no trace was taken


@pytest.mark.parametrize("control, limit", [
    ("none", None), ("renormalised_gates", "logprob_tol"),
    ("no_mscale", "logprob_tol"), ("plain_rotary", "first_rows_tol"),
    ("no_kv_norm", "first_rows_tol"), ("no_shared", "logprob_tol"),
    ("int8_rows", "first_rows_tol")])
def test_the_check_passes_the_program_and_refuses_each_control(control,
                                                               limit):
    """The comparison that decides ``correct`` tells the program from its
    nearest wrong neighbours (``benchmark/controls_deepseek_v2.py``, which
    the chip runs at the cell's size)."""
    from benchmark import controls_deepseek_v2

    out = controls_deepseek_v2.run_control({**TINY, "max_streams": 8},
                                           TINY_TRAFFIC, 5, control)
    assert out["refused"] == (control != "none")
    assert out["requests"] == 6 and out["rows_read"] == 2
    if limit is None:
        assert out["max_logprob_diff"] < 1e-4 and not out["bad"]
    else:
        assert limit in {b["limit"] for b in out["bad"]}


def test_config_file_holds_the_catalog_entrys_numbers():
    if not os.path.exists(CATALOG):
        pytest.skip("the public catalog is not on this machine")
    with open(CATALOG, encoding="utf-8") as f:
        entry = next(e for e in map(json.loads, f)
                     if e["name"] == "DeepSeek-V2-Lite")
    bench = bench_run.load_json(ROOT, "BENCHMARK.json")
    conf = next(c for c in bench["configs"]
                if c["name"] == "deepseek_v2_lite_ep2")
    mine = bench_run.load_json(ROOT, conf["file"])
    assert conf["source"] == mine["source"] == entry["source_url"]
    assert conf["reduced"] == mine["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "max_position_embeddings"]
    for key, value in entry["config"].items():
        if key not in conf["reduced"]:
            assert mine[key] == value, key
    assert mine["num_hidden_layers"] == 14 and mine["first_k_dense_replace"] == 1
    assert mine["router_outputs"] == entry["config"]["n_routed_experts"] == 64
    lo, hi = mine["experts_held"]
    assert hi - lo == mine["n_routed_experts"] == 32
    assert mine["published"] == {"num_hidden_layers": 27,
                                 "n_routed_experts": 64,
                                 "max_position_embeddings": 163840}
    assert "2 pipeline stages" in mine["deployment"]
    for key in ("latent_row", "rotary_layout", "router", "weights"):
        assert key in mine["assumed"]


def test_configuration_builds_the_published_widths():
    from benchmark.drivers import lm_deepseek_v2
    from nnstreamer_tpu.models import mla

    cfg = lm_deepseek_v2.deepseek_v2_config(bench_run.load_cell(CELL)["config"])
    assert (cfg.d_model, cfg.vocab, cfg.n_layers, cfg.n_heads) \
        == (2048, 102400, 14, 16)
    assert (cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim,
            cfg.kv_lora_rank, cfg.row_width, cfg.row_store) \
        == (128, 64, 128, 512, 576, 640)
    assert (cfg.rope_theta, cfg.rope_factor, cfg.rope_original_max,
            cfg.rope_beta_fast, cfg.rope_beta_slow, cfg.rope_mscale,
            cfg.rope_mscale_all_dim) == (1e4, 40, 4096, 32, 1, 0.707, 0.707)
    assert abs(mla.softmax_scale(cfg) - 0.114721) < 1e-6
    assert (cfg.first_dense_layers, cfg.dense_width) == (1, 10944)
    assert (cfg.num_experts, cfg.experts_per_token, cfg.expert_width,
            cfg.shared_width, cfg.experts_held, cfg.norm_topk_prob,
            cfg.routed_scaling_factor) == (64, 6, 1408, 2816, (0, 32),
                                           False, 1.0)
    assert (cfg.max_seq, cfg.rms_eps) == (4096, 1e-6)
    assert cfg.family.lane_state(cfg) is None
    assert cfg.family.kv_entry(cfg) == (14, 1, (640,))


def test_bytes_of_the_real_configuration_are_the_issues_arithmetic():
    import jax

    from benchmark.drivers import lm_deepseek_v2

    cfg = lm_deepseek_v2.deepseek_v2_config(bench_run.load_cell(CELL)["config"])
    shapes = jax.eval_shape(lambda: cfg.family.init_params(cfg, 0))
    parts = work_deepseek_v2.param_bytes(shapes)
    assert parts["one_expert"] == 2 * 3 * 2048 * 1408          # 8.65 M
    assert parts["expert_layers"] == 13
    assert parts["experts"] == 13 * 32 * parts["one_expert"]
    assert parts["head"] == parts["embed"] == 2 * 102400 * 2048
    # attention a layer: wq 6.29 M + wkv_a 1.18 M + wkv_b 2.10 M + wo
    # 4.19 M = 13.76 M parameters, and the float32 norm over the latent
    attn = 2048 * 16 * 192 + 2048 * 576 + 512 * 16 * 256 + 16 * 128 * 2048
    assert parts["mla"] == 14 * (2 * attn + 4 * 512)
    assert parts["dense"] == 2 * 3 * 2048 * 10944
    assert parts["moe_fixed"] == 13 * 2 * (2048 * 64 + 3 * 2048 * 2816)
    weights = sum(parts[k] for k in ("mla", "dense", "moe_fixed", "experts",
                                     "head", "embed", "norms"))
    assert weights == pytest.approx(9.01e9, rel=0.002)
    assert work_deepseek_v2.row_bytes(cfg) == 16128
    # 64 lanes, every held expert hit, a mean of 2130 tokens a lane
    blocks = 64 * 2130 / 16
    work = work_deepseek_v2.decode_bytes_per_step(
        shapes, cfg, lanes_live=64, experts_hit_per_layer=32,
        blocks_read_per_step=blocks, block_tokens=16)
    assert work["latent_read_bytes_per_step"] == pytest.approx(2.2e9,
                                                               rel=0.01)
    assert work["mla_bytes_per_step"] == parts["mla"] \
        + work["latent_read_bytes_per_step"] + 64 * 16128
    assert work["moe_bytes_per_step"] == parts["moe_fixed"] \
        + 13 * 32 * parts["one_expert"]
    by_part = work["decode_bytes_by_part"]
    assert work["decode_bytes_per_step"] == pytest.approx(
        sum(by_part.values())) == pytest.approx(10.8e9, rel=0.02)
    # an expert nobody chose and a block nobody holds are not counted
    none = work_deepseek_v2.decode_bytes_per_step(
        shapes, cfg, lanes_live=0, experts_hit_per_layer=0,
        blocks_read_per_step=0, block_tokens=16)
    assert none["latent_read_bytes_per_step"] == 0
    assert none["moe_bytes_per_step"] == parts["moe_fixed"]
    assert none["decode_bytes_per_step"] == sum(
        parts[k] for k in ("mla", "dense", "moe_fixed", "head", "norms"))


def test_the_cell_has_fourteen_per_layer_metrics_and_the_others_keep_theirs():
    cell = bench_run.load_cell(CELL)
    names = [m["name"] for m in cell["per_layer"]]
    assert len(names) == 14 and names[-4:] == [
        "mla_share.closed", "mla_unscoped_share.closed",
        "mla_hbm_share.closed", "mla_kernel_hbm_share.closed"]
    assert {m["name"] for m in cell["end_to_end"]} == {"lm_tokens_per_s",
                                                       "setup_s"}
    mine = {m["name"]: m for m in cell["per_layer"]}
    assert {tuple(mine[n]["args"]["leaves"]) for n in names[-4:]} == {LEAVES}
    from benchmark.drivers import lm_deepseek_v2

    assert lm_deepseek_v2.LEAVES == LEAVES  # one reduction serves them all
    # with moe_share, dense_ffn, logits and sample: every leaf and what
    # lies under none, once
    shares = sorted(s for n in ("moe_share.closed", "mla_share.closed",
                                "mla_unscoped_share.closed")
                    for s in mine[n]["args"]["count"])
    assert shares == sorted(set(LEAVES) - {"dense_ffn", "logits", "sample"}
                            | {"unscoped"})
    # the expert layers' share is the hybrid cells' own file: none of its
    # leaves nests in one of this program's
    assert set(mine["moe_share.closed"]["args"]["count"]) <= set(LEAVES)
    assert mine["mla_hbm_share.closed"]["args"]["work"] \
        == "mla_bytes_per_step"
    assert mine["mla_kernel_hbm_share.closed"]["args"]["work"] \
        == "latent_read_bytes_per_step"
    assert mine["mla_kernel_hbm_share.closed"]["args"]["count"] == ["attend"]
    assert cell["entry"]["chips"] == 1
    assert cell["workload"]["clients"] == cell["config"]["max_streams"] == 64
    assert cell["workload"]["requests"] == 128
    spec = cell["workload"]
    window = cell["config"]["max_position_embeddings"]
    assert spec["prompt_tokens"]["max"] + spec["output_tokens"]["max"] \
        + cell["config"]["steps_per_dispatch"] <= window == 4096
    assert all(n % 16 and n + spec["check_new_tokens"]
               <= spec["check_pad_to"] for n in spec["check_prompt_tokens"])
    assert max(spec["check_prompt_tokens"]) > 2048
    assert spec["prompt_tokens"]["max"] + spec["check_new_tokens"] \
        <= spec["check_pad_to"]
    # the bucket ladder: the traffic runs two buckets, and the warm-up
    # (drivers/lm.py prefill_buckets, which knows the default ladder) warms
    # both through the requests it sends
    import types

    from benchmark.drivers.lm import prefill_buckets
    from nnstreamer_tpu.serving import ContinuousBatchingEngine

    eng = types.SimpleNamespace(min_bucket=cell["config"]["min_bucket"],
                                S=window)
    bucket = lambda n: ContinuousBatchingEngine._bucket(eng, n)
    lo, hi = spec["prompt_tokens"]["min"], spec["prompt_tokens"]["max"]
    assert {bucket(n) for n in range(lo, hi + 1)} == {1536, 3072}
    warmed = {bucket(min(b, window - 1 - 8))
              for b in prefill_buckets(lo, hi, window)}
    assert warmed >= {1536, 3072}
    assert sorted({bucket(n) for n in spec["check_prompt_tokens"]}) \
        == [1536, 3072]
    assert len(bench_run.load_cell("qwen3next_chat_closed")["per_layer"]) == 15
    assert len(bench_run.load_cell("granite_h_chat_closed")["per_layer"]) == 15
    assert len(bench_run.load_cell("pythia_chat_closed")["per_layer"]) == 10
    bench = bench_run.load_json(ROOT, "BENCHMARK.json")
    assert len(bench["workloads"]) == 4
    assert all(w["chips"] == 1 for w in bench["workloads"])
