"""CPU-only tests of what the AFMoE cell adds to the benchmark: its driver
and its controls on a tiny dict, its configuration file against the public
catalog entry, its byte counts, and the data of its metrics.

    python -m pytest benchmark/tests -q
"""

import json
import os
import time

import pytest

from benchmark import run as bench_run
from benchmark import work_afmoe

ROOT = bench_run.ROOT
CELL = "trinity_longctx_closed"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
TINY = dict(
    name="tiny", vocab_size=211, hidden_size=64, num_hidden_layers=4,
    layer_types=["sliding_attention", "sliding_attention", "full_attention",
                 "sliding_attention"],
    num_dense_layers=1, num_attention_heads=8, num_key_value_heads=2,
    head_dim=16, sliding_window=24, rope_theta=10000, rope_scaling=None,
    hidden_act="silu", intermediate_size=96, moe_intermediate_size=32,
    num_shared_experts=1, router_outputs=16, num_experts_per_tok=4,
    experts_held=[0, 8], score_func="sigmoid", route_norm=True,
    route_scale=2.448, n_group=1, topk_group=1, mup_enabled=True,
    tie_word_embeddings=False, rms_norm_eps=1e-5,
    max_position_embeddings=256, dtype="float32", param_dtype="float32",
    max_streams=4, block_tokens=8, steps_per_dispatch=8, temperature=0.0,
    attention="auto", prefix_cache=0, min_bucket=16)
TINY_TRAFFIC = dict(
    loop="closed", clients=4, requests=64, warm_requests=4,
    prompt_tokens=dict(distribution="log_uniform", min=16, max=96),
    output_tokens=dict(distribution="log_uniform", min=8, max=32),
    check_prompt_tokens=[30, 75], check_new_tokens=16, check_pad_to=128,
    logprob_tol=1e-4, logprob_max_tol=1e-4, argmax_tol=1e-4, rows_tol=1e-4,
    first_rows_tol=1e-4, request_timeout_s=60, trace_seconds=0.3)
LEAVES = ["qkv", "kv_write", "kv_gather", "attend", "attend_window",
          "attn_out", "dense_ffn", "router", "experts", "shared_ffn",
          "logits", "sample"]
NEW_METRICS = ("attnmix_share.closed", "winattn_share.closed",
               "attnmix_unscoped_share.closed", "attnmix_hbm_share.closed",
               "winattn_kernel_hbm_share.closed", "window_read_share.closed")


@pytest.fixture(scope="module")
def tiny_run(tmp_path_factory):
    from benchmark.drivers import lm_afmoe

    return lm_afmoe.run_cell(
        TINY, TINY_TRAFFIC, 2147483659, 1.5, False, t0=time.monotonic(),
        workdir=str(tmp_path_factory.mktemp("work")))


def test_driver_runs_a_tiny_dict(tiny_run):
    out = tiny_run
    assert out["correct"] and out["failed"] == 0 and out["attempted"] > 0
    assert out["end_to_end"]["lm_tokens_per_s"] > 0
    check = out["detail"]["check"]
    assert check["ok"] and check["tokens_each"] == 16
    # two check prompts beside busy lanes, the filler beside each, and the
    # two once more alone, with the rows of both arenas
    assert check["requests"] == check["compared"] == 6
    assert check["rows_read"] == 2
    assert [r["alone"] for r in check["by_request"]] == [False] * 4 + [True] * 2
    assert check["mean_logprob_diff"] <= check["max_logprob_diff"] < 1e-4
    assert 0 < check["max_first_rows_diff"] < 1e-4
    assert 0 < check["max_rows_diff"] < 1e-4
    assert check["reference_s"] > 0  # after the window, outside setup_s
    pool = out["detail"]["pool"]
    # 4 lanes x (ceil((24 + 8) / 8) + 1) window blocks
    assert pool["window_blocks"] == 4 * 5 and pool["state_slots"] == 0
    # 2 parts x 2 heads x 16 x float32 a layer: one full layer, three window
    assert out["detail"]["kv_bytes_per_token_held"] == 256
    assert out["detail"]["kv_window_bytes_per_token_held"] == 3 * 256
    assert out["detail"]["engine_form"]["decode_attention"] == "gather"
    assert json.dumps(out["detail"])  # the detail line is plain data


def test_driver_carries_the_counters_its_metrics_read(tiny_run):
    stats = tiny_run["engine_stats"]
    steps = stats["dispatches"] * TINY["steps_per_dispatch"]
    assert stats["moe_layer_steps"] == steps * 3  # the first layer is dense
    assert 0 < stats["kv_window_blocks_live"] < stats["kv_blocks_live"]
    assert stats["kv_window_blocks_released"] > 0
    detail = tiny_run["detail"]
    assert detail["blocks_read_per_step"] == pytest.approx(
        stats["kv_blocks_live"] / steps)
    assert detail["window_blocks_read_per_step"] == pytest.approx(
        stats["kv_window_blocks_live"] / steps)
    cell = bench_run.load_cell(CELL)
    values = bench_run.read_layer_metrics(cell, {**tiny_run, "config": TINY})
    assert values["engine_occupancy.closed"]["value"] > 0
    assert values["window_read_share.closed"]["value"] == pytest.approx(
        100 * stats["kv_window_blocks_live"] / stats["kv_blocks_live"])
    for name in NEW_METRICS[:5] + ("moe_hbm_share.closed",
                                   "decode_hbm_share.closed"):
        assert name not in values  # no trace was taken


@pytest.mark.parametrize("control, limit", [
    ("none", None), ("no_window", "logprob_max_tol"),
    ("half_window", "logprob_max_tol"),
    ("window_off_by_one", "logprob_max_tol"),
    ("rotary_on_full", "rows_tol"), ("no_rotary_on_window", "first_rows_tol"),
    ("no_gate", "logprob_max_tol"), ("softmax_scores", "logprob_max_tol"),
    ("no_route_norm", "logprob_max_tol"),
    ("no_route_scale", "logprob_max_tol"),
    ("no_post_norms", "logprob_max_tol"),
    ("no_embed_scale", "first_rows_tol"),
    ("renormalise_held", "logprob_max_tol"), ("no_bias", "logprob_max_tol"),
    ("stale_window_rows", "first_rows_tol"), ("int8_rows", "first_rows_tol")])
def test_the_check_passes_the_program_and_refuses_each_control(control,
                                                               limit):
    """The comparison that decides ``correct`` tells the program from its
    nearest wrong neighbours (``benchmark/controls_afmoe.py``, which the
    chip runs at the cell's size): at this size, float32 and window 24, also
    the two the chip cannot tell at window 4096."""
    from benchmark import controls_afmoe

    out = controls_afmoe.run_control({**TINY, "max_streams": 8},
                                     TINY_TRAFFIC, 5, control)
    assert out["refused"] == (control != "none")
    assert out["requests"] == 6
    if limit is None:
        assert out["compared"] == 6 and out["rows_read"] == 2
        assert out["max_logprob_diff"] < 1e-4 and not out["bad"]
    else:
        assert out["compared"] >= 1  # stops at the first request over
        assert limit in {b["limit"] for b in out["bad"]}


def test_config_file_holds_the_catalog_entrys_numbers():
    if not os.path.exists(CATALOG):
        pytest.skip("the public catalog is not on this machine")
    with open(CATALOG, encoding="utf-8") as f:
        entry = next(e for e in map(json.loads, f)
                     if e["name"] == "Trinity-Large-Preview")
    bench = bench_run.load_json(ROOT, "BENCHMARK.json")
    conf = next(c for c in bench["configs"]
                if c["name"] == "trinity_large_ep8")
    mine = bench_run.load_json(ROOT, conf["file"])
    assert conf["source"] == mine["source"] == entry["source_url"]
    assert conf["reduced"] == mine["reduced"] == [
        "num_hidden_layers", "num_dense_layers", "layer_types",
        "num_experts", "vocab_size", "max_position_embeddings"]
    for key, value in entry["config"].items():
        if key not in conf["reduced"]:
            assert mine[key] == value, key
    assert mine["published"] == {k: entry["config"][k]
                                 for k in conf["reduced"]}
    assert mine["layer_types"] == ["sliding_attention"] * 4 \
        + ["full_attention"]
    assert (mine["num_hidden_layers"], mine["num_dense_layers"]) == (5, 1)
    assert mine["router_outputs"] == entry["config"]["num_experts"] == 256
    lo, hi = mine["experts_held"]
    assert hi - lo == mine["num_experts"] == 32
    assert mine["vocab_size"] * 8 == entry["config"]["vocab_size"]
    assert "12 pipeline stages" in mine["deployment"]
    for key in ("embedding_scale", "sandwich_norms", "attention_gate",
                "qk_norm", "positions", "routing", "expert_bias", "engine"):
        assert key in mine["assumed"]


def test_configuration_builds_the_published_widths():
    from benchmark.drivers import lm_afmoe

    cfg = lm_afmoe.afmoe_config(bench_run.load_cell(CELL)["config"])
    assert (cfg.d_model, cfg.vocab, cfg.n_layers, cfg.n_heads,
            cfg.n_kv_heads, cfg.head_dim) == (3072, 25024, 5, 48, 8, 128)
    assert (cfg.window, cfg.window_layers, cfg.full_layers) == (4096, 4, 1)
    assert (cfg.num_experts, cfg.experts_per_token, cfg.n_held,
            cfg.expert_width, cfg.shared_width, cfg.dense_width) \
        == (256, 4, 32, 3072, 3072, 12288)
    assert cfg.routed_scaling_factor == 2.448 and cfg.norm_topk_prob
    assert cfg.embedding_multiplier == pytest.approx(3072 ** 0.5)
    assert cfg.max_seq == 14336 and cfg.num_dense_layers == 1
    family = cfg.family
    assert family.kv_entry(cfg) == (1, 2, (8, 128))
    assert family.kv_window(cfg) == (4, 4096)
    import jax

    shapes = jax.eval_shape(lambda: family.init_params(cfg, 0))
    n = sum(int(a.size) for a in jax.tree_util.tree_leaves(shapes)
            if a.dtype.itemsize == 2)
    assert n == 4_321_837_056  # the issue's arithmetic, 2 B a parameter


def test_traffic_file_is_the_issues_letter_for_letter():
    w = bench_run.load_cell(CELL)["workload"]
    assert (w["loop"], w["clients"], w["requests"], w["warm_requests"]) \
        == ("closed", 32, 32, 32)
    assert w["prompt_tokens"] == dict(distribution="log_uniform", min=4096,
                                      max=12288)
    assert w["output_tokens"] == dict(distribution="log_uniform", min=512,
                                      max=2040)
    assert w["check_prompt_tokens"] == [4200, 5500, 8000, 11000]
    # three end inside a block, 8000 on a block's boundary: both hand-overs
    assert [n % 16 == 0 for n in w["check_prompt_tokens"]] \
        == [False, False, True, False]
    assert w["check_new_tokens"] == 64 and not w["shared_prefix"]
    assert w["request_timeout_s"] == 180 and w["trace_seconds"] == 3.0
    assert w["check_pad_to"] >= 12288 + 64
    config = bench_run.load_cell(CELL)["config"]
    assert w["clients"] == config["max_streams"]
    assert w["prompt_tokens"]["max"] + w["output_tokens"]["max"] \
        + config["steps_per_dispatch"] <= config["max_position_embeddings"]


def test_bytes_of_a_step_by_mechanism():
    import jax

    from benchmark.drivers import lm_afmoe

    cfg = lm_afmoe.afmoe_config(bench_run.load_cell(CELL)["config"])
    params = jax.eval_shape(lambda: cfg.family.init_params(cfg, 0))
    parts = work_afmoe.param_bytes(params)
    assert parts["one_expert"] == 3 * 3072 * 3072 * 2
    assert parts["expert_layers"] == 4
    assert parts["experts"] == 4 * 32 * parts["one_expert"]
    assert parts["dense"] == 3 * 3072 * 12288 * 2
    assert parts["head"] == parts["embed"] == 25024 * 3072 * 2
    assert work_afmoe.token_bytes(cfg) == 4096
    work = work_afmoe.decode_bytes_per_step(
        params, cfg, lanes_live=32, experts_hit_per_layer=12.7,
        blocks_read_per_step=32 * 507, window_blocks_read_per_step=32 * 257,
        block_tokens=16)
    by = work.pop("decode_bytes_by_part")
    assert work["window_read_bytes_per_step"] == by["window_read"] \
        == 4096 * 4 * 32 * 257 * 16
    assert work["full_read_bytes_per_step"] == 4096 * 1 * 32 * 507 * 16
    assert work["attnmix_bytes_per_step"] == pytest.approx(
        parts["mixers"] + by["window_read"] + by["full_read"]
        + 4096 * 5 * 32)
    assert work["moe_bytes_per_step"] == pytest.approx(
        parts["moe_fixed"] + 12.7 * 4 * parts["one_expert"])
    assert work["decode_bytes_per_step"] == pytest.approx(sum(by.values()))
    # the issue's reckoning: about 7.3 GB a step
    assert 6.5e9 < work["decode_bytes_per_step"] < 8e9
    # the band: every (query, key) pair inside it, and no more
    assert work_afmoe.band_attention_flops(cfg, 4096) \
        == 4.0 * 128 * 48 * (4096 * 4097 // 2)
    assert work_afmoe.band_attention_flops(cfg, 12288) \
        == 4.0 * 128 * 48 * (4096 * 4097 // 2 + 8192 * 4096)


def test_cells_metrics_are_data_and_name_the_programs_scopes():
    bench = bench_run.load_json(ROOT, "BENCHMARK.json")
    cell = bench_run.load_cell(CELL)
    names = [m["name"] for m in cell["per_layer"]]
    # the count is BENCHMARK.json's, not this test's
    assert len(names) == sum(
        1 for m in bench["per_layer"]
        if "workloads" not in m or CELL in m["workloads"])
    assert set(NEW_METRICS) <= set(names)
    assert "attn_share.closed" not in names
    assert [m["name"] for m in cell["end_to_end"]] \
        == ["lm_tokens_per_s", "setup_s"]
    for m in cell["per_layer"]:
        if m["name"] in NEW_METRICS:
            assert m["workloads"] == [CELL] and m["moves"] == "lm_tokens_per_s"
            if m["reader"] != "engine_stat_mean":
                assert m["args"]["leaves"] == LEAVES
                assert set(m["args"]["count"]) <= set(LEAVES) | {"unscoped"}
    from benchmark.drivers import lm_afmoe

    assert list(lm_afmoe.LEAVES) == LEAVES


def test_band_kernel_is_read_off_a_trace_by_its_name(monkeypatch):
    from benchmark import trace_reduce
    from benchmark.drivers import lm_afmoe

    cfg = lm_afmoe.afmoe_config(bench_run.load_cell(CELL)["config"])
    ops = [("/device:TPU:0", "%nns_band_flash_prefill.3 = bf16[1,48,6144,128]"
            "{3,2,1,0} custom-call(...)", 0.0, 2e6),
           ("/device:TPU:0", "%nns_band_flash_prefill.4 = bf16[1,48,12288,128]"
            "{3,2,1,0} custom-call(...)", 3e6, 5e6),
           ("/device:TPU:0", "%fusion.7 = bf16[1,6144,3072]{2,1,0} fusion(...)",
            9e6, 1e6)]
    monkeypatch.setattr(trace_reduce, "read_events",
                        lambda path: (ops, [], {}))
    got = lm_afmoe.band_prefill("x", cfg, 197e12)
    flops = work_afmoe.band_attention_flops(cfg, 6144) \
        + work_afmoe.band_attention_flops(cfg, 12288)
    assert got["calls"] == 2 and got["seconds"] == pytest.approx(7e-3)
    assert got["flops"] == flops
    assert got["compute_share_pct"] == pytest.approx(
        100 * flops / 7e-3 / 197e12)
    monkeypatch.setattr(trace_reduce, "read_events",
                        lambda path: (ops[2:], [], {}))
    assert lm_afmoe.band_prefill("x", cfg, 197e12) is None
