"""CPU-only tests of what the SambaY cell adds to the benchmark: its driver
and its controls on a tiny dict, its configuration file against the public
catalog entry, its byte counts, and the data of its metrics.

    python -m pytest benchmark/tests -q
"""

import json
import os
import time

import pytest

from benchmark import run as bench_run
from benchmark import work_sambay

ROOT = bench_run.ROOT
CELL = "phi4flash_reason_closed"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
TINY = dict(
    name="tiny", model_type="phi4flash", mb_per_layer=2, hidden_act="silu",
    tie_word_embeddings=True, mlp_bias=False, lm_head_bias=False,
    vocab_size=211, hidden_size=64, num_hidden_layers=8,
    num_attention_heads=16, num_key_value_heads=8, sliding_window=24,
    intermediate_size=96, layer_norm_eps=1e-5, mamba_expand=2,
    mamba_d_state=16, mamba_d_conv=4, mamba_dt_rank=4, ssm_chunk=8,
    ssm_state_dtype="float32", max_position_embeddings=256,
    dtype="float32", param_dtype="float32", max_streams=4, block_tokens=8,
    steps_per_dispatch=8, temperature=0.0, attention="auto", prefix_cache=0,
    min_bucket=16)
TINY_TRAFFIC = dict(
    loop="closed", clients=4, requests=64, warm_requests=4,
    prompt_tokens=dict(distribution="log_uniform", min=16, max=96),
    output_tokens=dict(distribution="log_uniform", min=8, max=32),
    check_prompt_tokens=[30, 72], check_new_tokens=16, check_pad_to=128,
    logprob_tol=1e-4, logprob_max_tol=1e-4, argmax_tol=1e-4, rows_tol=1e-4,
    first_rows_tol=1e-4, state_tol=1e-4, first_state_tol=1e-4,
    conv_tol=1e-4, request_timeout_s=60, trace_seconds=0.3)
LEAVES = ["ssm_in", "ssm_conv", "ssm_dt", "ssm_update", "ssm_out", "qkv",
          "kv_write", "kv_gather", "attend_window", "attend", "attend_cross",
          "diff_out", "attn_out", "gmu", "dense_ffn", "logits", "sample"]
NEW_METRICS = ("sambay_ssm_share.closed", "sambay_attn_share.closed",
               "crossattn_share.closed", "gmu_share.closed",
               "sambay_unscoped_share.closed", "sambay_ssm_hbm_share.closed",
               "ssm1_kernel_hbm_share.closed",
               "crossattn_kernel_hbm_share.closed",
               "prefill_cross_share.closed")


@pytest.fixture(scope="module")
def tiny_run(tmp_path_factory):
    from benchmark.drivers import lm_sambay

    return lm_sambay.run_cell(
        TINY, TINY_TRAFFIC, 2147483659, 1.5, False, t0=time.monotonic(),
        workdir=str(tmp_path_factory.mktemp("work")))


def test_driver_runs_a_tiny_dict(tiny_run):
    out = tiny_run
    assert out["correct"] and out["failed"] == 0 and out["attempted"] > 0
    assert out["end_to_end"]["lm_tokens_per_s"] > 0
    check = out["detail"]["check"]
    assert check["ok"] and check["tokens_each"] == 16
    # two check prompts beside busy lanes (their lanes' states read), the
    # filler beside each, and the two once more alone, with the rows of
    # both arenas and the states
    assert check["requests"] == check["compared"] == 6
    assert check["rows_read"] == 2 and check["states_read"] == 4
    assert [r["alone"] for r in check["by_request"]] == [False] * 4 + [True] * 2
    assert check["mean_logprob_diff"] <= check["max_logprob_diff"] < 1e-4
    for name in ("rows", "first_rows", "state", "first_state", "conv"):
        assert 0 < check[f"max_{name}_diff"] < 1e-4
    assert check["reference_s"] > 0  # after the window, outside setup_s
    pool = out["detail"]["pool"]
    # 4 lanes x (ceil((24 + 8) / 8) + 1) window blocks, a slot a lane
    assert pool["window_blocks"] == 4 * 5 and pool["state_slots"] == 4
    # 2 parts x 4 pairs x 8 x float32 a layer: ONE full layer, two window
    assert out["detail"]["kv_bytes_per_token_held"] == 256
    assert out["detail"]["kv_window_bytes_per_token_held"] == 2 * 256
    form = out["detail"]["engine_form"]
    assert (form["decode_attention"], form["state_update"]) \
        == ("gather", "reference")
    assert json.dumps(out["detail"])  # the detail line is plain data


def test_driver_carries_the_counters_its_metrics_read(tiny_run):
    stats = tiny_run["engine_stats"]
    steps = stats["dispatches"] * TINY["steps_per_dispatch"]
    assert 0 < stats["kv_window_blocks_live"] < stats["kv_blocks_live"]
    assert stats["kv_window_blocks_released"] > 0
    # one cross layer at this depth: its reads are the full layer's again
    # (the host counts a dispatch's blocks as it launches it, the program's
    # own count arrives with its tokens: a window's edges cut one apart)
    assert stats["kv_shared_reads"] == pytest.approx(
        stats["kv_blocks_live"], rel=0.05)
    assert stats["prefill_rows_cross"] == stats["prefills"] > 0
    assert stats["prefill_rows_self"] == stats["prefill_bucket_tokens"]
    detail = tiny_run["detail"]
    assert detail["shared_reads_per_step"] == pytest.approx(
        stats["kv_shared_reads"] / steps)
    cell = bench_run.load_cell(CELL)
    values = bench_run.read_layer_metrics(cell, {**tiny_run, "config": TINY})
    assert values["engine_occupancy.closed"]["value"] > 0
    assert values["window_read_share.closed"]["value"] == pytest.approx(
        100 * stats["kv_window_blocks_live"] / stats["kv_blocks_live"])
    assert values["prefill_cross_share.closed"]["value"] == pytest.approx(
        100 * stats["prefill_rows_cross"] / stats["prefill_rows_self"])
    assert values["prefill_cross_share.closed"]["value"] < 100 / 16
    for name in NEW_METRICS[:8] + ("decode_hbm_share.closed",):
        assert name not in values  # no trace was taken


@pytest.mark.parametrize("control", [
    "none", "no_diff", "no_pair_norm", "no_lambda_scale",
    "wrong_lambda_layer", "no_window", "half_window", "window_off_by_one",
    "memory_after_gate", "stale_memory", "no_gmu_silu",
    "cross_reads_window", "no_D", "no_dt_bias", "state_unchanged",
    "bf16_state", "int8_rows"])
def test_the_check_passes_the_program_and_refuses_each_control(control,
                                                               tiny_controls):
    """The comparison that decides ``correct`` tells the program from its
    nearest wrong neighbours (``benchmark/controls_sambay.py``, which the
    chip runs at the cell's size): at this size, float32 and window 24, also
    those the chip cannot tell at window 512."""
    out = tiny_controls[control]
    assert out["refused"] == (control != "none")
    assert out["requests"] == 6
    if control == "none":
        assert out["compared"] == 6 and out["rows_read"] == 2
        assert out["max_logprob_diff"] < 1e-4 and not out["bad"]
    else:
        assert out["compared"] >= 1  # stops at the first request over
        assert out["bad"]


@pytest.fixture(scope="module")
def tiny_controls():
    """Every control in ONE call, as the chip runs them: served once for
    the wrong references, an engine of its own for each wrong program."""
    from benchmark import controls_sambay

    # weights large enough for attention to be sharp: at normal x 0.02 the
    # pair norm hides what the two softmaxes differ by (tests/
    # test_sambay_lm.py _params)
    return {v["control"]: v for v in controls_sambay.run_controls(
        {**TINY, "max_streams": 8}, TINY_TRAFFIC, 5, ["all"],
        params_of=_sharp, stop_at_bad=True)}


def _sharp(params):
    for lp in params["layers"]:
        for name, by in (("ssm_in", 3), ("x_proj", 3), ("dt_proj", 3),
                         ("gmu_in", 5), ("wqkv", 10),
                         ("wq", 10), ("wo", 2), ("lam_q1", 3), ("lam_k1", 3),
                         ("lam_q2", 3), ("lam_k2", 3)):
            if name in lp:
                lp[name] = lp[name] * by
    params["embed"] = params["embed"] * 8
    return params


def test_config_file_holds_the_catalog_entrys_numbers():
    if not os.path.exists(CATALOG):
        pytest.skip("the public catalog is not on this machine")
    with open(CATALOG, encoding="utf-8") as f:
        entry = next(e for e in map(json.loads, f)
                     if e["name"] == "Phi-4-mini-flash-reasoning")
    bench = bench_run.load_json(ROOT, "BENCHMARK.json")
    conf = next(c for c in bench["configs"]
                if c["name"] == "phi4_mini_flash_reasoning")
    mine = bench_run.load_json(ROOT, conf["file"])
    assert conf["source"] == mine["source"] == entry["source_url"]
    assert conf["reduced"] == mine["reduced"] == ["max_position_embeddings"]
    for key, value in entry["config"].items():
        if key not in conf["reduced"]:
            assert mine[key] == value, key
    assert mine["published"] == {"max_position_embeddings": 262144}
    assert mine["max_position_embeddings"] == 6400 == 2048 + 4096 + 256
    assert (mine["num_hidden_layers"], mine["vocab_size"],
            mine["sliding_window"]) == (32, 200064, 512)
    assert "the WHOLE model" in mine["deployment"]
    for key in ("provenance", "layers", "norms", "positions", "mlp", "mamba",
                "memory", "differential_attention", "window", "page_entry",
                "cache", "init", "engine", "max_position_embeddings"):
        assert key in mine["assumed"]


def test_configuration_builds_the_published_widths():
    import jax

    from benchmark.drivers import lm_sambay

    cfg = lm_sambay.sambay_config(bench_run.load_cell(CELL)["config"])
    assert (cfg.d_model, cfg.vocab, cfg.n_layers, cfg.n_heads,
            cfg.n_kv_heads, cfg.head_dim, cfg.d_ff) \
        == (2560, 200064, 32, 40, 20, 64, 10240)
    assert (cfg.ssm_inner, cfg.ssm_state, cfg.ssm_conv, cfg.dt_rank) \
        == (5120, 16, 4, 160)
    assert (cfg.window, cfg.window_layers, cfg.ssm_layers, cfg.cross_layers,
            cfg.memory_layer) == (512, 8, 9, 7, 16)
    assert cfg.max_seq == 6400 and cfg.attention_scale == 0.125
    family = cfg.family
    assert family.kv_entry(cfg) == (1, 2, (10, 128))
    assert family.kv_window(cfg) == (8, 512)
    assert family.lane_state(cfg)["layers"] == 9
    shapes = jax.eval_shape(lambda: family.init_params(cfg, 0))
    by_layer = [sum(int(a.size) for a in jax.tree_util.tree_leaves(lp)
                    if a.dtype.itemsize == 2) for lp in shapes["layers"]]
    mlp = 3 * 2560 * 10240
    # the issue's arithmetic, matrices alone: Mamba 41.2 M, a layer that
    # owns a cache 19.66 M, a gated memory unit 26.2 M, a cross layer 13.1 M
    assert by_layer[0] - mlp == 2560 * 10240 + 5120 * 192 + 160 * 5120 \
        + 5120 * 2560
    assert by_layer[1] - mlp == by_layer[17] - mlp == 2560 * 5120 + 2560 ** 2
    assert by_layer[18] - mlp == 2 * 2560 * 5120
    assert by_layer[19] - mlp == 2 * 2560 ** 2
    n = sum(by_layer) + 200064 * 2560
    assert 3.84e9 < n < 3.86e9      # 3.8 B, as the model card says


def test_traffic_file_is_the_issues_letter_for_letter():
    w = bench_run.load_cell(CELL)["workload"]
    assert (w["loop"], w["clients"], w["requests"], w["warm_requests"]) \
        == ("closed", 64, 64, 8)
    assert w["prompt_tokens"] == dict(distribution="log_uniform", min=256,
                                      max=2048)
    assert w["output_tokens"] == dict(distribution="log_uniform", min=1024,
                                      max=4096)
    assert w["check_prompt_tokens"] == [300, 700, 1200, 1900]
    assert w["check_new_tokens"] == 64 and not w["shared_prefix"]
    assert w["request_timeout_s"] == 300 and w["trace_seconds"] == 3.0
    # a filler beside a check lane is compared too: the longest prompt
    assert w["check_pad_to"] >= w["prompt_tokens"]["max"] + 64
    config = bench_run.load_cell(CELL)["config"]
    assert w["clients"] == config["max_streams"]
    assert w["prompt_tokens"]["max"] + w["output_tokens"]["max"] \
        + config["steps_per_dispatch"] <= config["max_position_embeddings"]
    for name in ("logprob", "logprob_max", "argmax", "rows", "first_rows",
                 "state", "first_state", "conv"):
        assert w[name + "_tol"] > 0 and name + "_tol" in w["tolerances"]


def test_bytes_of_a_step_by_mechanism():
    import jax

    from benchmark.drivers import lm_sambay

    cfg = lm_sambay.sambay_config(bench_run.load_cell(CELL)["config"])
    params = jax.eval_shape(lambda: cfg.family.init_params(cfg, 0))
    parts = work_sambay.param_bytes(params)
    assert parts["head"] == 200064 * 2560 * 2
    assert parts["mlp"] == 32 * 3 * 2560 * 10240 * 2
    assert parts["gmu"] == 7 * 2 * 2560 * 5120 * 2
    assert work_sambay.token_bytes(cfg) == 5120
    lane = work_sambay.state_bytes_per_lane(cfg)
    assert lane == {"ssm": 9 * 16 * 5120 * 4, "conv": 9 * 3 * 5120 * 2}
    work = work_sambay.decode_bytes_per_step(
        params, cfg, lanes_live=64, blocks_read_per_step=64 * 150,
        shared_reads_per_step=7 * 64 * 150,
        window_blocks_read_per_step=64 * 33, block_tokens=16)
    by = work.pop("decode_bytes_by_part")
    assert work["state_rw_bytes_per_step"] == 2 * 64 * lane["ssm"]
    assert work["ssm_bytes_per_step"] == parts["ssm"] + 2 * 64 * (
        lane["ssm"] + lane["conv"])
    assert work["shared_kv_read_bytes_per_step"] == 8 * 64 * 150 * 16 * 5120
    assert work["window_read_bytes_per_step"] == 8 * 64 * 33 * 16 * 5120
    assert work["decode_bytes_per_step"] == pytest.approx(sum(by.values()))
    # the issue's reckoning at 2,400 tokens a lane: about 15.7 GB a step,
    # half of it what this architecture adds
    assert 15e9 < work["decode_bytes_per_step"] < 16.5e9
    added = by["shared_kv_read"] + by["window_read"] + by["state_rw"]
    assert 0.45 < added / work["decode_bytes_per_step"] < 0.55


def test_cells_metrics_are_data_and_name_the_programs_scopes():
    bench = bench_run.load_json(ROOT, "BENCHMARK.json")
    cell = bench_run.load_cell(CELL)
    names = [m["name"] for m in cell["per_layer"]]
    # the count is BENCHMARK.json's, not this test's
    assert len(names) == sum(
        1 for m in bench["per_layer"]
        if "workloads" not in m or CELL in m["workloads"])
    assert set(NEW_METRICS) | {"window_read_share.closed",
                               "decode_hbm_share.closed"} <= set(names)
    assert [m["name"] for m in cell["end_to_end"]] \
        == ["lm_tokens_per_s", "setup_s"]
    shares = {}
    for m in cell["per_layer"]:
        if m["name"] in NEW_METRICS:
            assert m["workloads"] == [CELL] and m["moves"] == "lm_tokens_per_s"
            if m["reader"] != "engine_stat_mean":
                assert m["args"]["leaves"] == LEAVES
                assert set(m["args"]["count"]) <= set(LEAVES) | {"unscoped"}
            if m["reader"] == "scope_share":
                shares[m["name"]] = set(m["args"]["count"])
    # the four shares and the three leaves tile the program: they sum to 100
    tiled = (shares["sambay_ssm_share.closed"]
             | shares["sambay_attn_share.closed"]
             | shares["gmu_share.closed"]
             | shares["sambay_unscoped_share.closed"]
             | {"dense_ffn", "logits", "sample"})
    assert tiled == set(LEAVES) | {"unscoped"}
    assert shares["crossattn_share.closed"] \
        < shares["sambay_attn_share.closed"]
    from benchmark.drivers import lm_sambay

    assert list(lm_sambay.LEAVES) == LEAVES


def test_the_decode_program_names_every_scope_once_a_layer_that_has_it():
    """The tiny engine's own decode program: each leaf of the cell's metrics
    is a scope of its text (``kv_gather`` stands for the kernels here: the
    CPU's form)."""
    import jax
    import jax.numpy as jnp

    from benchmark.drivers import lm_sambay

    cfg = lm_sambay.sambay_config(TINY)
    params = jax.eval_shape(lambda: cfg.family.init_params(cfg, 0))
    step = cfg.family.build_paged_decode_step(cfg, 8, 256)
    pool = jax.eval_shape(lambda: {
        "kv": jnp.zeros((1, 33, 2, 4, 8, 8)),
        "win": jnp.zeros((2, 21, 2, 4, 8, 8)),
        "state": {"ssm": jnp.zeros((3, 4, 1, 16, 128)),
                  "conv": jnp.zeros((3, 4, 3, 128))}})
    ints = jax.ShapeDtypeStruct((4,), jnp.int32)
    table = jax.ShapeDtypeStruct((4, 32), jnp.int32)
    text = jax.jit(step).lower(params, ints, pool,
                               {"kv": table, "win": table}, ints).as_text(
        debug_info=True)
    for leaf in set(LEAVES) - {"sample"}:
        assert f"/{leaf}/" in text or f"/{leaf}\"" in text, leaf
