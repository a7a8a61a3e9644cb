"""CPU-only tests of the benchmark harness: the data resolves, the drivers
run a tiny dict, the generator is seeded, the trace reduction adds up, a
later PR can add a cell and a metric with new files only.

    python -m pytest benchmark/tests -q
"""

import json
import os
import re
import shutil
import subprocess
import sys
import time

import numpy as np
import pytest

from benchmark import reference, trace_reduce, traffic, work
from benchmark import run as bench_run
from benchmark.readers import (
    detail_value,
    engine_stat_ratio,
    roofline,
    timeline_stage,
    trace_time,
)

ROOT = bench_run.ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")

TINY_STREAM = dict(
    name="tiny", num_classes=11, width_multiplier=0.25, image_size=32,
    channels=3, dtype="float32", batch=8, inflight=2, lanes=2,
    ingress_queue=16, stage_queue=8, drain_queue=64)
TINY_STREAM_TRAFFIC = dict(
    pattern="gradient", framerate="30/1", leaky_ingress=False, warm_batches=4,
    check_frames=16, check_pattern="ball", trace_seconds=0.3)
TINY_LM = dict(
    name="tiny", hidden_size=64, num_hidden_layers=2, num_attention_heads=4,
    intermediate_size=128, vocab_size=512, max_position_embeddings=256,
    dtype="float32", max_streams=4, block_tokens=16, steps_per_dispatch=8,
    temperature=0.0, attention="auto", prefix_cache=0)
TINY_LM_TRAFFIC = dict(
    loop="closed", clients=4, requests=64, warm_requests=4,
    prompt_tokens=dict(distribution="log_uniform", min=8, max=64),
    output_tokens=dict(distribution="log_uniform", min=8, max=32),
    check_prompt_tokens=[12, 40], check_pad_to=64, logprob_tol=0.05,
    request_timeout_s=60, trace_seconds=0.3)
RESULT_KEYS = {"correct", "attempted", "failed", "end_to_end", "detail"}


def _bench():
    return bench_run.load_json(ROOT, "BENCHMARK.json")


def test_benchmark_json_resolves_to_files():
    bench = _bench()
    base = os.path.join(ROOT, bench["paths"][0])
    assert bench["command"][1].startswith(bench["paths"][0] + "/")
    configs = {c["name"]: c for c in bench["configs"]}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    cells = [w["name"] for w in bench["workloads"]]
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    for c in bench["configs"]:
        data = bench_run.load_json(ROOT, c["file"])
        assert data["name"] == c["name"] and data["reduced"] == c["reduced"]
        assert os.path.exists(
            os.path.join(base, "drivers", data["driver"] + ".py"))
    for w in bench["workloads"]:
        assert w["config"] in configs and w["chips"] == 1
        assert len(w["why"]) <= 200
        cell = bench_run.load_cell(w["name"])
        assert {m["name"] for m in cell["end_to_end"]} > {"setup_s"}
        assert cell["per_layer"], f"{w['name']} reports no per-layer metric"
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= set(cells)
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1 and m["source"] == "host_clock"
    for m in bench["per_layer"]:
        spec = bench_run.load_json(base, "layer_metrics", m["name"] + ".json")
        assert os.path.exists(
            os.path.join(base, "readers", spec["reader"] + ".py"))
        moved = e2e[m["moves"]]
        assert set(m.get("workloads", cells)) <= set(
            moved.get("workloads", cells)), m["name"]
    for name in [n for x in ("configs", "workloads") for n in
                 (e["name"] for e in bench[x])]:
        assert NAME.match(name)


def test_every_data_file_names_code_that_exists():
    """Also the files of cells that BENCHMARK.json does not register yet."""
    base = os.path.join(ROOT, "benchmark")
    for kind, key, code in (("configs", "driver", "drivers"),
                            ("layer_metrics", "reader", "readers")):
        for name in sorted(os.listdir(os.path.join(base, kind))):
            spec = bench_run.load_json(base, kind, name)
            assert os.path.exists(os.path.join(base, code, spec[key] + ".py"))
    for name in sorted(os.listdir(os.path.join(base, "workloads"))):
        assert "who" in bench_run.load_json(base, "workloads", name)


def test_drop_in_cell_and_metric_need_new_files_only(tmp_path):
    """A later PR adds a cell and a per-layer metric: one workload file, one
    metric file, entries in BENCHMARK.json, and no other file edited."""
    bench = _bench()
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in (tmp_path / "benchmark").rglob("*")
              if p.is_file()}
    (tmp_path / "benchmark/workloads/throwaway_cell.json").write_text(
        json.dumps({"who": "nobody", "pattern": "black"}))
    (tmp_path / "benchmark/layer_metrics/throwaway_ms.x.json").write_text(
        json.dumps({"reader": "timeline_stage", "args": {"stages": ["sink"]}}))
    bench["workloads"].append(
        {"name": "throwaway_cell", "config": bench["configs"][0]["name"],
         "traffic": "throwaway", "chips": 1, "why": "drop-in test"})
    bench["end_to_end"][0]["workloads"].append("throwaway_cell")
    bench["per_layer"].append(
        {"name": "throwaway_ms.x", "unit": "ms", "better": "lower",
         "source": "program_span", "layer": "drain D2H",
         "moves": bench["end_to_end"][0]["name"],
         "workloads": ["throwaway_cell"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = bench_run.load_cell("throwaway_cell", root=str(tmp_path))
    assert cell["workload"]["pattern"] == "black"
    assert [m["name"] for m in cell["per_layer"]] == ["throwaway_ms.x"]
    run = {"trace": {"stages": {"frames": 3, "stages_ms": {"sink": 0.25}}}}
    assert bench_run.read_layer_metrics(cell, run) == {
        "throwaway_ms.x": {"value": 0.25, "unit": "ms"}}
    assert all(p.read_bytes() == b for p, b in before.items())


def test_stream_driver_runs_a_tiny_dict(tmp_path):
    from benchmark.drivers import stream

    out = stream.run_cell(TINY_STREAM, TINY_STREAM_TRAFFIC, 2 ** 31 + 7, 1.0,
                          False, t0=time.monotonic(), workdir=str(tmp_path))
    assert RESULT_KEYS <= set(out)
    assert out["correct"] and out["failed"] == 0 and out["attempted"] > 0
    assert out["end_to_end"]["stream_fps"] == out["attempted"] / 1.0
    assert out["end_to_end"]["setup_s"] > 0
    assert out["detail"]["missing_in_sequence"] == 0


def test_stream_driver_traced_gives_the_ledger_and_the_work(tmp_path):
    from benchmark.drivers import stream

    out = stream.run_cell(TINY_STREAM, TINY_STREAM_TRAFFIC, 3, 1.0, True,
                          t0=time.monotonic(), workdir=str(tmp_path))
    tr = out["trace"]
    assert tr["stages"]["frames"] > 0 and tr["flops_per_batch"] > 0
    assert tr["window_s"] >= 0.3
    run = {**out, "config": TINY_STREAM, "peaks": {"flops_per_s": 1e12}}
    assert timeline_stage.read(run, ["ingest", "lane_reorder"]) > 0
    # no device plane on the CPU: a reader that finds nothing returns nothing
    assert trace_time.read(run, None, ["jit_composed"]) is None


def test_lm_driver_runs_a_tiny_dict(tmp_path):
    from benchmark.drivers import lm

    out = lm.run_cell(TINY_LM, TINY_LM_TRAFFIC, 2 ** 31 + 7, 1.5, False,
                      t0=time.monotonic(), workdir=str(tmp_path))
    assert RESULT_KEYS | {"engine_stats"} <= set(out)
    assert out["correct"] and out["failed"] == 0 and out["attempted"] > 0
    assert out["end_to_end"]["lm_tokens_per_s"] > 0
    assert out["detail"]["ttft_p90_ms"] >= out["detail"]["ttft_p50_ms"] > 0
    assert detail_value.read(out, "ttft_p90_ms") == out["detail"]["ttft_p90_ms"]
    assert detail_value.read(out, "absent") is None
    assert out["detail"]["ttft_samples"] >= 10
    stats = out["engine_stats"]
    assert 0 < stats["active_slot_steps"] <= stats["slot_steps"]
    assert engine_stat_ratio.read(
        out, "active_slot_steps", "slot_steps") <= 100.0
    assert not [t for t in __import__("threading").enumerate()
                if t.name.startswith(("benchmark-client", "cb-engine"))]


def test_device_params_have_init_params_tree_shapes_and_dtypes():
    import jax

    from benchmark.drivers import lm
    from nnstreamer_tpu.models.transformer import init_params

    cfg = lm.transformer_config(TINY_LM)
    ours, theirs = lm.device_params(cfg, 5), init_params(cfg, seed=5)
    assert jax.tree.structure(ours) == jax.tree.structure(theirs)
    for a, b in zip(jax.tree.leaves(ours), jax.tree.leaves(theirs)):
        assert a.shape == b.shape and a.dtype == b.dtype
    assert abs(float(ours["w_in"].std()) - 0.02) < 2e-3
    assert float(ours["ln1"].min()) == 1.0
    again = lm.device_params(cfg, 5)
    assert np.array_equal(np.asarray(ours["qkv"]), np.asarray(again["qkv"]))
    other = lm.device_params(cfg, 6)
    assert not np.array_equal(np.asarray(ours["qkv"]), np.asarray(other["qkv"]))


def test_plain_reference_agrees_with_the_programs_forward():
    import jax.numpy as jnp

    from benchmark.drivers import lm
    from nnstreamer_tpu.models.transformer import build_forward

    cfg = lm.transformer_config(TINY_LM)
    params = lm.device_params(cfg, 1)
    tokens = traffic.prompt_tokens(1, 0, 48, cfg.vocab)
    ours = reference.transformer_logprobs(params, jnp.asarray(tokens), 30,
                                          cfg.n_layers)
    logits = build_forward(cfg)(params, jnp.asarray(tokens)[None])[0, 30]
    theirs = logits - jnp.log(jnp.sum(jnp.exp(logits)))
    assert float(jnp.max(jnp.abs(ours - theirs))) < 1e-4


def test_traffic_is_one_set_of_sizes_in_a_seeded_order():
    wl = TINY_LM_TRAFFIC
    a, again, b = (traffic.request_sizes(wl, s) for s in (7, 7, 2 ** 31 + 9))
    assert a == again and a != b and sorted(a) == sorted(b)
    prompts = [p for p, _ in a]
    assert min(prompts) >= 8 and max(prompts) <= 64 and len(a) == 64
    assert np.mean(prompts) < (8 + 64) / 2   # log-uniform leans short
    t1, t2 = (traffic.prompt_tokens(7, 3, 20, 512) for _ in range(2))
    assert np.array_equal(t1, t2) and t1.min() >= 1 and t1.max() < 512
    assert not np.array_equal(t1, traffic.prompt_tokens(8, 3, 20, 512))
    assert list(traffic.stratified(
        {"distribution": "fixed", "min": 5, "max": 5}, 3)) == [5, 5, 5]
    with pytest.raises(ValueError):
        traffic.stratified({"distribution": "zipf", "min": 1, "max": 2}, 3)


def test_prefill_buckets_double_from_the_engines_smallest():
    from benchmark.drivers import lm

    assert lm.prefill_buckets(64, 1024, 2048) == [64, 128, 256, 512, 1024]
    assert lm.prefill_buckets(8, 64, 256) == [16, 32, 64]
    assert lm.prefill_buckets(300, 300, 2048) == [512]


DEV = "/device:TPU:0"
# two programs with a gap between them; the ops of the first overlap and nest
OPS = [(DEV, "fusion.1", 0.0, 4e6), (DEV, "fusion.2", 2e6, 4e6),
       (DEV, "copy.3", 3e6, 1e6), (DEV, "fusion.1", 10e6, 2e6)]
MODULES = [(DEV, "jit_a(123)", 0.0, 6e6), (DEV, "jit_b(456)", 10e6, 2e6),
           (DEV, "jit_b(789)", 20e6, 1e6)]


def test_trace_reduce_union_program_sums_and_idle():
    assert trace_reduce.union([(5, 6), (0, 2), (1, 3), (1.5, 2)]) == [
        [0, 3], [5, 6]]
    assert trace_reduce.program_name("jit_dispatch(1234)") == "jit_dispatch"
    assert trace_reduce.op_name(
        "%copy.37 = bf16[8,128]{1,0:T(8,128)} copy(bf16[8,128]{0,1} %x)"
    ) == "%copy.37 bf16[8,128]"
    assert trace_reduce.op_name("%while.3 = (s32[]{:T(128)}, bf16[8]) while(") \
        == "%while.3 s32[]"
    assert trace_reduce.op_name("fusion.1") == "fusion.1"
    out = trace_reduce.reduce_events(OPS, MODULES)
    assert out["devices"] == 1
    assert out["busy_s"] == pytest.approx(0.008)       # [0,6] + [10,12] ms
    assert out["programs"]["jit_a"] == {"seconds": pytest.approx(0.006),
                                        "count": 1}
    assert out["programs"]["jit_b"] == {"seconds": pytest.approx(0.003),
                                        "count": 2}
    assert out["device_ops"][0] == ["fusion.1", pytest.approx(0.006)]
    assert out["idle_gaps"] == [["before jit_b", pytest.approx(0.012)]]
    assert out["device_span_s"] == pytest.approx(0.021)
    idle_share = 1 - out["busy_s"] / 0.021
    assert idle_share == pytest.approx(13 / 21)
    empty = trace_reduce.reduce_events([], [])
    assert empty["busy_s"] == 0.0 and empty["programs"] == {}


def test_readers_divide_the_trace_and_return_nothing_on_nothing():
    trace = trace_reduce.reduce_events(OPS, MODULES)
    trace["flops"] = 2e9
    run = {"trace": trace, "config": {"k": 4},
           "peaks": {"flops_per_s": 1e12}}
    assert trace_time.read(run, ["jit_b"]) == pytest.approx(1.5)
    assert trace_time.read(run, ["jit_b"], steps="k") == pytest.approx(0.375)
    assert trace_time.read(run, None, ["jit_a"]) == pytest.approx(8.0)
    assert trace_time.read(run, ["jit_missing"]) is None
    assert trace_time.read({}, ["jit_b"]) is None
    share = roofline.read(run, "flops", "flops_per_s", {"programs": ["jit_a"]})
    assert share == pytest.approx(100 * 2e9 / 0.006 / 1e12)
    assert roofline.read(run, "absent", "flops_per_s",
                         {"programs": ["jit_a"]}) is None
    assert engine_stat_ratio.read({"engine_stats": {"a": 3, "b": 4}},
                                  "a", "b") == 75.0
    assert engine_stat_ratio.read({"engine_stats": {"a": 3, "b": 0}},
                                  "a", "b") is None
    assert timeline_stage.read({"trace": {"stages": {"frames": 0}}},
                               ["ingest"]) is None


def test_decode_bytes_are_weights_once_plus_live_kv():
    kv = work.kv_bytes_per_token(24, 16, 128, 2)
    assert kv == 196608
    assert work.decode_bytes_per_step(1000, kv, 10) == 1000 + 1966080


def test_peaks_raise_on_an_unknown_device_kind():
    peaks = bench_run.load_json(ROOT, "benchmark", "peaks.json")
    assert bench_run.peaks_for(peaks, "TPU v5 lite")["flops_per_s"] == 197e12
    assert bench_run.peaks_for(peaks, "TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(SystemExit):
        bench_run.peaks_for(peaks, "TPU v9 imaginary")


def test_run_py_exits_nonzero_without_a_tpu_and_prints_no_result():
    bench = _bench()
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, *bench["command"][1:], "--workload",
         bench["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=120)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "needs a TPU" in proc.stderr
