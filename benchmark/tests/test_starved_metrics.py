"""CPU-only tests of the four per-layer metrics PR 35 added as data: each
resolves for every registered cell through ``run.load_cell``, reads the right
number from hand-made ``engine_stats`` through the reader the benchmark
already has, and reads nothing from a commit without the counters.

    python -m pytest benchmark/tests -q
"""

import time

import pytest

from benchmark import run as bench_run
from benchmark.tests.test_benchmark import TINY_LM, TINY_LM_TRAFFIC

NEW = ("device_starved_share.closed", "starved_admit_share.closed",
       "starved_between_share.closed", "prefill_pad_share.closed")
#: growth over a window, as the drivers hand it over: 50 s of loop of which
#: the device had nothing queued for 4.5 s
STATS = {"loop_us": 50_000_000, "starved_us": 4_500_000,
         "starved_admit_us": 1_000_000, "starved_select_us": 250_000,
         "starved_dispatch_us": 1_250_000, "starved_emit_us": 1_500_000,
         "starved_other_us": 500_000, "prefill_tokens": 1864,
         "prefill_bucket_tokens": 2505, "admit_boundaries": 3,
         "admissions": 7, "dispatches": 100}
WANT = {"device_starved_share.closed": 9.0, "starved_admit_share.closed": 2.0,
        "starved_between_share.closed": 4.5,
        "prefill_pad_share.closed": 100 * (2505 - 1864) / 2505}


def _cells():
    bench = bench_run.load_json(bench_run.ROOT, "BENCHMARK.json")
    return [w["name"] for w in bench["workloads"]]


def _new_metrics(cell):
    mine = [m for m in bench_run.load_cell(cell)["per_layer"]
            if m["name"] in NEW]
    assert [m["name"] for m in mine] == list(NEW), cell
    return {"per_layer": mine}


@pytest.mark.parametrize("cell", _cells())
def test_each_new_metric_resolves_and_reads_its_counters(cell):
    mine = _new_metrics(cell)
    for m in mine["per_layer"]:
        assert m["reader"] == "engine_stat_mean" and m["unit"] == "%"
        assert (m["source"], m["layer"], m["moves"], m["better"]) == (
            "program_counter", "LM engine", "lm_tokens_per_s", "lower")
    values = bench_run.read_layer_metrics(mine, {"engine_stats": STATS})
    assert {k: v["value"] for k, v in values.items()} == pytest.approx(WANT)
    # the shares of one loop: what is left of the whole is the launch
    launch = values["device_starved_share.closed"]["value"] \
        - values["starved_admit_share.closed"]["value"] \
        - values["starved_between_share.closed"]["value"]
    assert launch == pytest.approx(100 * 1_250_000 / 50_000_000)


@pytest.mark.parametrize("cell", _cells())
def test_a_commit_without_the_counters_reads_nothing(cell):
    mine = _new_metrics(cell)
    parent = {k: v for k, v in STATS.items()
              if not k.startswith(("starved_", "prefill_"))}
    assert bench_run.read_layer_metrics(mine, {"engine_stats": parent}) == {}
    assert bench_run.read_layer_metrics(mine, {}) == {}
    # no prefill ran in the window: a share of nothing is no number
    none = {**STATS, "prefill_tokens": 0, "prefill_bucket_tokens": 0}
    assert set(bench_run.read_layer_metrics(mine, {"engine_stats": none})) \
        == set(NEW) - {"prefill_pad_share.closed"}


def test_the_new_entries_are_the_last_four_and_list_every_cell():
    bench = bench_run.load_json(bench_run.ROOT, "BENCHMARK.json")
    last = bench["per_layer"][-4:]
    assert [m["name"] for m in last] == list(NEW)
    assert all(m["workloads"] == _cells() for m in last)
    # four more a cell than the cells' own tests pinned before PR 35
    assert {c: len(bench_run.load_cell(c)["per_layer"]) for c in _cells()} \
        == {"pythia_chat_closed": 14, "granite_h_chat_closed": 19,
            "qwen3next_chat_closed": 19, "dsv2lite_longctx_closed": 18}


def test_lm_driver_run_carries_the_starved_counters_to_the_readers(tmp_path):
    from benchmark.drivers import lm

    out = lm.run_cell(TINY_LM, TINY_LM_TRAFFIC, 13, 1.5, False,
                      t0=time.monotonic(), workdir=str(tmp_path))
    stats = out["engine_stats"]
    starved = [k for k in stats if k.startswith("starved_")
               and k != "starved_us"]
    assert len(starved) == 5
    assert sum(stats[k] for k in starved) == stats["starved_us"]
    assert all(stats[k] <= stats["phase_" + k[len("starved_"):]]
               for k in starved)
    assert 0 < stats["prefill_tokens"] <= stats["prefill_bucket_tokens"]
    assert 0 < stats["admit_boundaries"] <= stats["admissions"]
    values = bench_run.read_layer_metrics(
        _new_metrics("pythia_chat_closed"), {**out, "config": TINY_LM})
    assert set(values) == set(NEW)
    assert 0 < values["device_starved_share.closed"]["value"] <= 100
    assert 0 <= values["prefill_pad_share.closed"]["value"] < 50
