"""Run one cell of the benchmark once, on the TPU, and print its result.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything about a cell is data: ``BENCHMARK.json`` names the cell, its
configuration and its metrics; ``configs/<config>.json`` holds the sizes and
names the driver; ``workloads/<cell>.json`` holds the traffic parameters;
``layer_metrics/<metric>.json`` names the reader of each per-layer metric.
This file has no branch on any of those names. The last line of standard
output is the result; the line before it carries the detail (medians,
counts behind each percentile, set-up phases).
"""

from __future__ import annotations

import time

T0 = time.monotonic()  # set-up is counted from here to the window's start

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
WORKDIR = os.path.join(ROOT, ".benchmark_work")


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts), encoding="utf-8") as f:
        return json.load(f)


def applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(cell: str, root: str = ROOT) -> dict:
    """Everything the data says about one cell: its entry, its
    configuration, its traffic, its end-to-end metrics and, for each of its
    per-layer metrics, the metric's own file."""
    bench = load_json(root, "BENCHMARK.json")
    entry = next((w for w in bench["workloads"] if w["name"] == cell), None)
    if entry is None:
        raise SystemExit(f"benchmark: no cell {cell!r} in BENCHMARK.json "
                         f"({', '.join(w['name'] for w in bench['workloads'])})")
    conf = next(c for c in bench["configs"] if c["name"] == entry["config"])
    base = os.path.join(root, bench["paths"][0])
    return {
        "entry": entry,
        "config": load_json(root, conf["file"]),
        "workload": load_json(base, "workloads", f"{cell}.json"),
        "end_to_end": [m for m in bench["end_to_end"] if applies(m, cell)],
        "per_layer": [
            {**m, **load_json(base, "layer_metrics", f"{m['name']}.json")}
            for m in bench["per_layer"] if applies(m, cell)],
        "peaks": load_json(base, "peaks.json"),
    }


def peaks_for(peaks: dict, device_kind: str) -> dict:
    try:
        return peaks["peaks"][device_kind]
    except KeyError:
        raise SystemExit(
            f"benchmark: device_kind {device_kind!r} is not in peaks.json "
            f"({', '.join(peaks['peaks'])}); a peak is never guessed") from None


def require_tpu(chips: int):
    """The devices, or no result: any backend but ``tpu``, or fewer chips
    than the cell asks for, ends the run (``chip_smoke.require_tpu``)."""
    import jax

    backend = jax.default_backend()
    devices = jax.devices()
    if backend != "tpu":
        raise SystemExit(
            f"benchmark: needs a TPU, but JAX initialized backend "
            f"{backend!r} (device_kind {devices[0].device_kind!r}). "
            f"No result.")
    if len(devices) < chips:
        raise SystemExit(f"benchmark: the cell needs {chips} chips, JAX "
                         f"found {len(devices)}. No result.")
    return devices


def device_report(devices, traced) -> dict:
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    out = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices), "memory_peak_bytes": peak}
    if traced is not None:
        out["busy_s"] = traced["busy_s"]
        out["window_s"] = traced["window_s"]
    return out


def read_layer_metrics(cell: dict, run: dict) -> dict:
    """Each per-layer metric through the reader its file names. A reader
    that finds nothing returns None and the metric is left out."""
    metrics = {}
    for m in cell["per_layer"]:
        reader = importlib.import_module(f"benchmark.readers.{m['reader']}")
        value = reader.read(run, **m.get("args", {}))
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return metrics


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, ROOT)
    cell = load_cell(args.workload)
    # one rule for the compile cache (PR 21): the directory the environment
    # names, else a fixed one inside this checkout
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(ROOT, ".jax_cache"))
    os.makedirs(os.environ["JAX_COMPILATION_CACHE_DIR"], exist_ok=True)
    os.makedirs(WORKDIR, exist_ok=True)

    devices = require_tpu(int(cell["entry"]["chips"]))
    t_chip = time.monotonic()
    peaks = peaks_for(cell["peaks"], devices[0].device_kind)
    from nnstreamer_tpu.pipeline import continuity

    continuity.arm_compile_cache()
    driver = importlib.import_module(
        f"benchmark.drivers.{cell['config']['driver']}")
    result = driver.run_cell(cell["config"], cell["workload"], args.seed,
                             args.seconds, bool(args.trace), t0=T0,
                             workdir=WORKDIR)
    traced = result.get("trace")
    if args.trace:
        run = {**result, "config": cell["config"], "peaks": peaks}
        metrics = read_layer_metrics(cell, run)
    else:
        metrics = {m["name"]: {"value": float(result["end_to_end"][m["name"]]),
                               "unit": m["unit"]}
                   for m in cell["end_to_end"]}
    detail = dict(result.get("detail", {}))
    detail["reach_chip_s"] = t_chip - T0
    detail["compile_cache"] = continuity.cache_stats()
    if traced is not None:
        detail["programs"] = traced["programs"]
        detail["trace"] = {k: traced.get(k) for k in (
            "device_lines", "trace_bytes", "stop_and_reduce_s", "devices",
            "device_span_s", "stages")}
    print(json.dumps({"detail": detail, "end_to_end": result["end_to_end"]}))
    line = {"correct": bool(result["correct"]),
            "attempted": int(result["attempted"]),
            "failed": int(result["failed"]), "metrics": metrics,
            "device": device_report(devices, traced)}
    if traced is not None:
        line["breakdown"] = {"device_ops": traced["device_ops"],
                             "idle_gaps": traced["idle_gaps"]}
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
