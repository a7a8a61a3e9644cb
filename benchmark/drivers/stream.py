"""Driver for stream cells: one source -> model -> sink pipeline, free-running.

``run_cell`` builds the flagship topology of ``bench.py build_pipeline``
(copied, not imported: a later PR may change ``bench.py``), checks the
labels of a few distinct frames against a direct ``jax.jit`` of the model
(copied from ``chip_smoke.py``), then starts the free-running source once,
lets a warm part pass, and counts the frames that reach the sink during the
window. Everything that belongs to one configuration or one traffic mix
comes in through the two dicts; nothing here knows a cell's name.
"""

from __future__ import annotations

import gc
import time

import numpy as np

from benchmark import trace_reduce, work

MODEL_PREFIX = "benchmark_stream_"


def _dtype(name: str):
    import jax.numpy as jnp

    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[name]


def _register_model(config: dict, seed: int):
    """The configuration's model, weights from the seed, under a name the
    ``tensor_filter framework=jax`` element can look up."""
    from nnstreamer_tpu.filters.jax_backend import register_jax_model
    from nnstreamer_tpu.models.mobilenet_v2 import mobilenet_v2

    apply_fn, params, in_info, out_info = mobilenet_v2(
        num_classes=config["num_classes"], width=config["width_multiplier"],
        image_size=config["image_size"], batch=config["batch"],
        dtype=_dtype(config["dtype"]), seed=seed % (2 ** 31 - 1))
    name = f"{MODEL_PREFIX}{config['name']}_b{config['batch']}"
    register_jax_model(name, apply_fn, params, in_info=in_info,
                       out_info=out_info)
    return name, apply_fn, params


def _source(config: dict, pattern: str, framerate: str, frames: int) -> str:
    size = config["image_size"]
    return (f"videotestsrc num-buffers={frames} width={size} height={size} "
            f"pattern={pattern} framerate={framerate} ! tensor_converter ! ")


def build_pipeline(config: dict, workload: dict, model: str, pattern: str,
                   frames: int, name: str):
    """``bench.py build_pipeline``'s topology: converter -> ingress queue ->
    aggregator -> staging queue (async H2D) -> transform -> filter ->
    labeling -> drain queue (grouped D2H) -> sink. The ingress queue blocks
    unless the traffic says it leaks."""
    import nnstreamer_tpu as nt

    batch = config["batch"]
    leaky = ("leaky=downstream stamp-admission=true "
             if workload.get("leaky_ingress") else "")
    desc = (
        _source(config, pattern, workload["framerate"], frames)
        + f"queue name=q_ingress max-size-buffers={config['ingress_queue']} "
        f"{leaky}! "
        f"tensor_aggregator frames-in=1 frames-out={batch} "
        f"frames-flush={batch} frames-dim=3 concat=true ! "
        f"queue name=stage max-size-buffers={config['stage_queue']} "
        "prefetch-device=true ! "
        "tensor_transform mode=arithmetic "
        "option=typecast:float32,add:-127.5,div:127.5 ! "
        f"tensor_filter framework=jax model={model} name=filter "
        f"inflight={config['inflight']} ! "
        "tensor_decoder mode=image_labeling option2=batched ! "
        f"queue name=tohost max-size-buffers={config['drain_queue']} "
        "materialize-host=true ! "
        "tensor_sink name=sink to-host=true")
    pipe = nt.parse_launch(desc, pipeline=nt.Pipeline(name=name))
    pipe.lanes = config["lanes"]
    return pipe


def _source_frames(config: dict, workload: dict, frames: int):
    """The uint8 frames the check's source produces, [frames, H, W, 3]."""
    import nnstreamer_tpu as nt

    size = config["image_size"]
    pipe = nt.parse_launch(
        _source(config, workload["check_pattern"], workload["framerate"],
                frames) + "tensor_sink name=sink to-host=true")
    got = []
    pipe.get("sink").connect(
        lambda b: got.append(np.asarray(b.tensors[0]).reshape(size, size, 3)))
    msg = pipe.run(timeout=120)
    if msg is None or msg.kind != "eos" or len(got) != frames:
        raise RuntimeError(f"frame capture: {msg}, {len(got)} of {frames}")
    return np.stack(got)


def reference_labels(apply_fn, params, frames_u8, batch: int):
    """(argmax, max logit) per frame of a plain ``jax.jit`` of the model,
    with the pipeline's own normalisation, batch by batch
    (``chip_smoke.py _reference_labels``)."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def ref(p, u8):
        logits = apply_fn(p, (u8.astype(jnp.float32) + -127.5) / 127.5)
        return jnp.argmax(logits, axis=-1), jnp.max(logits, axis=-1)

    dev_params = jax.device_put(params)
    labels, scores = [], []
    for i in range(0, len(frames_u8), batch):
        idx, top = ref(dev_params, frames_u8[i:i + batch])
        labels += np.asarray(idx).tolist()
        scores += np.asarray(top, np.float32).tolist()
    return labels, scores


def check_labels(config, workload, model, apply_fn, params) -> dict:
    """Distinct frames through the same topology to EOS; their labels must
    equal the direct jit's argmax and their top scores lie within
    ``chip_smoke.py``'s tolerance. Also warms the window's one shape."""
    frames = int(workload["check_frames"])
    frames_u8 = _source_frames(config, workload, frames)
    ref_labels, ref_scores = reference_labels(
        apply_fn, params, frames_u8, config["batch"])
    pipe = build_pipeline(config, workload, model, workload["check_pattern"],
                          frames, "benchmark_stream_check")
    bufs = []
    pipe.get("sink").connect(bufs.append)
    msg = pipe.run(timeout=900)
    if msg is None or msg.kind != "eos":
        raise RuntimeError(f"check pipeline did not reach EOS: {msg}")
    labels = [int(i) for b in bufs for i in b.meta["label_index"]]
    scores = [float(v) for b in bufs for v in b.meta["score"]]
    worst = max((abs(a - b) for a, b in zip(scores, ref_scores)),
                default=float("inf"))
    tol = 1e-3 + 1e-2 * max(abs(v) for v in ref_scores)
    wrong = (sum(a != b for a, b in zip(labels, ref_labels))
             + abs(len(labels) - frames))
    return {"frames": frames, "wrong_labels": wrong,
            "distinct_labels": len(set(labels)),
            "score_max_abs_err": worst, "score_tol": tol,
            "ok": wrong == 0 and worst <= tol}


class _Arrivals:
    """What the sink saw: one record per buffer, stamped on arrival. The
    callback runs on the sink's thread, so it only appends."""

    def __init__(self):
        self.records = []  # (t, pts, labels)

    def __call__(self, buf):
        self.records.append((time.monotonic(), buf.pts,
                             buf.meta["label_index"]))


def run_cell(config: dict, workload: dict, seed: int, seconds: float,
             trace: bool, t0: float, workdir: str) -> dict:
    from nnstreamer_tpu.obs import timeline as _timeline

    model, apply_fn, params = _register_model(config, seed)
    check = check_labels(config, workload, model, apply_fn, params)
    size = config["image_size"]
    flops_per_batch = work.model_flops(
        apply_fn, params, (config["batch"], size, size, config["channels"])
    ) if trace else None

    pipe = build_pipeline(config, workload, model, workload["pattern"], -1,
                          "benchmark_stream_window")
    arrivals = _Arrivals()
    pipe.get("sink").connect(arrivals)
    gc.collect()
    gc.freeze()
    pipe.start()
    traced = None
    try:
        # warm part: the first dispatch and a few batches have been delivered
        deadline = time.monotonic() + 600
        while len(arrivals.records) < int(workload["warm_batches"]):
            if time.monotonic() > deadline:
                raise RuntimeError("stream never warmed: "
                                   f"{len(arrivals.records)} batches")
            time.sleep(0.01)
        t_open = time.monotonic()
        t_close = t_open + seconds
        if trace:
            span = min(float(workload["trace_seconds"]), seconds)
            time.sleep(max(0.0, (seconds - span) / 2))
            # the frame ledger is on for the traced seconds only: its ring
            # is smaller than a whole window's spans
            tl = _timeline.activate(capacity=1 << 18)
            ledger = {}
            try:
                traced = trace_reduce.profile(
                    workdir, span,
                    at_end=lambda: ledger.update(tl.stage_breakdown()))
            finally:
                _timeline.deactivate()
            traced["stages"] = ledger
        time.sleep(max(0.0, t_close - time.monotonic()))
    finally:
        pipe.stop()
        gc.unfreeze()

    inside = [r for r in list(arrivals.records) if t_open <= r[0] < t_close]
    frames = sum(len(r[2]) for r in inside)
    # the sink's sequence: each buffer carries its first frame's pts, so in
    # order and with nothing missing consecutive buffers lie one batch apart
    step = None
    missing = 0
    for a, b in zip(inside, inside[1:]):
        d = b[1] - a[1]
        if step is None:
            step = d
        if d != step or d <= 0:
            missing += 1
    # one cached frame in, so one label out: any other is a wrong one
    labels = [int(i) for r in inside for i in r[2]]
    wrong_labels = sum(i != labels[0] for i in labels)
    failed = missing + wrong_labels + check["wrong_labels"]
    out = {
        "correct": bool(check["ok"] and failed == 0 and frames > 0),
        "attempted": frames,
        "failed": failed,
        "end_to_end": {
            "stream_fps": frames / seconds,
            "setup_s": t_open - t0,
        },
        "detail": {
            "check": check, "frames": frames, "batches": len(inside),
            "batch": config["batch"], "missing_in_sequence": missing,
            "wrong_labels_in_window": wrong_labels,
            "window_labels": sorted(set(labels))[:4],
            "warm_batches": int(workload["warm_batches"]),
            # frames that arrived in each whole second of the window: drift
            # or steps inside a run show here
            "frames_by_second": np.bincount(
                [int(r[0] - t_open) for r in inside],
                weights=[len(r[2]) for r in inside]).astype(int).tolist(),
        },
    }
    if traced is not None:
        traced["flops_per_batch"] = flops_per_batch
        out["trace"] = traced
    return out
