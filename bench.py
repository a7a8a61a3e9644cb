"""Benchmark — MobileNetV2 224×224 classification pipeline on TPU.

The north-star metric (BASELINE.json): pipeline FPS + p50 per-frame latency
for the stock image-classification pipeline. This drives the REAL pipeline
(videotestsrc → tensor_converter → tensor_transform → tensor_filter[jax]
→ tensor_decoder[image_labeling] → tensor_sink) end to end — source frame
synthesis, caps negotiation, per-element stats, XLA invoke — exactly how
the reference measures itself (runtime latency/throughput around invoke,
tensor_filter.c:325-423).

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": "fps", "vs_baseline": N, ...}

Runs in ONE process, on the TPU only: ``main()`` refuses any other
backend (a CPU number must never appear under a device metric's name)
and an unknown ``device_kind`` is an error in the peaks table.

``vs_baseline``: ratio vs the driver-recorded baseline constant
(``FALLBACK_BASELINE_FPS``).

How to read the bound fields (the report's own limiter analysis):

- ``value`` is the steady-state (warm) median; ``fps_cold`` and the
  chronological ``fps_runs`` expose compile warm-up separately.
- ``device_fps_ceiling`` (model dispatch alone) bounds what the CHIP
  sustains; ``pipeline_efficiency = fps_median/ceiling`` (the gated
  median-of-k statistic, not the single headline run).
- ``ingest_bound_fps`` re-runs the IDENTICAL topology with a free
  model: the ceiling the host+link+framework impose with zero model
  cost. ``vs_ingest_bound`` near 1 is the written proof that a wall
  number is transfer/framework-bound, not model- or scheduler-bound;
  above 1 means the host↔device link was slower in the probe's windows
  than across the flagship's median-of-N (treat the bound as
  inconclusive for that session).
- ``value_norm`` / ``norm_runs`` / ``spread_norm``: link-normalized
  score. Each flagship repeat is paired with an ingest-ceiling sample
  taken right after it; the ratio fps/ceiling cancels drift in the
  host↔device link between repeats. Caveat: when the link changes
  WITHIN a pair (~10 s apart) individual ratios can exceed 1 and
  ``spread_norm`` blows up.
- ``latency_p50/p99_ms`` is end-to-end per-frame latency under 30 fps
  realtime pacing (create→sink materialization, window wait included)
  with the ``latency_budget_ms`` adaptive-batching budget active: the
  aggregator flushes partial padded windows rather than holding frames
  for the full batch window (elements/aggregator.py latency-budget-ms).
  ``latency_sat_*`` is the same stat inside the saturated throughput
  runs, sampled only for frames the leaky ingress queue ADMITTED and
  measured from the admission stamp (service latency of served traffic
  — the pre-admission wait of a free-running source is backlog depth,
  not pipeline latency); ``latency_dropped_frames`` counts what the
  queue shed instead.
- ``fps_median`` / ``spread_mad``: robust companions to ``value`` /
  ``spread_warm`` — true median of the warm runs and median absolute
  deviation over it. The max−min ``spread_warm`` moves by a wild run's
  full excursion; the MAD barely notices it, so perf GATES should
  compare medians and read ``spread_mad`` for stability.
- ``slo_budget_ms`` / ``admitted_fps`` / ``shed_ratio``: the SLO
  scheduler's report card (``BENCH_SLO_BUDGET_MS`` > 0 attaches
  serving/scheduler.py to the saturated runs). ``admitted_fps`` is the
  served ADMITTED population per wall second; ``shed_ratio`` the share
  of offered traffic turned away (door rejections + post-stamp sheds).
  The SLO contract to check: ``latency_sat_p99_ms`` ≤ 2x budget while
  ``admitted_fps`` stays ≥80% of the unscheduled saturation rate.
- ``d2h_per_frame`` / ``resident_ratio``: device-residency health.
  Explicit device→host materializations per frame (sink-only
  materialization in the stock topology ⇒ one grouped fetch per
  sink-bound buffer = 1/batch; 0 once the drain-side batched fetch
  carries them) and the share of DeviceBuffer pad crossings forwarded
  without a host copy. See "Device residency" in docs/profiling.md;
  NNSTPU_RESIDENT=0 turns the layer off.
- ``h2d_batched_uploads`` / ``h2d_batched_frames`` /
  ``d2h_batched_fetches``: staged multi-frame transfer batching (one
  ``device_put``/``device_get`` per drained run — "Whole-graph fusion &
  transfer batching" in docs/profiling.md). Frames carried by these
  paid no per-frame transfer round trip.
- ``mfu_*`` use XLA's own flop count over the chip's public bf16 peak.
"""

from __future__ import annotations

import gc
import json
import os
import sys
import time

import numpy as np

#: 800 frames (100 batch-8 buffers) — long enough that the fixed per-run
#: costs (first grouped flush, trailing drain round trip) amortize to a
#: small share of the span
N_FRAMES = int(os.environ.get("BENCH_FRAMES", "800"))
WARMUP = int(os.environ.get("BENCH_WARMUP", "10"))
#: the flagship reports the median of this many runs (the first run also
#: pays the compile); 9 samples keep the median from landing on an
#: outlier
REPEATS = int(os.environ.get("BENCH_REPEATS", "9"))
IMAGE = 224

# Reference baseline: TFLite CPU (xnnpack) MobileNetV2 fp32 FPS on this
# class of host, as the driver recorded it (a constant, never re-measured
# here).
FALLBACK_BASELINE_FPS = 40.0


#: flagship micro-batch: the aggregator packs this many frames into one
#: MXU dispatch, amortizing the fixed per-dispatch host↔device cost over
#: 8 frames (the BASELINE.json north-star's own mux/merge-batching
#: prescription, applied in-stream).
BATCH = int(os.environ.get("BENCH_BATCH", "8"))

#: dispatch-window depth for the flagship filter (pipeline/dispatch.py):
#: K device batches may be outstanding before the producer fences, so the
#: host prepares batch N+1 while the chip runs batch N. 0 = synchronous.
INFLIGHT = int(os.environ.get("BENCH_INFLIGHT", "2"))

#: parallel ingest lanes (pipeline/lanes.py): the replicable pre-queue
#: host segment runs across N worker lanes with in-order reassembly.
#: Applies to the flagship AND the interleaved ingest-ceiling probe
#: (identical topology contract), so ingest_bound_fps is recomputed
#: under the same lane count the flagship runs with. NNSTPU_LANES
#: overrides; 1 restores the serial ingest path.
LANES = int(os.environ.get("BENCH_LANES", "4"))

#: fixed-length warmup drain (buffers of `batch` frames) run once before
#: the measured repeats: absorbs the jit compile, pool/lane-arena priming and the first fused-region trace so run 1 of
#: the repeat loop starts from the same steady state as run N — the
#: other half (with the gc fence in _collect) of taming spread_warm
WARMUP_DRAIN = int(os.environ.get("BENCH_WARMUP_DRAIN", "4"))

#: SLO budget in ms for the saturated runs (serving/scheduler.py): >0
#: attaches the deadline scheduler — admission control at the leaky
#: ingress, EDF ordering, shed-late-first, feedback-tuned batch cap —
#: and the JSON grows admitted_fps / shed_ratio / slo_budget_ms. 0
#: (default) is the kill switch: no scheduler object is built and the
#: pipeline runs the exact pre-scheduler path.
SLO_BUDGET_MS = float(os.environ.get("BENCH_SLO_BUDGET_MS", "0") or 0)

#: mesh-sharded serving plane (parallel/serve.py): BENCH_MESH=dp8 runs
#: the flagship with `mesh=dp8` on the tensor_filter and the JSON grows
#: `mesh` / `shard_scaling` (warm median over a single-device reference
#: run from the same session) / `reshard_bytes_per_frame`
#: (matched-sharding boundaries move zero bytes, so this should be 0).
#: Unset (the default) leaves the single-device path — and the JSON's
#: mesh fields are null.
MESH_SPEC = os.environ.get("BENCH_MESH", "").strip()

#: perf gates (the determinism item): the JSON grows a `gates` field
#: judging fps_median, spread_mad, and saturation p99 against these
#: thresholds. spread_mad defaults ON (warm spread under 0.15 of the
#: median); the other two arm via env / the SLO budget.
#: BENCH_ENFORCE_GATES=1 turns a failing gate into a nonzero exit.
GATE_FPS_MEDIAN_MIN = float(
    os.environ.get("BENCH_GATE_FPS_MEDIAN_MIN", "0") or 0)
GATE_SPREAD_MAD_MAX = float(
    os.environ.get("BENCH_GATE_SPREAD_MAD_MAX", "0.15") or 0)
GATE_SAT_P99_MS_MAX = float(
    os.environ.get("BENCH_GATE_SAT_P99_MS_MAX", "0")
    or (2.0 * SLO_BUDGET_MS if SLO_BUDGET_MS > 0 else 0))
ENFORCE_GATES = os.environ.get(
    "BENCH_ENFORCE_GATES", "").strip().lower() in ("1", "true", "yes", "on")

#: last measured run's flight-recorder harvest (obs/flight.py): the
#: always-on attribution/SLO snapshot, captured before the pipeline
#: object is discarded so the JSON can name the dominant-variance stage
#: without a traced run
_LAST_FLIGHT: dict = {}


def _device_fence() -> None:
    """Block until ALL previously dispatched device work retired.

    With a dispatch window (inflight>0) run N's trailing async work —
    the drained window's D2H copies, XLA donation cleanup — can still
    occupy the device when ``run()`` returns; without a fence it bleeds
    into run N+1's measurement window and into the interleaved ingest
    probe, which is exactly the warm-spread noise the per-run pairing
    exists to cancel. A trivial op enqueued now completes only after
    everything already queued on the device stream."""
    import jax.numpy as jnp

    jnp.zeros((), jnp.int32).block_until_ready()


def _register_mnv2(batch: int) -> str:
    import jax.numpy as jnp

    from nnstreamer_tpu.filters.jax_backend import (
        is_jax_model_registered,
        register_jax_model,
    )

    model_name = f"mobilenet_v2_bench_b{batch}"
    if not is_jax_model_registered(model_name):
        from nnstreamer_tpu.models.mobilenet_v2 import mobilenet_v2

        apply_fn, params, in_info, out_info = mobilenet_v2(
            image_size=IMAGE, batch=batch, dtype=jnp.bfloat16
        )
        register_jax_model(model_name, apply_fn, params,
                           in_info=in_info, out_info=out_info)
    return model_name


_ARTIFACT_CACHE: dict = {}


def _artifact_path(batch: int) -> str:
    """Export the flagship model as a compiled StableHLO artifact once and
    run the pipeline from the FILE (BENCH_ARTIFACT=1): proves the
    opaque-model-file path end to end at benchmark scale."""
    if batch not in _ARTIFACT_CACHE:
        import tempfile

        import jax.numpy as jnp

        from nnstreamer_tpu.filters.artifact import save_artifact
        from nnstreamer_tpu.models.mobilenet_v2 import mobilenet_v2

        apply_fn, params, in_info, _ = mobilenet_v2(
            image_size=IMAGE, batch=batch, dtype=jnp.bfloat16)
        import jax

        path = os.path.join(tempfile.gettempdir(),
                            f"bench_mnv2_b{batch}.jaxexp")
        save_artifact(path, apply_fn, params, in_info=in_info,
                      platforms=(jax.default_backend(),))
        _ARTIFACT_CACHE[batch] = path
    return _ARTIFACT_CACHE[batch]


def build_pipeline(batch: int = BATCH, live_fps: int = 0,
                   n_frames: int = None, model_override: str = None,
                   latency_budget_ms: int = 0):
    from nnstreamer_tpu import parse_launch

    if model_override is not None:
        model_name = model_override
    elif os.environ.get("BENCH_ARTIFACT", "").strip() in ("1", "true",
                                                          "yes"):
        model_name = _artifact_path(batch)
    else:
        model_name = _register_mnv2(batch)
    # a partial trailing window never leaves the aggregator: round the
    # frame count to a batch multiple so the configured workload is what
    # actually gets measured
    if n_frames is None:
        n_frames = N_FRAMES
    n_frames = ((n_frames + batch - 1) // batch) * batch
    live = (f"is-live=true framerate={live_fps}/1 " if live_fps else "")
    # micro-batch stage BEFORE the transform: frames cross the
    # host↔device link as uint8 (4x fewer bytes than float32) and the
    # typecast/normalize runs on-device inside the fused region with the
    # model
    # latency-budget adaptive batching (aggregator latency-budget-ms):
    # live runs bound each frame's admission wait — a window short of
    # `batch` flushes early, padded to the compiled shape, and the sink
    # trims the padding (elements/aggregator.py). Saturated runs fill
    # windows faster than any budget fires, so throughput is untouched.
    # pad-device: partial windows ship only their real frames; the
    # staging queue zero-pads on device (a padded uint8 batch-8 window
    # is 1.2 MB of pad rows that never cross the link)
    budget = (f"latency-budget-ms={latency_budget_ms} pad-device=true "
              if latency_budget_ms else "")
    agg = (f"tensor_aggregator frames-in=1 frames-out={batch} "
           f"frames-flush={batch} frames-dim=3 concat=true {budget}! "
           if batch > 1 else "")
    # queue after the converter decouples host frame synthesis from device
    # dispatch (source thread fills frame N+1 while the fused region runs N)
    # H2D staging queue between the aggregator and the fused XLA region:
    # prefetch-device issues an async device_put on the producer side, so
    # the uint8 batch's upload overlaps the PREVIOUS batch's compute and
    # the dispatch thread never blocks on an implicit per-call transfer
    # (the pipeline analog of the serving engine's one-block-behind
    # overlap, serving/engine.py _inflight)
    # latency mode shrinks the in-flight windows (staging 4, drain 4 vs
    # 8/64): backpressure then reaches the aggregator's budget gate
    # (accepts_now) within ~8 windows, so on a saturated link budget
    # mode degrades to plain batching instead of stacking seconds of
    # queue wait; throughput mode keeps the deep queues (backlog absorb)
    stage_n, drain_n = (4, 4) if latency_budget_ms else (8, 64)
    stage = (f"queue max-size-buffers={stage_n} prefetch-device=true ! "
             if os.environ.get("BENCH_STAGE", "1").strip() not in
             ("0", "false", "no") else "")
    # saturation (non-live) runs: the source free-runs, so a blocking
    # ingress queue lets an unbounded create→sink backlog build and the
    # reported saturated p99 measures queue depth (5 s observed), not
    # service latency. leaky=downstream bounds the standing backlog to
    # the queue's capacity — frames that DO reach the sink carry a
    # bounded wait — while the delivered rate stays the bottleneck rate.
    # Live runs are already paced by the source clock and stay blocking
    # (dropping paced frames would corrupt the latency population).
    # stamp-admission marks each frame the leaky queue ACCEPTS: the sink
    # then reports a served-traffic latency population (admitted→sink)
    # next to the create-based one, and the drop counter's delta becomes
    # latency_dropped_frames — the saturated p99 stops measuring the
    # free-running source's pre-admission backlog wait
    ingress = ("queue max-size-buffers=16 ! " if live_fps else
               "queue name=q_ingress max-size-buffers=16 "
               "leaky=downstream stamp-admission=true ! ")
    pipe = parse_launch(
        f"videotestsrc num-buffers={n_frames} width={IMAGE} height={IMAGE} "
        f"pattern=gradient {live}! "
        f"tensor_converter ! {ingress}"
        f"{agg}{stage}"
        "tensor_transform mode=arithmetic "
        "option=typecast:float32,add:-127.5,div:127.5 ! "
        f"tensor_filter framework=jax model={model_name} name=filter "
        f"{f'mesh={MESH_SPEC} ' if MESH_SPEC else ''}"
        f"inflight={INFLIGHT} ! "
        f"tensor_decoder mode=image_labeling "
        f"{'option2=batched ' if batch > 1 else ''}! "
        # a device→host flush has a fixed cost regardless of size;
        # materialize-host drains in GROUPS (one overlapped flush covers
        # the whole backlog, pipeline/pipeline.py _drain)
        f"queue max-size-buffers={drain_n} materialize-host=true ! "
        "tensor_sink name=sink to-host=true"
    )
    pipe.lanes = LANES
    # saturation-only knob: live runs are paced by the source clock and
    # never shed, so a budget there would only add admission bookkeeping
    if SLO_BUDGET_MS > 0 and not live_fps:
        pipe.slo_budget_ms = SLO_BUDGET_MS
    return pipe


def device_probe(batch: int = BATCH, iters: int = 30) -> dict:
    """Separate the chip from the host↔device link: time the flagship
    model as pure device dispatches (one end sync) and as blocking round
    trips. The gap between ``pipeline fps`` and ``device_fps_ceiling`` is
    framework overhead; the gap between dispatch and roundtrip is the
    link."""
    import jax
    import jax.numpy as jnp

    from nnstreamer_tpu.filters.jax_backend import _registered

    # reuse the flagship's registered model (same weights, no re-init)
    entry = _registered.get(_register_mnv2(batch))
    apply_fn, params = entry["fn"], entry["params"]
    jf = jax.jit(apply_fn)
    params = jax.device_put(params)
    x = jax.device_put(jnp.zeros((batch, IMAGE, IMAGE, 3), jnp.float32))
    np.asarray(jf(params, x))  # compile + warm
    t0 = time.perf_counter()
    outs = [jf(params, x) for _ in range(iters)]
    np.asarray(outs[-1])
    dispatch_ms = (time.perf_counter() - t0) / iters * 1e3
    t0 = time.perf_counter()
    for _ in range(3):
        np.asarray(jf(params, x))
    roundtrip_ms = (time.perf_counter() - t0) / 3 * 1e3
    return dict(
        device_dispatch_ms_per_batch=round(dispatch_ms, 3),
        device_compute_ms_per_frame=round(dispatch_ms / batch, 4),
        device_roundtrip_ms=round(roundtrip_ms, 2),
        device_fps_ceiling=round(batch * 1e3 / dispatch_ms, 1),
    )


#: public bf16 peak TFLOP/s per chip by device kind — the MFU denominator
_TPU_PEAK_BF16 = {
    "v6": 918e12, "v5p": 459e12, "v5e": 197e12, "v5 lite": 197e12,
    "v4": 275e12, "v3": 123e12, "v2": 45e12,
}


def _peak_flops() -> float:
    import jax

    kind = jax.devices()[0].device_kind
    for key, peak in _TPU_PEAK_BF16.items():
        if key in kind.lower():
            return peak
    raise RuntimeError(
        f"bench: device_kind {kind!r} is not in the peaks table "
        f"({', '.join(_TPU_PEAK_BF16)}); add its public bf16 peak rather "
        f"than reporting utilization against nothing")


def _model_flops(batch: int):
    """XLA's own flop count for one flagship invoke (cost analysis on the
    lowered computation — no second compile)."""
    try:
        import jax
        import jax.numpy as jnp

        from nnstreamer_tpu.filters.jax_backend import _registered

        entry = _registered.get(_register_mnv2(batch))
        x = jax.ShapeDtypeStruct((batch, IMAGE, IMAGE, 3), jnp.float32)
        lowered = jax.jit(entry["fn"]).lower(entry["params"], x)
        cost = lowered.cost_analysis()
        if cost is None:  # some backends only report post-compile
            cost = lowered.compile().cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0] if cost else {}
        flops = (cost or {}).get("flops")
        return float(flops) if flops else None
    except Exception as e:  # noqa: BLE001 — MFU is informative only
        print(f"bench: cost analysis unavailable ({e})", file=sys.stderr)
        return None


def ingest_probe(batch: int = BATCH) -> dict:
    """Transfer+framework ceiling measured by the pipeline itself: the
    EXACT flagship topology (same build_pipeline call — source
    synthesis, conversion, aggregation, H2D staging, transform, decoder,
    grouped D2H drain) with only the model swapped for a near-zero-FLOP
    checksum. ``ingest_bound_fps`` is therefore the fps this
    host/link/framework combination could deliver if the model were
    free; ``value/ingest_bound_fps`` close to 1 proves the flagship
    number is transfer/framework-bound, not model- or scheduler-bound.
    (Synthetic serial device_put probes are NOT used: their per-call
    round-trip structure understates what the overlapped pipeline
    achieves.)"""
    # the EXACT flagship topology (build_pipeline), model swapped only.
    # A ceiling estimate must not read LOW on a volatile link (that
    # would put the flagship "above" its own ceiling): take the best of
    # two runs.
    fps = max(ingest_run_once(batch) for _ in range(2))
    return dict(ingest_bound_fps=round(fps, 1))


def _register_ingest_model():
    import jax.numpy as jnp

    from nnstreamer_tpu.filters.jax_backend import (
        is_jax_model_registered,
        register_jax_model,
    )

    if not is_jax_model_registered("bench_ingest_probe"):
        # [B, 16] pseudo-logits so the image_labeling decoder stage runs
        # exactly as in the flagship; compute is a reduction + broadcast
        register_jax_model(
            "bench_ingest_probe",
            lambda x: (jnp.stack(
                [jnp.sum(x, axis=(1, 2, 3)).astype(jnp.float32)] * 16,
                axis=1),),
            None)


def ingest_run_once(batch: int = BATCH) -> float:
    """One ingest-ceiling sample (see :func:`ingest_probe`). Interleaved
    with the flagship repeats so each run can be normalized by the
    link/framework ceiling measured right after it (``value_norm``)."""
    _register_ingest_model()
    pipe = build_pipeline(batch, model_override="bench_ingest_probe")
    return _steady_fps(_collect(pipe), frames_per_buffer=batch)


#: live-run latency budget (ms) for the aggregator's adaptive batching —
#: 50 ms ≈ a 1-2 frame window at 30 fps, chosen so p50 (window wait +
#: dispatch + grouped D2H) lands under ~100 ms on a healthy link while
#: the saturated throughput path still dispatches full batches
LAT_BUDGET_MS = int(os.environ.get("BENCH_LAT_BUDGET_MS", "50"))


def measure_latency_live(batch: int = BATCH, fps: int = 30,
                         seconds: int = 10,
                         budget_ms: int = None) -> dict:
    """Per-frame end-to-end latency under realtime pacing — the
    north-star latency half (BASELINE.md). The saturated throughput runs
    report latency too, but there it is dominated by deep-queue wait (a
    throughput-mode artifact); a 30 fps live source measures the service
    latency a realtime stream actually sees. With the latency budget
    active (default) the aggregator flushes partial padded windows, so
    the admission wait is bounded by the budget instead of the full
    batch window (batch/fps — 267 ms for batch=8 at 30 fps)."""
    if budget_ms is None:
        budget_ms = LAT_BUDGET_MS
    # warm the compile path off the clock — without this, frames queue
    # behind the first dispatch's compile and the percentiles measure the
    # backlog drain
    _collect(build_pipeline(batch, n_frames=2 * batch))
    pipe = build_pipeline(batch, live_fps=fps, n_frames=fps * seconds,
                          latency_budget_ms=budget_ms)
    _collect(pipe)
    # drop the first two batch windows: they carry one-time pipeline
    # warm-up (first dispatch), not steady service
    lat = pipe.get("sink").latency_percentiles(50, 99, skip=2 * batch)
    return dict(latency_p50_ms=round(lat[0], 2) if lat else None,
                latency_p99_ms=round(lat[1], 2) if lat else None,
                latency_budget_ms=budget_ms)


def _ingress_drops(pipe) -> float:
    """Cumulative leaky-ingress drop count for this pipeline's metric
    labels. The obs counter is registry-global and every bench run reuses
    the same {pipeline, element} labels, so callers diff two reads for a
    per-run number."""
    from nnstreamer_tpu.obs import get_registry

    c = get_registry().get("nns_queue_drops_total",
                           pipeline=getattr(pipe, "name", "") or "",
                           element="q_ingress")
    return float(c.value) if c is not None else 0.0


def _sched_counts(pipe) -> dict:
    """Cumulative scheduler + admission counters for this pipeline's
    labels (same diff-two-reads contract as :func:`_ingress_drops` — the
    obs registry is global and repeats reuse the labels)."""
    from nnstreamer_tpu.obs import get_registry

    reg = get_registry()
    name = getattr(pipe, "name", "") or ""

    def val(metric, **labels):
        c = reg.get(metric, **labels)
        return float(c.value) if c is not None else 0.0

    return {
        "rejected": val("nns_sched_rejected_total", pipeline=name),
        "shed": (val("nns_sched_shed_total", pipeline=name, reason="late")
                 + val("nns_sched_shed_total", pipeline=name,
                       reason="capacity")),
        "stamped": val("nns_queue_admitted_total", pipeline=name,
                       element="q_ingress"),
        "revoked": val("nns_queue_admitted_revoked_total", pipeline=name,
                       element="q_ingress"),
    }


def measure_pipeline(batch: int = BATCH) -> dict:
    from nnstreamer_tpu.tensors.buffer import transfer_snapshot

    pipe = build_pipeline(batch)
    drops0 = _ingress_drops(pipe)
    sched0 = _sched_counts(pipe)
    xfer0 = transfer_snapshot()
    frame_t = _collect(pipe)
    xfer1 = transfer_snapshot()
    drops = _ingress_drops(pipe) - drops0
    sched = {k: v - sched0[k] for k, v in _sched_counts(pipe).items()}
    warmup_arrivals = max(1, WARMUP // batch) if batch > 1 else WARMUP
    steady = frame_t[warmup_arrivals:]
    if len(steady) >= 2:
        deltas = np.diff(steady)
        # inter-ARRIVAL of sink buffers (one buffer = `batch` frames);
        # honest name — a frame's true end-to-end latency under
        # micro-batching includes waiting for its batch window, which
        # this does NOT measure
        p50_ms = float(np.percentile(deltas, 50)) * 1e3
        p90_ms = float(np.percentile(deltas, 90)) * 1e3
    elif len(frame_t) >= 2:
        p50_ms = p90_ms = \
            (frame_t[-1] - frame_t[0]) / (len(frame_t) - 1) * 1e3
    else:
        p50_ms = p90_ms = 0.0
    filt = pipe.get("filter")
    sink = pipe.get("sink")
    # served-traffic latency: frames the leaky ingress ADMITTED, measured
    # from the admission stamp. The create-based population still counts
    # the source's free-running pre-admission wait — under saturation
    # that's backlog depth, not pipeline service time (5017 ms observed).
    # Falls back to create-based when no admission stamps arrived.
    lat = sink.latency_percentiles(50, 99, base="admitted") or \
        sink.latency_percentiles(50, 99)
    # invoke tail from the same registry histogram the /metrics endpoint
    # and the post-EOS table read (obs nns_tensor_filter_invoke_seconds);
    # the windowed `latency` property alone hides compile-spike outliers
    inv_p99 = filt._obs_invoke()["invoke"].percentile(99)
    frames = len(frame_t) * batch
    d2h_events = xfer1["d2h_events"] - xfer0["d2h_events"]
    # scheduler-facing accounting over the same first-arrival→EOS window
    # _steady_fps uses: admitted_fps is the SERVED admitted population
    # (stamped frames that reached the sink) per wall second; shed_ratio
    # is the offered traffic the admission point turned away — door
    # rejections plus post-stamp sheds/drops over everything offered.
    eos_t = getattr(frame_t, "eos_t", None)
    span = (((eos_t if eos_t is not None else frame_t[-1]) - frame_t[0])
            if len(frame_t) >= 2 else 0.0)
    fr = getattr(pipe, "_flight", None)
    if fr is not None:
        _LAST_FLIGHT["attribution"] = fr.attribution()
        _LAST_FLIGHT["slo"] = fr.slo_snapshot()
    served_admitted = int(sink.admitted_latencies.count)
    offered = sched["stamped"] + sched["rejected"]
    return dict(fps=_steady_fps(frame_t, frames_per_buffer=batch),
                p50_ms=p50_ms, p90_ms=p90_ms,
                latency_p50_ms=round(lat[0], 2) if lat else None,
                latency_p99_ms=round(lat[1], 2) if lat else None,
                latency_dropped_frames=int(drops),
                admitted_fps=(round(served_admitted / span, 2)
                              if span > 0 and served_admitted else None),
                shed_ratio=(round((sched["rejected"] + sched["revoked"])
                                  / offered, 4) if offered else None),
                sched_rejected=int(sched["rejected"]),
                sched_shed=int(sched["shed"]),
                # explicit host materializations per frame — sink-only
                # materialization in the stock pipeline means one grouped
                # fetch per sink-bound buffer (= 1/batch per frame); 0
                # when the drain-side batched fetch carried every frame
                d2h_per_frame=(round(d2h_events / frames, 4)
                               if frames else None),
                d2h_bytes=int(xfer1["d2h_bytes"] - xfer0["d2h_bytes"]),
                # staged multi-frame window transfers (one device_put /
                # device_get per drained run — tensors/buffer.py): these
                # carried frames with zero per-frame round trips
                h2d_batched=int(xfer1["h2d_batched_events"]
                                - xfer0["h2d_batched_events"]),
                h2d_batched_frames=int(xfer1["h2d_batched_frames"]
                                       - xfer0["h2d_batched_frames"]),
                d2h_batched=int(xfer1["d2h_batched_events"]
                                - xfer0["d2h_batched_events"]),
                invoke_latency_us=filt.get_property("latency"),
                invoke_latency_p99_us=(round(inv_p99 * 1e6, 1)
                                       if inv_p99 is not None else None),
                frames=frames)


def measure_traced(batch: int = BATCH) -> dict:
    """One flagship run with the frame-ledger timeline active
    (obs/timeline.py): returns the run's fps plus the per-stage
    ``stage_breakdown`` and ``variance_report`` aggregations. Kept to a
    single run — the ledger's cost is the thing being measured
    (``trace_overhead_pct``), so it must not contaminate the warm
    repeats above it."""
    from nnstreamer_tpu.obs import timeline as _timeline

    _timeline.activate()
    try:
        run = measure_pipeline(batch)
        tl = _timeline.ACTIVE
        skip = max(1, WARMUP // batch) if batch > 1 else WARMUP
        breakdown = tl.stage_breakdown(skip_frames=skip)
        variance = tl.variance_report(skip_frames=skip)
    finally:
        _timeline.deactivate()
    return dict(fps=run["fps"], breakdown=breakdown, variance=variance)


def _steady_fps(frame_t, frames_per_buffer: int = 1):
    """Sustained fps = frames after the first arrival / (first arrival →
    EOS).

    The first arrival is the warmup anchor (compile + first flush land
    before it); anchoring the window END at EOS (recorded by
    :func:`_collect`) rather than the last arrival keeps the estimate
    honest under bursty arrivals: grouped D2H flushes can deliver a whole
    backlog within milliseconds, and frames/(last−first arrival) would
    then exclude the very processing time being measured."""
    eos_t = getattr(frame_t, "eos_t", None)
    if len(frame_t) < 2:
        print("bench: too few frames for a rate estimate", file=sys.stderr)
        return 0.0
    # anchor at the FIRST arrival (the post-compile instant) and EOS:
    # these bracket all remaining work, so a grouped flush delivering the
    # whole backlog in one burst cannot shrink the measured span
    span = (eos_t if eos_t is not None else frame_t[-1]) - frame_t[0]
    if span <= 0:
        return 0.0
    return (len(frame_t) - 1) * frames_per_buffer / span


class _Arrivals(list):
    """Arrival timestamps + the EOS instant (set by _collect)."""

    eos_t = None


def _collect(pipe, sink_name="sink", timeout=600):
    frame_t = _Arrivals()
    pipe.get(sink_name).connect(lambda b: frame_t.append(time.monotonic()))
    # gc fence around the timed region: collect the inter-run garbage NOW
    # (previous pipeline graphs, drained buffers) and keep the cyclic
    # collector from firing mid-run — observed warm-run spread (1.19)
    # correlates with collector pauses landing inside some windows and
    # not others. Refcount-driven finalizers (pool slab recycling) are
    # unaffected. gc.enable() unconditionally is correct here: the bench
    # process never runs with the collector deliberately off.
    gc.collect()
    gc.disable()
    try:
        msg = pipe.run(timeout=timeout)
    finally:
        gc.enable()
    if msg is None or msg.kind != "eos":
        raise RuntimeError(f"bench pipeline failed: {msg}")
    # end-of-run device fence + per-run interleave guard: EOS drains the
    # dispatch window in order, but trailing async device work may still
    # be retiring; the fence pins eos_t to actual completion (fps spans
    # all work) and guarantees the NEXT interleaved run/probe starts on
    # an idle device instead of inheriting this run's dispatch tail
    _device_fence()
    frame_t.eos_t = time.monotonic()
    return frame_t


def measure_ssd() -> dict:
    """Config #2 (BASELINE.md): SSD-MobileNet + bounding-box decode. The
    whole post-process — anchor decode, sigmoid, per-class NMS — runs inside
    the fused XLA program (decoders/bounding_boxes.py device_kernel)."""
    import jax.numpy as jnp

    from nnstreamer_tpu import parse_launch
    from nnstreamer_tpu.filters.jax_backend import register_jax_model
    from nnstreamer_tpu.models.ssd_mobilenet import ssd_mobilenet

    apply_fn, params, in_info, out_info = ssd_mobilenet(
        image_size=300, batch=1, dtype=jnp.bfloat16)
    register_jax_model("ssd_bench", apply_fn, params,
                       in_info=in_info, out_info=out_info)
    pipe = parse_launch(
        f"videotestsrc num-buffers={N_FRAMES} width=300 height=300 "
        "pattern=gradient ! tensor_converter ! queue max-size-buffers=8 ! "
        "tensor_transform mode=arithmetic "
        "option=typecast:float32,add:-127.5,div:127.5 ! "
        "tensor_filter framework=jax model=ssd_bench name=filter ! "
        "tensor_decoder mode=bounding_boxes option1=mobilenet-ssd "
        "option4=300:300 option7=meta ! "
        "queue max-size-buffers=64 materialize-host=true ! "
        "tensor_sink name=sink to-host=true")
    frame_t = _collect(pipe)
    return dict(metric="ssd_mobilenet_300_pipeline_fps",
                fps=_steady_fps(frame_t), frames=len(frame_t))


def measure_pose_mux() -> dict:
    """Config #3: 4 sources → tensor_mux → ONE batched PoseNet invoke on
    the chip (the reference fans streams out to parallel CPU branches; the
    TPU way is mux → batch dim → single MXU-friendly program)."""
    import jax.numpy as jnp

    from nnstreamer_tpu import parse_launch
    from nnstreamer_tpu.filters.jax_backend import register_jax_model
    from nnstreamer_tpu.models.posenet import posenet

    apply_fn, params, _, _ = posenet(image_size=257, batch=4,
                                     dtype=jnp.bfloat16)

    def batched4(p, a, b, c, d):
        x = jnp.concatenate([a, b, c, d], axis=0).astype(jnp.float32)
        x = (x - 127.5) / 127.5
        heat, offs = apply_fn(p, x)
        return heat, offs

    register_jax_model("pose4_bench", batched4, params)

    def desc(n, live=""):
        srcs = " ".join(
            f"videotestsrc num-buffers={n} width=257 height=257 "
            f"pattern=gradient {live}! tensor_converter ! mux. "
            for _ in range(4))
        return (
            "tensor_mux name=mux sync-mode=slowest ! "
            "tensor_filter framework=jax model=pose4_bench name=filter ! "
            # keypoint decode fuses onto the device: [K,3] rows cross
            # the link, not full heatmaps; completion-proven via the
            # host sink
            "tensor_decoder mode=pose_estimation option2=meta ! "
            "queue max-size-buffers=64 materialize-host=true ! "
            "tensor_sink name=sink to-host=true " + srcs)

    n = max(N_FRAMES // 4, 30)
    pipe = parse_launch(desc(n))
    frame_t = _collect(pipe)
    sat = pipe.get("sink").latency_percentiles(50, 99)
    # realtime-paced latency (the saturated run's latency is deep-queue
    # wait by design): 15 fps per source (60 fps offered across 4) stays
    # under even bad-link capacity so the stat is service latency, not
    # overload queueing. A fresh pipeline re-traces its fused region on
    # the first buffer (~1-2 s) — frames paced in behind it queue up —
    # so run ~8 s and score only the steady second half
    n_srcs, live_n = 4, 120
    live_pipe = parse_launch(desc(live_n,
                                  live="is-live=true framerate=15/1 "))
    _collect(live_pipe)
    lat = live_pipe.get("sink").latency_percentiles(
        50, 99, skip=live_n // 2 * n_srcs)
    return dict(metric="posenet_mux4_batched_fps",
                fps=_steady_fps(frame_t, frames_per_buffer=4),
                latency_p50_ms=round(lat[0], 2) if lat else None,
                latency_p99_ms=round(lat[1], 2) if lat else None,
                latency_sat_p50_ms=round(sat[0], 2) if sat else None,
                latency_sat_p99_ms=round(sat[1], 2) if sat else None,
                frames=len(frame_t) * 4)


def measure_query() -> dict:
    """Config #4: tensor_query offload loopback — client pipeline sends
    frames over the framed-TCP query protocol to a server pipeline running
    the MobileNetV2 filter, results return by client id."""
    import jax.numpy as jnp

    from nnstreamer_tpu import parse_launch
    from nnstreamer_tpu.filters.jax_backend import register_jax_model
    from nnstreamer_tpu.models.mobilenet_v2 import mobilenet_v2

    apply_fn, params, in_info, out_info = mobilenet_v2(
        image_size=IMAGE, batch=1, dtype=jnp.bfloat16)

    def net(p, x):
        xf = (x.astype(jnp.float32) - 127.5) / 127.5
        return apply_fn(p, xf)

    register_jax_model("mnv2_query_bench", net, params)
    server = parse_launch(
        "tensor_query_serversrc name=ssrc port=0 ! "
        "tensor_filter framework=jax model=mnv2_query_bench ! "
        # serversink needs host bytes per result: grouped materialization
        # turns one ~100ms link flush per FRAME into one per backlog
        "queue max-size-buffers=64 materialize-host=true ! "
        "tensor_query_serversink")
    server.start()
    try:
        port = server.get("ssrc").port
        client = parse_launch(
            f"videotestsrc num-buffers={N_FRAMES} width={IMAGE} "
            f"height={IMAGE} pattern=gradient ! tensor_converter ! "
            f"tensor_query_client dest-host=127.0.0.1 dest-port={port} "
            "timeout=120 max-in-flight=16 ! "  # pipelined offload; long
            # timeout covers the first server-side jit compile
            "tensor_sink name=sink to-host=true")
        frame_t = _collect(client)
        lat = client.get("sink").latency_percentiles(50, 99)
    finally:
        server.stop()
    return dict(metric="query_offload_mobilenetv2_fps",
                fps=_steady_fps(frame_t),
                latency_p50_ms=round(lat[0], 2) if lat else None,
                latency_p99_ms=round(lat[1], 2) if lat else None,
                frames=len(frame_t))


def _run_repo_loop(desc_fn, slot: str, n: int, reset=None):
    """Shared completion-proof protocol for tensor_repo loop configs:
    a 2-buffer warm run first (the compile lands there), then the
    measured run, then the final loop state
    materializes INSIDE the timed window — the returned arrivals prove
    the whole dependent chain executed, not just that dispatches were
    enqueued."""
    from nnstreamer_tpu import parse_launch
    from nnstreamer_tpu.elements.repo import GLOBAL_REPO

    if reset is not None:
        reset()
    warm = parse_launch(desc_fn(2))
    warm.run(timeout=300)
    wbuf = GLOBAL_REPO.get(slot, consume=True)
    if wbuf is not None:
        np.asarray(wbuf.tensors[0])
    if reset is not None:
        reset()
    pipe = parse_launch(desc_fn(n))
    frame_t = _collect(pipe)
    final = GLOBAL_REPO.get(slot)
    if final is None:
        raise RuntimeError(
            f"bench: repo slot {slot!r} empty after the run — cannot "
            "prove completion")
    np.asarray(final.tensors[0])
    frame_t.eos_t = time.monotonic()
    return frame_t


def measure_lstm() -> dict:
    """Config #5: tensor_repo recurrence — LSTM state circulates through a
    repo slot as device-resident arrays; one filter invoke per step."""
    import jax.numpy as jnp

    from nnstreamer_tpu import parse_launch
    from nnstreamer_tpu.filters.jax_backend import register_jax_model
    from nnstreamer_tpu.models.lstm import lstm_cell

    hidden = 128
    apply_fn, params, _, _ = lstm_cell(input_dim=hidden, hidden=hidden,
                                       batch=1)

    def step(p, state):
        s = state.reshape(1, 2 * hidden).astype(jnp.float32)
        h, c = s[:, :hidden], s[:, hidden:]
        y, h2, c2 = apply_fn(p, h, h, c)  # self-feeding recurrence
        return jnp.concatenate([h2, c2], axis=1).reshape(2 * hidden)

    register_jax_model("lstm_bench", step, params)

    def loop_desc(num):
        return (f"tensor_reposrc slot=lstm_bench num-buffers={num} "
                f"initial-dim={2 * hidden} initial-type=float32 "
                "initial-value=0.01 timeout=30 ! "
                "tensor_filter framework=jax model=lstm_bench name=filter ! "
                "tee name=t  t. ! tensor_reposink slot=lstm_bench  "
                "t. ! tensor_sink name=sink to-host=false")

    frame_t = _run_repo_loop(loop_desc, "lstm_bench", N_FRAMES)
    return dict(metric="lstm_repo_recurrence_steps_per_s",
                fps=_steady_fps(frame_t), frames=len(frame_t))


def measure_attention() -> dict:
    """Long-context path: Pallas flash attention vs the XLA reference at
    seq 4096 (ops/flash_attention.py; layout [batch, seq, heads, dim])."""
    import time as _t

    import jax
    import jax.numpy as jnp

    from nnstreamer_tpu.ops.flash_attention import flash_attention

    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(0, 1, (1, 4096, 8, 128)), jnp.float32)
    k, v = q + 0.1, q - 0.1

    @jax.jit
    def step(q, k, v):
        # scalar checksum keeps the full attention on the device and lets
        # completion be proven by fetching 4 bytes
        # main() guarantees the TPU, so "pallas" is Mosaic, not interpret
        return jnp.sum(flash_attention(q, k, v, causal=True,
                                       force="pallas"))

    np.asarray(step(q, k, v))
    iters = 20
    t0 = _t.perf_counter()
    outs = [step(q, k, v) for _ in range(iters)]
    for o in outs:
        o.copy_to_host_async()
    for o in outs:
        np.asarray(o)
    dt = (_t.perf_counter() - t0) / iters
    return dict(metric="flash_attention_seq4096_iters_per_s",
                fps=1.0 / dt, frames=iters)


def measure_batch4() -> dict:
    """Micro-batched throughput: tensor_aggregator packs 4 frames into one
    batch-4 invoke (the reference's aggregator micro-batching, SURVEY
    §2.4.3). Same model as the flagship; one dispatch serves 4 frames, so
    per-dispatch overhead amortizes — the TPU-native way to push a
    single stream past the per-call latency floor."""
    import jax.numpy as jnp

    from nnstreamer_tpu import parse_launch
    from nnstreamer_tpu.filters.jax_backend import register_jax_model
    from nnstreamer_tpu.models.mobilenet_v2 import mobilenet_v2

    apply_fn, params, _, _ = mobilenet_v2(
        image_size=IMAGE, batch=4, dtype=jnp.bfloat16)

    def net(p, x):  # [4,H,W,C] uint8 → [4,classes]
        xf = (x.astype(jnp.float32) - 127.5) / 127.5
        return apply_fn(p, xf)

    register_jax_model("mnv2_b4_bench", net, params)
    pipe = parse_launch(
        f"videotestsrc num-buffers={N_FRAMES} width={IMAGE} height={IMAGE} "
        "pattern=gradient ! tensor_converter ! queue max-size-buffers=8 ! "
        "tensor_aggregator frames-in=1 frames-out=4 frames-flush=4 "
        "frames-dim=3 concat=true ! "
        "tensor_filter framework=jax model=mnv2_b4_bench name=filter ! "
        "queue max-size-buffers=64 materialize-host=true ! "
        "tensor_sink name=sink to-host=true")
    frame_t = _collect(pipe)
    return dict(metric="mobilenetv2_224_batch4_fps",
                fps=_steady_fps(frame_t, frames_per_buffer=4),
                frames=len(frame_t) * 4)


def measure_decode() -> dict:
    """LM token streaming: KV-cached transformer decode through the
    tensor_repo loop (examples/llm_stream.py topology). The cache lives in
    HBM as loop state; only token ids circulate host-side. Metric:
    sustained decode steps (tokens) per second."""
    import jax.numpy as jnp

    from nnstreamer_tpu import parse_launch
    from nnstreamer_tpu.elements.repo import GLOBAL_REPO
    from nnstreamer_tpu.filters.jax_backend import register_jax_model
    from nnstreamer_tpu.models.transformer import (
        TransformerConfig,
        build_greedy_stream_step,
        init_cache,
        init_params,
    )
    from nnstreamer_tpu.tensors.buffer import TensorBuffer

    cfg = TransformerConfig(vocab=32000, d_model=512, n_heads=8,
                            n_layers=8, d_ff=2048, max_seq=1024,
                            dtype=jnp.bfloat16)
    params = init_params(cfg)
    # 16 decode steps per invoke (lax.scan inside the program): the token
    # chain is inherently sequential, so the only throughput lever is
    # amortizing per-dispatch overhead across a block — the serving
    # engine's K-step dispatch, repo-loop flavored
    K = 16
    register_jax_model("lm_decode_bench",
                       build_greedy_stream_step(cfg, steps=K), params)
    n = max(1, min(N_FRAMES, 1000) // K)

    def seed():
        # seed with the device-resident cache directly: np.asarray here
        # would bounce ~16 MB through the host just to re-upload on the
        # first invoke
        GLOBAL_REPO.set("lm_bench", TensorBuffer(
            [np.asarray([1], np.int32),
             init_cache(cfg, batch=1),
             np.asarray(0, np.int32)], pts=0))

    def loop_desc(num):
        return (f"tensor_reposrc slot=lm_bench num-buffers={num} "
                "timeout=120 ! "
                "tensor_filter framework=jax model=lm_decode_bench "
                "name=filter input-combination=i0,i1,i2 ! "
                "tee name=t  t. ! tensor_reposink slot=lm_bench  "
                "t. ! tensor_sink name=sink to-host=false")

    frame_t = _run_repo_loop(loop_desc, "lm_bench", n, reset=seed)
    return dict(metric="lm_decode_tokens_per_s_d512_l8_kv1024",
                fps=_steady_fps(frame_t, frames_per_buffer=K),
                frames=len(frame_t) * K)


def _hbm_bandwidth_probe(mb: int = 256, iters: int = 10):
    """Measured HBM read bandwidth (bytes/s): a reduction over a
    device-resident array is memory-bound, so bytes/time is the
    achievable stream rate — the roofline denominator for decode."""
    try:
        import jax
        import jax.numpy as jnp

        from jax import lax

        n = mb * (1 << 20) // 2  # bf16 elements
        passes = 50  # in-program passes amortize the per-dispatch RPC
        x = jax.device_put(jnp.ones((n,), jnp.bfloat16))

        @jax.jit
        def f(a):
            # each pass re-reads the full array: the elementwise max
            # against the evolving accumulator cannot be hoisted or
            # factored out of the reduction, and max+reduce fuse, so the
            # loop body is a pure streaming read
            return lax.fori_loop(
                0, passes,
                lambda i, acc: acc + jnp.sum(jnp.maximum(
                    a, acc.astype(jnp.bfloat16)).astype(jnp.float32)),
                jnp.float32(0.0))

        np.asarray(f(x))  # compile + warm
        t0 = time.perf_counter()
        outs = [f(x) for _ in range(iters)]
        np.asarray(outs[-1])
        dt = time.perf_counter() - t0
        return 2.0 * n * passes * iters / dt
    except Exception as e:  # noqa: BLE001 — roofline is informative
        print(f"bench: hbm probe failed ({e})", file=sys.stderr)
        return None


def measure_serve() -> dict:
    """Continuous-batching serving: 8 concurrent streams share one batched
    KV-cached decode program (serving/engine.py). Metric: aggregate
    generated tokens/s across streams — the serving-throughput counterpart
    of the single-stream ``decode`` config."""
    import time as _t

    import jax.numpy as jnp

    from nnstreamer_tpu.models.transformer import (
        TransformerConfig,
        init_params,
    )
    from nnstreamer_tpu.serving import ContinuousBatchingEngine

    cfg = TransformerConfig(vocab=32000, d_model=512, n_heads=8, n_layers=8,
                            d_ff=2048, max_seq=512, dtype=jnp.bfloat16)
    # steps_per_dispatch="auto": the engine measures the link RTT and
    # per-step decode time at start() and sizes K so the per-dispatch
    # sync amortizes (engine._calibrate_k); these length-bound greedy
    # streams never waste steps on early EOS
    serve_params = init_params(cfg)
    engine = ContinuousBatchingEngine(
        cfg, serve_params, max_streams=8, steps_per_dispatch="auto",
        temperature=0.0).start()
    try:
        rng = np.random.default_rng(0)
        prompts = [rng.integers(1, cfg.vocab, n).tolist()
                   for n in (8, 17, 33, 12, 25, 9, 40, 14, 21, 30, 11, 19)]
        # warm the compile caches off the clock: the dispatch program plus
        # ONE prefill per padding bucket the prompt set will hit (16/32/64)
        for warm_len in (8, 17, 33):
            engine.generate(rng.integers(1, cfg.vocab, warm_len).tolist(),
                            max_new_tokens=engine.K, timeout=600)
        t0 = _t.monotonic()
        streams = [engine.submit(p, max_new_tokens=128) for p in prompts]
        total = sum(len(s.result(timeout=600)) for s in streams)
        dt = _t.monotonic() - t0
    finally:
        engine.stop()
    tps = total / dt

    # ---- roofline: the decode ceiling this config could ever reach ----
    # every decode step streams all params plus the full static KV cache
    # from HBM and yields max_streams tokens, so
    #   bytes/token = (params_bytes + cache_bytes) / max_streams
    # and tokens_per_s_ceiling = measured HBM bandwidth / bytes_per_token
    # (jax-ml.github.io/scaling-book's bandwidth-bound decode recipe)
    import jax

    from nnstreamer_tpu.models.transformer import init_cache

    # bytes from the ACTUAL leaf dtypes (init_params stores f32 master
    # weights; assuming cfg.dtype here would halve params_bytes and
    # inflate the ceiling)
    param_leaves = jax.tree_util.tree_leaves(
        jax.eval_shape(lambda: init_params(cfg)))
    n_params = sum(int(np.prod(v.shape)) for v in param_leaves)
    params_bytes = sum(int(np.prod(v.shape)) * v.dtype.itemsize
                       for v in param_leaves)
    cache_bytes = sum(
        int(np.prod(a.shape)) * a.dtype.itemsize
        for a in jax.tree_util.tree_leaves(
            jax.eval_shape(lambda: init_cache(cfg, batch=8))))
    bytes_per_token = (params_bytes + cache_bytes) / 8
    bw = _hbm_bandwidth_probe()
    peak = _peak_flops()
    ceiling = bw / bytes_per_token if bw else None

    # ---- prefill throughput (flash-attention path, VERDICT r4 #4) ----
    # full-length prompts through the engine's own prefill program
    # (attention="auto" → Pallas flash kernel on TPU for these tileable
    # [4, 512] shapes); tokens/s over the O(s²) prompt pass
    from nnstreamer_tpu.models.transformer import build_prefill
    from nnstreamer_tpu.ops import flash_attention as _flash

    pf = jax.jit(build_prefill(cfg, cfg.max_seq, attention_fn=_flash))
    pparams = jax.device_put(serve_params)
    ptoks = jnp.asarray(
        np.random.default_rng(1).integers(1, cfg.vocab, (4, cfg.max_seq)),
        jnp.int32)
    jax.block_until_ready(pf(pparams, ptoks))  # compile+warm off clock
    samples = []
    for _ in range(3):
        t0 = _t.monotonic()
        jax.block_until_ready(pf(pparams, ptoks))
        samples.append(ptoks.size / (_t.monotonic() - t0))
    prefill_tok_s = sorted(samples)[1]

    return dict(metric="serving_aggregate_tokens_per_s_d512_l8_x8streams",
                fps=tps, frames=total,
                prefill_tok_s=round(prefill_tok_s, 1),
                hbm_bandwidth_gbps=round(bw / 1e9, 1) if bw else None,
                model_mbytes=round(params_bytes / 1e6, 1),
                kv_cache_mbytes=round(cache_bytes / 1e6, 1),
                tokens_per_s_ceiling=round(ceiling, 1) if ceiling else None,
                vs_ceiling=round(tps / ceiling, 4) if ceiling else None,
                mfu_serve=round(tps * 2 * n_params / peak, 5))


def measure_spec() -> dict:
    """Speculative decoding: same target model as the ``decode`` config
    (d512 l8) with a depth-pruned self-speculative draft (first 2 of 8
    layers, shared embedding), γ=4, 8 rounds fused per dispatch — tokens/s
    should beat plain single-token decode by roughly the mean acceptance
    length (models/speculative.py)."""
    import time as _t

    import jax.numpy as jnp

    from nnstreamer_tpu.models.speculative import (
        SpeculativeDecoder,
        draft_from_target,
    )
    from nnstreamer_tpu.models.transformer import (
        TransformerConfig,
        init_params,
    )

    target = TransformerConfig(vocab=32000, d_model=512, n_heads=8,
                               n_layers=8, d_ff=2048, max_seq=1024,
                               dtype=jnp.bfloat16)
    params = init_params(target, seed=0)
    # damp layer outputs → a LOW-ENTROPY model (random-init argmax over a
    # 32k vocab is chaotic; trained LMs are locally predictable, which is
    # the regime speculation exists for). mean_accepted ≈ 4.7 here —
    # printed below so the regime is visible next to the number.
    params = {**params, "proj": params["proj"] * 0.3,
              "w_out": params["w_out"] * 0.3}
    draft, draft_params = draft_from_target(target, params, 2)
    dec = SpeculativeDecoder(target, params, draft, draft_params, gamma=4)
    prompt = np.random.default_rng(0).integers(1, 32000, 32).tolist()
    n = min(N_FRAMES, 800)
    dec.generate(prompt, max_new_tokens=n, fused=True)  # compile off clock
    dec.stats.update(rounds=0, tokens=0, dispatches=0)  # report timed run
    t0 = _t.monotonic()
    out = dec.generate(prompt, max_new_tokens=n, fused=True)
    dt = _t.monotonic() - t0
    print(f"bench spec: mean_accepted={dec.mean_accepted:.2f} "
          f"rounds={dec.stats['rounds']}", file=sys.stderr)
    return dict(metric="speculative_decode_tokens_per_s_d512_l8_g4",
                fps=len(out) / dt, frames=len(out))


def measure_fleet() -> dict:
    """Replicated-fleet scaling (``BENCH_FLEET=N``): N echo replicas
    (serving/fleet.py, CPU-bound ``--spin-ms`` service so added
    replicas buy real process parallelism) behind one discovery
    operation, fronted by a single ``balance=shortest-slack`` client.
    The run measures admitted fps at every fleet size 1..N in one
    session on one machine; ``fleet_scaling`` =
    fps_N / (N * fps_1) is the near-linear-throughput score gated by
    ``BENCH_GATE_FLEET_SCALING_MIN`` (CI: 0.75 at N=3 on loopback
    CPU)."""
    import time as _t

    from nnstreamer_tpu.registry import ELEMENT, get_subplugin
    from nnstreamer_tpu.serving.fleet import FleetLauncher
    from nnstreamer_tpu.tensors.buffer import TensorBuffer

    n = max(1, int(os.environ.get("BENCH_FLEET", "3") or 3))
    spin_ms = float(os.environ.get("BENCH_FLEET_SPIN_MS", "20"))
    frames = int(os.environ.get("BENCH_FLEET_FRAMES", "120"))
    warmup = 8

    def run_once(k: int) -> float:
        fleet = FleetLauncher(replicas=k, operation=f"bench-fleet{k}",
                              spin_ms=spin_ms).start()
        try:
            eps = fleet.endpoints(timeout=30.0)
            if len(eps) < k:
                raise RuntimeError(
                    f"fleet of {k} never fully advertised ({eps})")
            Client = get_subplugin(ELEMENT, "tensor_query_client")
            cl = Client(operation=f"bench-fleet{k}",
                        broker_port=fleet.broker_port, reliable=True,
                        balance="shortest-slack",
                        max_in_flight=4 * k, timeout=10.0)
            outs = []
            cl.srcpad.push = lambda b: outs.append(b)
            try:
                for i in range(warmup):  # connects + RTT priming
                    cl.chain(cl.sinkpad, TensorBuffer(
                        [np.full((4,), i, dtype=np.float32)], pts=i))
                t0 = _t.monotonic()
                for i in range(warmup, warmup + frames):
                    cl.chain(cl.sinkpad, TensorBuffer(
                        [np.full((4,), i, dtype=np.float32)], pts=i))
                cl.handle_eos()
                dt = _t.monotonic() - t0
            finally:
                cl.stop()
            if len(outs) != warmup + frames:
                raise RuntimeError(
                    f"fleet of {k} lost frames: {len(outs)} of "
                    f"{warmup + frames}")
            return frames / dt
        finally:
            fleet.stop()

    fps = [run_once(k) for k in range(1, n + 1)]
    scaling = fps[-1] / (n * fps[0]) if n > 1 and fps[0] else 1.0
    gate_min = float(
        os.environ.get("BENCH_GATE_FLEET_SCALING_MIN", "0") or 0)
    gates = {
        "fleet_scaling": {
            "value": round(scaling, 3),
            "min": gate_min or None,
            "ok": not gate_min or scaling >= gate_min,
        },
    }
    gates["ok"] = gates["fleet_scaling"]["ok"]
    return dict(metric="fleet_admitted_fps", fps=fps[-1], frames=frames,
                fleet_replicas=n,
                fleet_admitted_fps=[round(f, 1) for f in fps],
                fleet_scaling=round(scaling, 3),
                fleet_spin_ms=spin_ms, gates=gates)


EXTRA_CONFIGS = {
    "ssd": measure_ssd,
    "pose4": measure_pose_mux,
    "query": measure_query,
    "lstm": measure_lstm,
    "attn": measure_attention,
    "batch4": measure_batch4,
    "decode": measure_decode,
    "serve": measure_serve,
    "spec": measure_spec,
    "fleet": measure_fleet,
}


def _require_tpu() -> None:
    """A measurement path that finds no chip FAILS — it never falls back
    to the CPU, whose numbers must not appear under a device metric."""
    import jax

    backend = jax.default_backend()
    if backend != "tpu":
        sys.exit(f"bench: needs a TPU; JAX initialized backend "
                 f"{backend!r} ({jax.devices()[0].device_kind}). Nothing "
                 f"was measured.")
    _peak_flops()  # an unknown device_kind is an error, up front


def main():
    _require_tpu()
    # persistent XLA compile cache, placed by the one rule in
    # pipeline/continuity.py (JAX_COMPILATION_CACHE_DIR, else
    # <checkout>/.jax_cache); its hit/miss counters feed the footer
    from nnstreamer_tpu.pipeline.continuity import arm_compile_cache

    arm_compile_cache()

    # secondary configs (BASELINE.md #2-#5): `python bench.py ssd|pose4|
    # query|lstm` or BENCH_CONFIG env. Default (driver contract): flagship
    # MobileNetV2 pipeline, ONE JSON line.
    config = (sys.argv[1] if len(sys.argv) > 1 else
              os.environ.get("BENCH_CONFIG", "")).strip()
    if not config and os.environ.get("BENCH_FLEET", "").strip():
        config = "fleet"  # BENCH_FLEET=N — replicated-fleet scaling
    if config and config != "mobilenet":
        def _emit(r):
            extra = {k: v for k, v in r.items()
                     if k not in ("metric", "fps", "frames") and
                     v is not None}
            print(json.dumps({"metric": r["metric"],
                              "value": round(r["fps"], 2),
                              "unit": "fps", "frames": r["frames"],
                              **extra, "platform": _platform()}))

        if config == "all":
            for name, fn in EXTRA_CONFIGS.items():
                _emit(fn())
            return
        if config not in EXTRA_CONFIGS:
            print(f"bench: unknown config {config!r} "
                  f"(choose from {', '.join(EXTRA_CONFIGS)})",
                  file=sys.stderr)
            sys.exit(2)
        r = EXTRA_CONFIGS[config]()
        _emit(r)
        g = r.get("gates")
        if ENFORCE_GATES and isinstance(g, dict) and not g.get("ok", True):
            sys.exit(1)
        return

    # fixed-length warmup drain (WARMUP_DRAIN buffers): compile,
    # fused-region trace and pool/lane-arena priming all land here, off
    # the clock, so the repeat loop below measures only steady state.
    # fps_cold still reports run 1 separately.
    _collect(build_pipeline(BATCH, n_frames=WARMUP_DRAIN * BATCH))
    # each flagship run is paired with an ingest-ceiling sample taken
    # right after it: norm_runs = fps/ceiling (see the "link-normalized"
    # note in the module docstring)
    runs, ingest_seq = [], []
    for _ in range(max(1, REPEATS)):
        runs.append(measure_pipeline())
        ingest_seq.append(ingest_run_once())
    # one traced run adjacent to the repeats (same session, never
    # counted among them): its ledger produces the report's
    # stage_breakdown, and its fps against the untraced warm median is
    # the measured cost of tracing (trace_overhead_pct)
    traced = measure_traced()
    fps_seq = [round(r["fps"], 2) for r in runs]  # chronological
    norm_seq = [round(r["fps"] / i, 3) if i else None
                for r, i in zip(runs, ingest_seq)]
    # warm/cold split: the first run pays compile warm-up and is
    # reported separately as fps_cold; the headline value is the
    # steady-state (warm) median so one cold run cannot drag it
    warm = runs[1:] if len(runs) > 1 else runs
    warm_sorted = sorted(warm, key=lambda r: r["fps"])
    # lower-middle run: the median for odd counts, the conservative
    # middle (never the best run) for even
    stats = warm_sorted[(len(warm_sorted) - 1) // 2]
    warm_fps = [round(r["fps"], 2) for r in warm_sorted]
    spread = ((warm_fps[-1] - warm_fps[0]) / stats["fps"]
              if stats["fps"] else 0.0)
    # robust spread companions (used by the perf gates): fps_median is
    # the true median of the warm runs (interpolated for even counts —
    # `value` stays the conservative lower-middle RUN so the headline
    # keeps its full stats row), and spread_mad is the median absolute
    # deviation over the median — one wild warm run moves the max-min
    # spread_warm by its full excursion but barely dents the MAD
    fps_median = float(np.median([r["fps"] for r in warm]))
    mad = float(np.median([abs(r["fps"] - fps_median) for r in warm]))
    spread_mad = round(mad / fps_median, 3) if fps_median else 0.0
    # link-normalized score: median of the warm per-run fps/ceiling
    # ratios (each ratio uses the ingest sample adjacent to its run)
    warm_norm = sorted(n for n in norm_seq[1:] or norm_seq if n)
    value_norm = warm_norm[(len(warm_norm) - 1) // 2] if warm_norm else None
    spread_norm = (round((warm_norm[-1] - warm_norm[0]) / value_norm, 3)
                   if value_norm else None)
    # probe AFTER the repeats: device_roundtrip_ms / device_fps_ceiling
    # are recomputed right after the runs they are compared with,
    # so pipeline_efficiency compares like with like (with residency on,
    # the pipeline no longer pays that roundtrip per frame — the probe
    # keeps the link number honest rather than inherited from a colder
    # pre-run measurement)
    probe = device_probe()
    # the r01/r02-comparable single-frame pipeline rides along as a
    # secondary (median of 3): it shows the per-dispatch floor the
    # micro-batched flagship amortizes away
    single = sorted(measure_pipeline(batch=1)["fps"] for _ in range(3))[1]
    baseline = FALLBACK_BASELINE_FPS
    flops = _model_flops(BATCH)
    peak = _peak_flops()
    # the ceiling for vs_ingest_bound must not read LOW on a volatile
    # link: best sample across the interleaved probes
    ingest = {"ingest_bound_fps": round(max(ingest_seq), 1)
              if any(ingest_seq) else None}
    lat_live = measure_latency_live()
    mesh_fields = _measure_mesh_fields(fps_median, runs)
    result = {
        "metric": "mobilenetv2_224_pipeline_fps",
        "value": round(stats["fps"], 2),
        "unit": "fps",
        "vs_baseline": round(stats["fps"] / baseline, 3),
        "batch": BATCH,
        "inflight": INFLIGHT,
        "lanes": _effective_lanes(),
        "pool_hit_rate": _pool_hit_rate(),
        # end-to-end per-frame latency under 30 fps realtime pacing (the
        # north-star latency); the *_sat_* fields are the same measurement
        # inside the saturated throughput runs, where deep-queue wait
        # dominates by design
        **lat_live,
        # *_sat_* now reports the ADMITTED population (frames the leaky
        # ingress accepted, measured from the admission stamp) — service
        # latency of delivered traffic; the frames the queue shed instead
        # are counted separately
        "latency_sat_p50_ms": stats["latency_p50_ms"],
        "latency_sat_p99_ms": stats["latency_p99_ms"],
        "latency_dropped_frames": stats["latency_dropped_frames"],
        # SLO scheduler (BENCH_SLO_BUDGET_MS > 0): throughput of the
        # SERVED admitted population and the share of offered traffic
        # the admission point turned away (door rejections + sheds).
        # Without a budget shed_ratio still reports the leaky ingress's
        # blind tail-drop ratio under saturation.
        "slo_budget_ms": SLO_BUDGET_MS if SLO_BUDGET_MS > 0 else None,
        "admitted_fps": stats["admitted_fps"],
        "shed_ratio": stats["shed_ratio"],
        # residency: explicit D2H materializations per frame (sink-only
        # materialization ⇒ 1/batch) and the session-wide share of
        # DeviceBuffer pad crossings that stayed resident
        "d2h_per_frame": stats["d2h_per_frame"],
        "resident_ratio": _resident_ratio(),
        # staged multi-frame transfer batching: window uploads / grouped
        # fetches the headline run used, and the frames they carried
        "h2d_batched_uploads": stats["h2d_batched"],
        "h2d_batched_frames": stats["h2d_batched_frames"],
        "d2h_batched_fetches": stats["d2h_batched"],
        "p50_interarrival_ms": round(stats["p50_ms"], 3),
        "invoke_latency_us": stats["invoke_latency_us"],
        "frames": stats["frames"],
        "fps_cold": fps_seq[0],
        "fps_runs": fps_seq,
        "fps_median": round(fps_median, 2),
        "spread_warm": round(spread, 3),
        "spread_mad": spread_mad,
        # link-normalized: fps over the ingest ceiling sampled right
        # after each run
        "value_norm": value_norm,
        "norm_runs": norm_seq,
        "spread_norm": spread_norm,
        "single_frame_fps": round(single, 2),
        # frame-ledger report (obs/timeline.py, one traced run): mean
        # per-frame ms by stage — reconciliation ~1.0 means the stages
        # tile the frame's whole e2e life; trace_overhead_pct is the
        # traced run's fps deficit vs the untraced warm median (negative
        # = run-to-run spread, not a speedup)
        "stage_breakdown": traced["breakdown"],
        "trace_dominant_stage": traced["variance"]["dominant_stage"],
        "trace_overhead_pct": (
            round((1 - traced["fps"] / fps_median) * 100, 2)
            if fps_median and traced["fps"] else None),
        **probe,
        **ingest,
        # gated statistic: the MEDIAN-of-k warm fps over the same-window
        # ceiling — a single lucky (or unlucky) run cannot move a perf
        # gate built on this the way the lower-middle `value` run could
        "pipeline_efficiency": round(
            fps_median / probe["device_fps_ceiling"], 3)
        if probe["device_fps_ceiling"] and fps_median else None,
        # ≥0.7 means the wall number IS the transfer link's ceiling —
        # the pipeline itself is not the limiter (see ingest_probe)
        "vs_ingest_bound": round(
            stats["fps"] / ingest["ingest_bound_fps"], 3)
        if ingest.get("ingest_bound_fps") else None,
        "model_gflops_per_frame": round(flops / BATCH / 1e9, 3)
        if flops else None,
        # MFU at the pipeline level (delivered frames × model flops over
        # peak) and at the dispatch level (what the chip sustains on the
        # model alone — the gap between the two is framework + link)
        "mfu_pipeline": round(stats["fps"] * flops / BATCH / peak, 4)
        if flops else None,
        "mfu_dispatch": round(
            flops / (probe["device_dispatch_ms_per_batch"] / 1e3) / peak, 4)
        if flops and probe["device_dispatch_ms_per_batch"]
        else None,
        "baseline_fps": baseline,
        # mesh-sharded serving (BENCH_MESH=dp8): spec, warm median over
        # the single-device reference, resharded bytes per measured
        # frame (0 = every boundary hand-off was a matched zero-copy)
        **mesh_fields,
        "platform": _platform(),
    }
    # flight recorder (obs/flight.py): the always-on attribution from
    # the last UNtraced measured run — unlike trace_dominant_stage it
    # costs no dedicated run and reflects the gated repeats themselves
    fa = _LAST_FLIGHT.get("attribution")
    result["flight_dominant_stage"] = (fa or {}).get("dominant_stage")
    result["flight_dominant_share"] = (fa or {}).get("dominant_share")
    result["gates"] = gates = _perf_gates(
        fps_median=fps_median, spread_mad=spread_mad,
        sat_p99_ms=stats["latency_p99_ms"])
    print(json.dumps(result))
    if ENFORCE_GATES and not gates["ok"]:
        sys.exit(1)


def _perf_gates(fps_median, spread_mad, sat_p99_ms) -> dict:
    """Judge the run against the determinism gates: the headline median
    AND the two tail statistics (warm spread as MAD/median, saturation
    p99 of the admitted population). A threshold of 0/None means that
    gate is unarmed and passes."""
    gates = {
        "fps_median": {
            "value": round(fps_median, 2),
            "min": GATE_FPS_MEDIAN_MIN or None,
            "ok": (not GATE_FPS_MEDIAN_MIN
                   or fps_median >= GATE_FPS_MEDIAN_MIN),
        },
        "spread_mad": {
            "value": spread_mad,
            "max": GATE_SPREAD_MAD_MAX or None,
            "ok": (not GATE_SPREAD_MAD_MAX
                   or spread_mad <= GATE_SPREAD_MAD_MAX),
        },
        "latency_sat_p99_ms": {
            "value": sat_p99_ms,
            "max": GATE_SAT_P99_MS_MAX or None,
            "ok": (not GATE_SAT_P99_MS_MAX or sat_p99_ms is None
                   or sat_p99_ms <= GATE_SAT_P99_MS_MAX),
        },
    }
    gates["ok"] = all(g["ok"] for g in gates.values()
                      if isinstance(g, dict))
    return gates


def _resident_ratio():
    """Session-wide nns_buffer_resident_ratio (tensors/buffer.py); None
    when no DeviceBuffer ever crossed a pad (NNSTPU_RESIDENT=0)."""
    try:
        from nnstreamer_tpu.tensors.buffer import resident_ratio

        r = resident_ratio()
        return None if r is None else round(r, 3)
    except Exception:  # noqa: BLE001 — informative field only
        return None


def _effective_lanes() -> int:
    """The lane count the runs actually used (NNSTPU_LANES overrides
    BENCH_LANES — pipeline/lanes.py)."""
    try:
        from nnstreamer_tpu.pipeline.lanes import effective_lanes

        return effective_lanes(LANES)
    except Exception:  # noqa: BLE001 — informative field only
        return LANES


def _pool_hit_rate():
    """Cumulative ingest-pool hit rate across the session's runs
    (tensors/pool.py); None when the pool saw no traffic or is disabled
    via NNSTPU_POOL=0."""
    try:
        from nnstreamer_tpu.tensors.pool import get_pool, pool_enabled

        if not pool_enabled():
            return None
        snap = get_pool().snapshot()
        if not (snap["hits"] or snap["misses"]):
            return None
        return round(snap["hit_rate"], 3)
    except Exception:  # noqa: BLE001 — informative field only
        return None


def _measure_mesh_fields(fps_median, runs) -> dict:
    """Mesh-sharded run report (BENCH_MESH=dp8): the spec, the warm
    median over a single-device reference run taken in the same
    session with the kill switch thrown (NNSTPU_MESH=0 is the
    byte-identical dp1 path, so the ratio isolates the mesh), and the
    session's resharded bytes per measured frame — 0 when every
    device-passthrough hand-off between sharded regions was a matched
    zero-copy. All three are null without BENCH_MESH."""
    if not MESH_SPEC:
        return {"mesh": None, "shard_scaling": None,
                "reshard_bytes_per_frame": None}
    from nnstreamer_tpu.parallel import serve as _serve

    frames = sum(int(r.get("frames") or 0) for r in runs)
    per_frame = (round(_serve.reshard_bytes_total() / frames, 1)
                 if frames else None)
    prev = os.environ.get("NNSTPU_MESH")
    os.environ["NNSTPU_MESH"] = "0"
    try:
        # the reference pays its own compile off the clock, like the
        # flagship's warmup drain, so the ratio compares steady states
        _collect(build_pipeline(BATCH, n_frames=WARMUP_DRAIN * BATCH))
        ref_fps = measure_pipeline()["fps"]
    finally:
        if prev is None:
            os.environ.pop("NNSTPU_MESH", None)
        else:
            os.environ["NNSTPU_MESH"] = prev
    return {"mesh": MESH_SPEC,
            "shard_scaling": (round(fps_median / ref_fps, 3)
                              if ref_fps and fps_median else None),
            "reshard_bytes_per_frame": per_frame}


def _platform() -> str:
    import jax

    return str(jax.devices()[0].platform)


if __name__ == "__main__":
    main()
