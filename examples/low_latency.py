"""Latency-budget adaptive batching — bound a live stream's per-frame
latency while keeping the batched MXU dispatch.

A micro-batched pipeline (aggregator frames-out=8) makes a 30 fps
frame wait up to 267 ms for its batch window. `latency-budget-ms=50`
flushes a partial window once its oldest frame has waited 50 ms —
padded ON DEVICE to the compiled batch shape (`pad-device=true`, so
only real frames cross the host→device link) and trimmed back at the
sink. Under overload the budget yields to backpressure and the
pipeline degrades to plain batching instead of compounding a backlog.
"""

import jax.numpy as jnp

import nnstreamer_tpu as nt
from nnstreamer_tpu.filters.jax_backend import register_jax_model


def classify(x):  # [8, 64, 64, 3] → [8, 10] pseudo-logits
    xf = (x.astype(jnp.float32) - 127.5) / 127.5
    return (jnp.stack([jnp.sum(xf, axis=(1, 2, 3))] * 10, axis=1),)


register_jax_model("demo_classify8", classify, None)

pipe = nt.parse_launch(
    "videotestsrc num-buffers=90 is-live=true framerate=30/1 "
    "width=64 height=64 pattern=gradient ! tensor_converter ! "
    "tensor_aggregator frames-in=1 frames-out=8 frames-flush=8 "
    "frames-dim=3 concat=true latency-budget-ms=50 pad-device=true ! "
    "queue max-size-buffers=4 prefetch-device=true ! "
    "tensor_filter framework=jax model=demo_classify8 ! "
    "queue max-size-buffers=4 materialize-host=true ! "
    "tensor_sink name=out to-host=true")
msg = pipe.run(timeout=60)
assert msg is not None and msg.kind == "eos", msg

sink = pipe.get("out")
frames = sum(
    b.meta.get("valid_frames", b.tensors[0].shape[0]) for b in sink.buffers)
lat = sink.latency_percentiles(50, 99, skip=16)
print(f"{len(sink.buffers)} dispatches carried {frames} frames")
if lat:
    print(f"end-to-end latency p50={lat[0]:.1f} ms p99={lat[1]:.1f} ms "
          f"(full batch window would be ~267 ms at 30 fps)")
