"""Semantic segmentation pipeline — per-pixel argmax on device.

The fused region runs normalize → encoder-decoder FCN → argmax as one
XLA program; an [H, W] int32 class map crosses to the host (C× less D2H
than raw logits), where the image_segment decoder colors it RGBA.

Run: PYTHONPATH=.. python segment.py
(JAX picks the backend: the TPU where there is one; JAX_PLATFORMS=cpu
forces CPU XLA.)
"""

import jax.numpy as jnp
import numpy as np

import nnstreamer_tpu as nt
from nnstreamer_tpu.filters.jax_backend import register_jax_model
from nnstreamer_tpu.models.segmenter import segmenter

SIZE = 256
apply_fn, params, in_info, out_info = segmenter(num_classes=21,
                                                image_size=SIZE)


def net(p, x):
    return apply_fn(p, (x.astype(jnp.float32) - 127.5) / 127.5)


register_jax_model("seg", net, params)

pipe = nt.parse_launch(
    f"videotestsrc num-buffers=30 width={SIZE} height={SIZE} "
    "pattern=smpte ! tensor_converter ! queue max-size-buffers=8 ! "
    "tensor_filter framework=jax model=seg name=net ! "
    "tensor_decoder mode=image_segment ! "
    "queue max-size-buffers=32 prefetch-host=true ! "
    "tensor_sink name=out to-host=true")
pipe.get("out").connect(
    lambda buf: print(
        f"frame pts={buf.pts}: classes present="
        f"{sorted(np.unique(buf.meta['segment_labels']).tolist())}"))
msg = pipe.run(timeout=300)
print(f"done: {msg.kind}; invoke latency "
      f"{pipe.get('net').get_property('latency')} us")
