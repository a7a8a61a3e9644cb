"""MobileNetV2 classification — the flagship fused pipeline.

uint8 frame → normalize → MobileNet → argmax runs as ONE XLA program;
only the label index/score cross back per frame."""

import nnstreamer_tpu as nt
from nnstreamer_tpu.filters.jax_backend import register_jax_model
from nnstreamer_tpu.models.mobilenet_v2 import mobilenet_v2

apply_fn, params, in_info, out_info = mobilenet_v2(image_size=224)
register_jax_model("mnv2", apply_fn, params, in_info=in_info,
                   out_info=out_info)

pipe = nt.parse_launch(
    "videotestsrc num-buffers=30 width=224 height=224 pattern=gradient ! "
    "tensor_converter ! queue max-size-buffers=8 ! "
    "tensor_transform mode=arithmetic "
    "option=typecast:float32,add:-127.5,div:127.5 ! "
    "tensor_filter framework=jax model=mnv2 name=net ! "
    "tensor_decoder mode=image_labeling ! "
    "queue max-size-buffers=32 prefetch-host=true ! "
    "tensor_sink name=out to-host=true")
pipe.get("out").connect(
    lambda buf: print(f"label={buf.meta['label']} "
                      f"score={buf.meta['score']:.3f}"))
msg = pipe.run(timeout=300)
print(f"done: {msg.kind}; invoke latency "
      f"{pipe.get('net').get_property('latency')} us")
