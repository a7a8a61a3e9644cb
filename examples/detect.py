"""SSD-MobileNet detection — anchor decode + per-class NMS fused on device;
only [100, 6] box rows leave the chip per frame."""

import nnstreamer_tpu as nt
from nnstreamer_tpu.filters.jax_backend import register_jax_model
from nnstreamer_tpu.models.ssd_mobilenet import ssd_mobilenet

apply_fn, params, in_info, out_info = ssd_mobilenet(image_size=300)
register_jax_model("ssd", apply_fn, params, in_info=in_info,
                   out_info=out_info)

pipe = nt.parse_launch(
    "videotestsrc num-buffers=10 width=300 height=300 pattern=gradient ! "
    "tensor_converter ! queue max-size-buffers=8 ! "
    "tensor_transform mode=arithmetic "
    "option=typecast:float32,add:-127.5,div:127.5 ! "
    "tensor_filter framework=jax model=ssd ! "
    "tensor_decoder mode=bounding_boxes option1=mobilenet-ssd "
    "option4=300:300 option7=meta ! "
    "queue max-size-buffers=16 prefetch-host=true ! "
    "tensor_sink name=out to-host=true")
pipe.get("out").connect(
    lambda buf: print(f"{len(buf.meta['detections'])} detections:",
                      [(d['class'], round(d['score'], 2))
                       for d in buf.meta['detections'][:5]]))
print("run:", pipe.run(timeout=300).kind)
