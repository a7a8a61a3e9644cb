"""Audio keyword-spotting pipeline: audiotestsrc → window → classify.

The audio peer of classify.py — the same converter/filter/decoder
contract over an audio stream (reference: tensor_converter audio path +
aggregator windowing).

Run: PYTHONPATH=.. python audio.py
(JAX picks the backend: the TPU where there is one; JAX_PLATFORMS=cpu
forces CPU XLA.)
"""

import nnstreamer_tpu as nt
from nnstreamer_tpu.filters.jax_backend import register_jax_model
from nnstreamer_tpu.models.audio_classifier import audio_classifier

SAMPLES = 8000  # 0.5 s window @ 16 kHz

apply_fn, params, in_info, out_info = audio_classifier(
    samples=SAMPLES, num_classes=12)
register_jax_model("kws", apply_fn, params,
                   in_info=in_info, out_info=out_info)

pipe = nt.parse_launch(
    f"audiotestsrc num-buffers=8 samplesperbuffer={SAMPLES} ! "
    f"tensor_converter frames-per-tensor={SAMPLES} ! "
    "tensor_transform mode=arithmetic option=typecast:float32,div:32768 ! "
    "tensor_filter framework=jax model=kws name=f ! "
    "tensor_decoder mode=image_labeling ! "
    "tensor_sink name=out to-host=true")

labels = []
pipe.get("out").connect(lambda b: labels.append(b.meta["label_index"]))
msg = pipe.run(timeout=300)
assert msg is not None and msg.kind == "eos", msg
print(f"classified {len(labels)} windows; labels: {labels}")
print(f"filter latency: {pipe.get('f').get_property('latency')} µs")
