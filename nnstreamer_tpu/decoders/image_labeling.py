"""image_labeling decoder — classification scores → text label.

Reference: ``ext/nnstreamer/tensor_decoder/tensordec-imagelabel.c`` (271
LoC): argmax over the score tensor, label looked up from the option1 labels
file, output ``text/x-raw``.
"""

from __future__ import annotations

import numpy as np

from nnstreamer_tpu.pipeline.caps import Caps
from nnstreamer_tpu.registry import DECODER, subplugin
from nnstreamer_tpu.tensors.buffer import TensorBuffer


def load_labels(path: str):
    with open(path) as f:
        return [line.strip() for line in f if line.strip()]


@subplugin(DECODER, "image_labeling")
class ImageLabeling:
    def __init__(self):
        self._labels = None
        self._labels_path = None

    def _get_labels(self, options):
        path = options.get("option1")
        if path and path != self._labels_path:
            self._labels = load_labels(path)
            self._labels_path = path
        return self._labels

    def out_caps(self, config, options) -> Caps:
        return Caps("text/x-raw", {"format": "utf8"})

    @staticmethod
    def _batched(options) -> bool:
        """option2=batched: rows of tensor[0] are separate frames (an
        upstream tensor_aggregator micro-batch) — one label per row. The
        default keeps reference semantics: argmax over the whole tensor
        (a 2-D score tensor is ONE frame, tensordec-imagelabel.c)."""
        return str(options.get("option2", "")).strip().lower() in (
            "batched", "batch", "per-row")

    def decode(self, buf: TensorBuffer, config, options) -> TensorBuffer:
        scores = np.asarray(buf[0])
        if self._batched(options) and scores.ndim >= 2:
            flat = scores.reshape(scores.shape[0], -1)
            idxs = np.argmax(flat, axis=-1)
            tops = flat[np.arange(flat.shape[0]), idxs]
            return self._emit(buf, idxs.tolist(), tops.tolist(), options)
        flat = scores.reshape(-1)
        idx = int(np.argmax(flat))
        return self._emit(buf, idx, float(flat[idx]), options)

    def _emit(self, buf, idx, score, options) -> TensorBuffer:
        labels = self._get_labels(options)

        def name(i):
            return labels[i] if labels and i < len(labels) else str(i)

        if isinstance(idx, list):
            texts = [name(int(i)) for i in idx]
            out = np.frombuffer("\n".join(texts).encode("utf-8"), np.uint8)
            return buf.with_tensors([out]).replace(
                meta={**buf.meta, "label_index": [int(i) for i in idx],
                      "label": texts, "score": [float(s) for s in score]}
            )
        text = name(int(idx))
        out = np.frombuffer(text.encode("utf-8"), np.uint8)
        return buf.with_tensors([out]).replace(
            meta={**buf.meta, "label_index": int(idx), "label": text,
                  "score": float(score)}
        )

    # -- fused-region split (elements/decoder.py device_stage) ---------------
    def device_kernel(self, options):
        """Device half: argmax + top score stay in the XLA program, so only
        per-frame scalars ever cross to the host instead of the full score
        tensor (one pair per batch row with option2=batched)."""
        import jax.numpy as jnp

        batched = self._batched(options)

        def fn(consts, tensors):
            s = tensors[0]
            rows = s.reshape(s.shape[0], -1) if batched and s.ndim >= 2 \
                else s.reshape(1, -1)
            return [jnp.argmax(rows, axis=-1).astype(jnp.int32),
                    jnp.max(rows, axis=-1).astype(jnp.float32)]

        return None, fn

    def host_finalize(self, host_buf: TensorBuffer, config, options
                      ) -> TensorBuffer:
        idxs = np.asarray(host_buf[0]).reshape(-1)
        scores = np.asarray(host_buf[1]).reshape(-1)
        if idxs.size > 1:
            return self._emit(host_buf, idxs.tolist(), scores.tolist(),
                              options)
        return self._emit(host_buf, int(idxs[0]), float(scores[0]), options)
