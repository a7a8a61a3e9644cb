"""Autoregressive LM token streaming through a tensor_repo loop.

The LSTM recurrence pattern (recurrence.py) scaled to transformer decode:
the KV cache is DEVICE-RESIDENT state circulating through a repo slot as
jax.Array handles — each pipeline iteration is one cached decode step
(models/transformer.build_decode_step), and only the sampled token ids
ever reach the host. The reference's tensor_repo enables exactly this
loop topology (tests/nnstreamer_repo_lstm); the KV-cache-in-HBM part is
what TPU adds.

Run: PYTHONPATH=.. python llm_stream.py
(JAX picks the backend: the TPU where there is one; JAX_PLATFORMS=cpu
forces CPU XLA.)
"""

import jax.numpy as jnp
import numpy as np

import nnstreamer_tpu as nt
from nnstreamer_tpu.elements.repo import GLOBAL_REPO
from nnstreamer_tpu.filters.jax_backend import register_jax_model
import jax

from nnstreamer_tpu.models.transformer import (
    TransformerConfig,
    build_greedy_stream_step,
    build_prefill,
    init_params,
)
from nnstreamer_tpu.tensors.buffer import TensorBuffer

N_TOKENS = 16
cfg = TransformerConfig(vocab=256, d_model=64, n_heads=4, n_layers=2,
                        d_ff=128, max_seq=64, dtype=jnp.float32)
params = init_params(cfg)
register_jax_model("lm_decode", build_greedy_stream_step(cfg), params)

# serving flow: prefill the prompt in ONE full-sequence pass, then stream.
# The warmed cache enters the loop as a device-resident jax.Array — it
# never leaves HBM.
prompt = jnp.asarray([[7, 42, 3, 99]], jnp.int32)
logits, cache = jax.jit(build_prefill(cfg))(params, prompt)
first = jnp.argmax(logits, axis=-1).astype(jnp.int32)
GLOBAL_REPO.set("lm", TensorBuffer(
    [np.asarray(first),
     cache,
     np.asarray(prompt.shape[1], np.int32)], pts=0))

pipe = nt.parse_launch(
    f"tensor_reposrc slot=lm num-buffers={N_TOKENS} timeout=30 ! "
    "tensor_filter framework=jax model=lm_decode name=f ! "
    "tee name=t  t. ! tensor_reposink slot=lm  "
    "t. ! tensor_sink name=out to-host=false")

tokens = []
pipe.get("out").connect(
    lambda b: tokens.append(int(np.asarray(b[0]).reshape(-1)[0])))
msg = pipe.run(timeout=300)
assert msg is not None and msg.kind == "eos", msg
print(f"prompt {prompt.tolist()[0]} → first sampled {int(first[0])}")
print(f"streamed {len(tokens)} tokens: {tokens}")
print(f"decode-step latency: {pipe.get('f').get_property('latency')} µs")
