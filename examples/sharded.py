"""Sharded invoke — the filter shards its batch dim over every visible
device with NamedSharding; XLA inserts the collectives.

Run with a virtual 8-device mesh to try it anywhere:
  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      python examples/sharded.py
"""

import jax
import jax.numpy as jnp
import numpy as np

import nnstreamer_tpu as nt
from nnstreamer_tpu.filters.jax_backend import register_jax_model

n_dev = len(jax.devices())
print(f"devices: {n_dev} x {jax.devices()[0].platform}")

w = jnp.full((3, 8), 0.5, jnp.float32)
register_jax_model("lin", lambda p, x: x.astype(jnp.float32) @ p, w)

# the sharded batch dim must be divisible by the device count — push
# device-count-sized batches of frames [n_dev, H, W, 3]
pipe = nt.parse_launch(
    "appsrc name=src ! tensor_transform mode=typecast option=float32 ! "
    "tensor_filter framework=jax model=lin custom=sharding:batch ! "
    "tensor_sink name=out to-host=true")
pipe.get("out").connect(lambda buf: print("out", buf))
src = pipe.get("src")
pipe.start()
for i in range(5):
    src.push([np.full((n_dev, 8, 4, 3), i, np.uint8)])
src.end_of_stream()
msg = pipe.wait(timeout=120)
pipe.stop()
print("run:", msg.kind if msg is not None else "timeout")
