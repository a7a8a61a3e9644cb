"""Recurrence — LSTM hidden/cell state circulates through a tensor_repo
slot as device-resident arrays (never leaves HBM between steps)."""

import jax.numpy as jnp
import numpy as np

import nnstreamer_tpu as nt
from nnstreamer_tpu.filters.jax_backend import register_jax_model
from nnstreamer_tpu.models.lstm import lstm_cell

hidden = 32
apply_fn, params, _, _ = lstm_cell(input_dim=hidden, hidden=hidden)


def step(p, state):
    s = state.reshape(1, 2 * hidden).astype(jnp.float32)
    h, c = s[:, :hidden], s[:, hidden:]
    y, h2, c2 = apply_fn(p, h, h, c)
    return jnp.concatenate([h2, c2], axis=1).reshape(2 * hidden)


register_jax_model("lstm_step", step, params)

pipe = nt.parse_launch(
    "tensor_reposrc slot=state num-buffers=10 "
    f"initial-dim={2 * hidden} initial-type=float32 initial-value=0.01 "
    "timeout=10 ! "
    "tensor_filter framework=jax model=lstm_step ! "
    "tee name=t  t. ! tensor_reposink slot=state  "
    "t. ! tensor_sink name=out to-host=true")
pipe.get("out").connect(
    lambda buf: print("step norm:",
                      round(float(np.linalg.norm(np.asarray(buf[0]))), 4)))
print("run:", pipe.run(timeout=120).kind)
