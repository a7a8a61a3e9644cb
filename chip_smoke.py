"""chip_smoke.py — does the system still start, and compute the right
answers, on the TPU?

    python chip_smoke.py            # no arguments, no JAX_PLATFORMS

One process drives the two main paths once, through the entry points a
user calls, at the full width of the models the repo serves (random
weights from a seed, nothing downloaded):

- **kernel leg** — the three Pallas kernels compiled by Mosaic (never
  interpreted) at the shapes the other legs and ``bench.py attn`` use,
  compared on the chip with their ``force="reference"`` results; and the
  routed experts' grouped matmul (``nns_expert_tiles``) against the tile
  loop at both benchmark configurations' real expert shapes, the decode
  tile and the largest prefill tile of each; and the recurrent mixers'
  in-place state update (``nns_lane_state``) against its reference at
  both recurrent configurations' real state arenas;
- **stream leg** — MobileNetV2 (width 1.0, 224², bf16, batch 8) through
  ``parse_launch`` with the flagship topology; every label must equal the
  argmax of a plain ``jax.jit`` of the same model on the same frames, and
  the pipeline's own staged and produced buffers must live on the chip;
- **server leg** — a paged ``ContinuousBatchingEngine`` (d_model 512,
  8 layers, vocab 32000) behind ``tensor_query_serversrc !
  tensor_lm_serve ! tensor_query_serversink``, asked by loopback
  ``tensor_query_client`` pipelines; every client must get exactly
  ``max-new-tokens`` real tokens (never the ``-1`` error token) and
  every prompt's first token must agree with the full forward pass;
- **hybrid leg** — the hybrid LM family (``models/hybrid.py``: Mamba-2
  and attention mixers, 8 experts top-3 of which 4 are held) at a small
  size through the same engine's paged path: each prompt's first token
  and eight decoded tokens must agree with the family's full forward
  over prompt + served tokens, and the pool must end with no block and
  no state slot live; then the same checks for the family's other
  recurrence (**hybrid_delta**: gated delta rule mixers and gated
  attention with 16 query over 2 key-value heads of 256, partial rotary,
  a gated shared expert, an untied head);
- **mesh leg** — on a host with four or more chips, the stream leg again
  with ``mesh=dp4``: the batches the pipeline staged lie two rows each on
  four distinct devices, no byte is resharded, labels equal the
  single-device leg's.

Any failed check is a non-zero exit with a traceback and NO result line.
On success stdout is two lines, each one JSON object: the summary (one
entry per leg, the compile cache's directory and hit/miss counts, the
transport that served, ``sync_roundtrip_ms``, ``"claim": null``), then,
last, the verdict with exactly these keys, the device as JAX reports it::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

The run refuses — non-zero, naming what it found — unless
``jax.default_backend()`` is ``tpu`` and the ``device_kind`` is one it
knows.

``--rehearse-cpu`` is a separate mode for debugging THIS SCRIPT without
chip time: the same legs at cut sizes on CPU XLA, kernels interpreted. It
requires ``JAX_PLATFORMS=cpu``, prints no ``"ok"`` and proves nothing
about the chip; the argument-less run can never reach it.

The pipeline strings below are the smoke's own (``tests/test_chip_smoke``
parses them on the CPU so a renamed property fails there, not on chip
time); ``bench.py`` is deliberately not imported.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time
import traceback

#: device kinds this smoke has been run on; extend when a new chip is
#: brought up, after running it there
KNOWN_DEVICE_KINDS = ("TPU v5 lite", "TPU v5e")

STREAM_DESC = (
    "videotestsrc num-buffers={frames} width=224 height=224 pattern=ball ! "
    "tensor_converter ! queue max-size-buffers=16 ! "
    "tensor_aggregator frames-in=1 frames-out=8 frames-flush=8 "
    "frames-dim=3 concat=true ! "
    "queue name=stage max-size-buffers=8 prefetch-device=true ! "
    "tensor_transform mode=arithmetic "
    "option=typecast:float32,add:-127.5,div:127.5 ! "
    "tensor_filter framework=jax model={model} name=filter {mesh}"
    "inflight=2 ! "
    "tensor_decoder mode=image_labeling option2=batched ! "
    "queue name=tohost max-size-buffers=64 materialize-host=true ! "
    "tensor_sink name=sink to-host=true")

#: the same source alone: the frames the reference is computed on
FRAMES_DESC = (
    "videotestsrc num-buffers={frames} width=224 height=224 pattern=ball ! "
    "tensor_converter ! tensor_sink name=sink to-host=true")

SERVER_DESC = (
    "tensor_query_serversrc name=ssrc port=0 ! "
    "tensor_lm_serve engine={engine} max-new-tokens={max_new} ! "
    "tensor_query_serversink")

CLIENT_DESC = (
    "appsrc name=src ! tensor_query_client dest-host=127.0.0.1 "
    "dest-port={port} timeout=600 max-in-flight=2 ! "
    "tensor_sink name=out to-host=true")

BATCH = 8
#: served vs full-forward log-probability of a first token, bf16 model
LOGPROB_TOL = 0.05
MODEL_NAME = "smoke_mobilenet_v2_b8"
ENGINE_NAME = "smoke_lm"

#: full width everywhere; the rehearsal cuts depth, counts and the one
#: kernel shape the Pallas interpreter cannot finish in reasonable time
FULL = dict(
    frames=64, lm_layers=8, max_new=16,
    # per client; buckets hit: 16, 64, 256, 512 (min_bucket 16, x2 steps)
    prompts=((12, 300), (40, 200), (9, 260)),
    hybrid_prompts=(12, 200, 300),
    flash_shapes=((1, 256, 8, 64), (4, 4096, 8, 64)),
    # (experts per token, experts, held, d, f), then tokens a call: 128
    # lanes and a 512-token prompt of qwen3_next_80b_a3b_ep2 (tiles of 8
    # and 32 rows), 64 lanes and a 512-token prompt of
    # granite_4p0_h_small_ep2 (32 and 128)
    expert_shapes=(((10, 512, 256, 2048, 512), (128, 512)),
                   ((10, 72, 36, 4096, 768), (64, 512))),
    # rule, state arena [layers, lanes, heads, rows, cols]: 64 lanes of
    # granite_4p0_h_small_ep2 (2.42 GB), 128 of qwen3_next_80b_a3b_ep2
    lane_state_shapes=(("mamba2", (9, 64, 128, 64, 128)),
                       ("gated_delta", (3, 128, 32, 128, 128))))
REHEARSAL = dict(
    frames=16, lm_layers=2, max_new=4,
    prompts=((12, 260), (40,)),
    hybrid_prompts=(12, 40),
    flash_shapes=((1, 256, 8, 64),),
    expert_shapes=(((4, 64, 32, 256, 128), (16,)),
                   ((4, 16, 8, 256, 256), (64,))),
    lane_state_shapes=(("mamba2", (2, 4, 16, 8, 128)),
                       ("gated_delta", (2, 4, 4, 16, 128))))


class SmokeFailure(AssertionError):
    """A smoke check did not hold."""


def check(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


# --------------------------------------------------------------------------
# device
# --------------------------------------------------------------------------
def require_tpu() -> None:
    import jax

    backend = jax.default_backend()
    kind = jax.devices()[0].device_kind
    if backend != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, but JAX initialized backend "
                 f"{backend!r} (device_kind {kind!r}). No result.")
    if kind not in KNOWN_DEVICE_KINDS:
        sys.exit(f"chip_smoke: device_kind {kind!r} is not one this smoke "
                 f"knows ({', '.join(KNOWN_DEVICE_KINDS)}). No result.")


def sync_roundtrip_ms(n: int = 50) -> float:
    """Median wall time of a trivial jitted program whose result is
    fetched with np.asarray — the per-dispatch floor of this host↔device
    link (reported, not judged)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    f = jax.jit(lambda x: x + 1)
    x = jnp.zeros((8,), jnp.int32)
    np.asarray(f(x))
    samples = []
    for _ in range(n):
        t0 = time.perf_counter()
        np.asarray(f(x))
        samples.append((time.perf_counter() - t0) * 1e3)
    return round(sorted(samples)[n // 2], 4)


# --------------------------------------------------------------------------
# kernel leg
# --------------------------------------------------------------------------
def _mosaic_compiled(fn, *args) -> bool:
    """The lowered program carries the Mosaic custom call (an interpreted
    kernel lowers to plain HLO instead)."""
    import jax

    return "tpu_custom_call" in jax.jit(fn).lower(*args).as_text()


def kernel_leg(sizes: dict, on_chip: bool) -> dict:
    import jax.numpy as jnp
    import numpy as np

    from nnstreamer_tpu.ops import (
        dequantize_int8,
        flash_attention,
        normalize_u8,
        quantize_int8,
    )

    rng = np.random.default_rng(0)
    out: dict = {"mosaic": on_chip, "flash_max_abs_err": {}}

    def pallas(fn):
        return lambda *a, **kw: fn(*a, force="pallas", **kw)

    # bf16 in/out, values O(1): one bf16 ulp in [2, 4) is 2**-6
    flash_tol = 2 * 2.0 ** -6
    for shape in sizes["flash_shapes"]:
        q, k, v = (jnp.asarray(rng.normal(0, 1, shape), jnp.bfloat16)
                   for _ in range(3))
        if on_chip:
            check(_mosaic_compiled(pallas(flash_attention), q, k, v),
                  f"flash_attention{shape}: no Mosaic call in the program")
        got = flash_attention(q, k, v, causal=True, force="pallas")
        ref = flash_attention(q, k, v, causal=True, force="reference")
        err = float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                    - ref.astype(jnp.float32))))
        check(got.shape == shape and got.dtype == jnp.bfloat16,
              f"flash_attention{shape}: got {got.shape} {got.dtype}")
        check(err <= flash_tol,
              f"flash_attention{shape}: max |pallas - reference| = {err} "
              f"> {flash_tol}")
        out["flash_max_abs_err"]["x".join(map(str, shape))] = err

    frames = jnp.asarray(rng.integers(0, 256, (BATCH, 224, 224, 3),
                                      dtype=np.uint8))
    if on_chip:
        check(_mosaic_compiled(pallas(normalize_u8), frames),
              "normalize_u8: no Mosaic call in the program")
    got = normalize_u8(frames, force="pallas")
    ref = normalize_u8(frames, force="reference")
    err = float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                - ref.astype(jnp.float32))))
    check(got.shape == frames.shape and got.dtype == jnp.bfloat16,
          f"normalize_u8: got {got.shape} {got.dtype}")
    # outputs in [-1, 1]: one bf16 ulp there is 2**-8
    check(err <= 2.0 ** -8, f"normalize_u8: max abs err {err} > 2**-8")
    out["normalize_max_abs_err"] = err

    x = jnp.asarray(rng.normal(0, 1, (BATCH, 224, 224, 3)), jnp.float32)
    if on_chip:
        check(_mosaic_compiled(lambda a: quantize_int8(
            a, seed=3, force="pallas")[0], x),
            "quantize_int8: no Mosaic call in the program")
    q8, scale = quantize_int8(x, seed=3, force="pallas")
    q8_ref, scale_ref = quantize_int8(x, force="reference")
    check(q8.shape == x.shape and q8.dtype == jnp.int8,
          f"quantize_int8: got {q8.shape} {q8.dtype}")
    check(float(scale[0]) == float(scale_ref[0]),
          f"quantize_int8: scale {float(scale[0])} != reference "
          f"{float(scale_ref[0])}")
    # the Pallas path dithers (stochastic rounding), the reference rounds
    # to nearest: dequantised values may differ by one step, never more
    steps = float(jnp.max(jnp.abs(dequantize_int8(q8, scale)
                                  - dequantize_int8(q8_ref, scale_ref)))
                  ) / float(scale[0])
    check(steps <= 1.001, f"quantize_int8: {steps} steps from reference")
    out["quantize_max_steps_from_reference"] = round(steps, 6)
    out["expert_tiles"] = [
        case for layer, calls in sizes["expert_shapes"]
        for case in _expert_tiles_cases(layer, calls, on_chip)]
    out["lane_state"] = [_lane_state_case(rule, shape, on_chip)
                         for rule, shape in sizes["lane_state_shapes"]]
    return out


def _lane_state_case(rule: str, shape: tuple, on_chip: bool) -> dict:
    """The in-place state update against its reference on one arena: the
    last layer stepped once, every seventh lane empty, operands at the
    sizes a mixer hands over. Both forms are float32 elementwise and
    differ in the order of the sums behind an output alone: 1e-5 of the
    largest value holds; an empty lane's slot and every other layer come
    out bit for bit as they went in. Each arena is made, stepped in place
    (donated) and reduced on the device: two of them live at a time."""
    import jax
    import jax.numpy as jnp

    from nnstreamer_tpu.models.gated_delta import l2norm
    from nnstreamer_tpu.ops import lane_state as ls

    layers, lanes, heads, rows, cols = shape
    keys = jax.random.split(jax.random.PRNGKey(layers), 6)

    def normal(i, *dims):
        return jax.random.normal(keys[i], dims, jnp.float32)

    if rule == ls.MAMBA2:
        operands = (normal(0, lanes, heads, rows),
                    jax.nn.softplus(normal(1, lanes, heads)),
                    -jnp.exp(0.3 * normal(2, heads)), normal(3, lanes, cols),
                    normal(4, lanes, cols))
    else:
        operands = (l2norm(normal(0, lanes, heads, rows)) * rows ** -0.5,
                    l2norm(normal(1, lanes, heads, rows)),
                    normal(2, lanes, heads, cols),
                    -jax.nn.softplus(normal(3, lanes, heads)),
                    jax.nn.sigmoid(normal(4, lanes, heads)))
    live = jnp.arange(lanes) % 7 != 3
    layer = layers - 1
    make = jax.jit(lambda key: 0.5 * jax.random.normal(key, shape,
                                                       jnp.float32))

    def fresh():       # the key an argument: a program of constants is
        return make(keys[5])    # evaluated, 2.4 GB of it, by the compiler

    def step(force):   # the operands as arguments: constants get folded
        return lambda arena, live, operands: ls.update(
            rule, ls.LaneSlot(arena, layer), live, operands, force=force)

    def stepped(force):
        out, slot = jax.jit(step(force), donate_argnums=(0,))(
            fresh(), live, operands)
        return out, slot.arena

    what = f"lane_state {rule} over {shape}"
    if on_chip:
        check(_mosaic_compiled(
            step("pallas"), jax.ShapeDtypeStruct(shape, jnp.float32), live,
            operands), f"{what}: no Mosaic call in the program")
    want_out, want = stepped("reference")
    got_out, got = stepped("pallas")

    @jax.jit
    def compare(got, want, got_out, want_out, was):
        alive = live[:, None, None]
        return {
            "max_abs_delta_state": jnp.max(jnp.abs(got - want)),
            "max_abs_state": jnp.max(jnp.abs(want[layer])),
            "max_abs_delta_out": jnp.max(jnp.where(
                alive, jnp.abs(got_out - want_out), 0.0)),
            "max_abs_out": jnp.max(jnp.where(alive, jnp.abs(want_out), 0.0)),
            "untouched": jnp.all(jnp.where(
                live[:, None, None, None], True, got[layer] == was[layer]))
            & jnp.all(got[:layer] == was[:layer]),
            "moved": jnp.max(jnp.abs(want[layer] - was[layer])),
        }

    read = {k: v.item() for k, v in compare(got, want, got_out, want_out,
                                            fresh()).items()}
    check(got.shape == shape and got.dtype == jnp.float32
          and got_out.shape == want_out.shape, f"{what}: got {got.shape}")
    check(read["moved"] > 0.1 and read["max_abs_delta_state"]
          <= 1e-5 * read["max_abs_state"],
          f"{what}: max |kernel - reference| over the state = {read}")
    check(read["max_abs_out"] > 0 and read["max_abs_delta_out"]
          <= 1e-5 * read["max_abs_out"],
          f"{what}: max |kernel - reference| over the outputs = {read}")
    check(read["untouched"], f"{what}: an empty lane's slot or another "
                             f"layer is not what it was")
    return {"rule": rule, "arena": list(shape),
            "head_block": ls.head_block(heads, rows * cols * 4)[0],
            **{k: read[k] for k in ("max_abs_delta_state", "max_abs_state",
                                    "max_abs_delta_out", "max_abs_out")}}


def _expert_tiles_cases(layer: tuple, calls: tuple, on_chip: bool):
    """The grouped matmul against the tile loop, one routed batch an entry
    of ``calls``: ``tokens`` tokens choose ``k`` of ``experts`` at random,
    the pairs on the ``held`` first experts are laid out as ``moe_ffn``
    lays them out, and both forms run the same buffer through the same
    bfloat16 weights (one draw for all of ``calls``). The two differ in
    the order of float32 sums alone, so a row may move by one rounding of
    ``silu(a) * b`` to bfloat16: 2**-7 of the largest output."""
    import jax
    import jax.numpy as jnp

    k, experts, held, d, f = layer
    key = jax.random.PRNGKey(held)
    w_in = jax.random.normal(key, (held, d, 2 * f), jnp.bfloat16) * 0.02
    w_out = jax.random.normal(jax.random.fold_in(key, 1), (held, f, d),
                              jnp.bfloat16) * 0.02
    for tokens in calls:
        yield _expert_tiles_case(tokens, k, experts, held, w_in, w_out,
                                 on_chip)


def _expert_tiles_case(tokens, k, experts, held, w_in, w_out, on_chip):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from nnstreamer_tpu.models.hybrid import HybridConfig, expert_tile
    from nnstreamer_tpu.ops.grouped_matmul import expert_tiles

    d = w_in.shape[1]
    tile, rows = expert_tile(HybridConfig(
        num_experts=experts, experts_per_token=k, experts_held=(0, held)),
        tokens)
    rng = np.random.default_rng(tokens)
    choice = np.stack([rng.permutation(experts)[:k] for _ in range(tokens)])
    counts = np.bincount(choice[choice < held], minlength=held)
    padded = -(-counts // tile) * tile
    ends = np.cumsum(padded)
    real = np.zeros(rows, bool)
    for start, n in zip(ends - padded, counts):
        real[start:start + n] = True
    tile_expert = jnp.asarray(np.minimum(
        (ends[None, :] <= (np.arange(rows // tile) * tile)[:, None]).sum(1),
        held - 1), jnp.int32)
    n_live = jnp.int32(ends[-1] // tile)
    x = jnp.where(jnp.asarray(real)[:, None], jax.random.normal(
        jax.random.PRNGKey(tokens), (rows, d), jnp.bfloat16), 0)
    what = f"expert_tiles {tokens} tokens through {tuple(w_in.shape)}"
    if on_chip:
        check(_mosaic_compiled(
            lambda *a: expert_tiles(*a, tile, force="pallas"),
            x, tile_expert, n_live, w_in, w_out),
            f"{what}: no Mosaic call in the program")
    got = expert_tiles(x, tile_expert, n_live, w_in, w_out, tile,
                       force="pallas")
    ref = expert_tiles(x, tile_expert, n_live, w_in, w_out, tile,
                       force="reference")
    err = float(jnp.max(jnp.abs(got - ref)))
    size = float(jnp.max(jnp.abs(ref)))
    check(got.shape == (rows, d) and got.dtype == jnp.float32,
          f"{what}: got {got.shape} {got.dtype}")
    check(size > 0 and err <= 2.0 ** -7 * size,
          f"{what}: max |kernel - tile loop| = {err} against outputs of "
          f"{size}")
    check(not bool(jnp.any(got[int(n_live) * tile:])),
          f"{what}: rows past the live tiles are not zero")
    return {"tokens": tokens, "w_in": list(w_in.shape), "tile": tile,
            "tiles_grid": rows // tile, "tiles_live": int(n_live),
            "max_abs_delta": err, "max_abs_out": size}


# --------------------------------------------------------------------------
# stream leg (and its mesh=dp4 twin)
# --------------------------------------------------------------------------
def _register_mobilenet() -> tuple:
    import jax.numpy as jnp

    from nnstreamer_tpu.filters.jax_backend import register_jax_model
    from nnstreamer_tpu.models.mobilenet_v2 import mobilenet_v2

    apply_fn, params, in_info, out_info = mobilenet_v2(
        width=1.0, image_size=224, batch=BATCH, dtype=jnp.bfloat16, seed=0)
    register_jax_model(MODEL_NAME, apply_fn, params, in_info=in_info,
                       out_info=out_info)
    return apply_fn, params


def _source_frames(frames: int):
    """The uint8 frames videotestsrc produces, [frames, 224, 224, 3]."""
    import numpy as np

    import nnstreamer_tpu as nt

    pipe = nt.parse_launch(FRAMES_DESC.format(frames=frames))
    got = []
    pipe.get("sink").connect(
        lambda b: got.append(np.asarray(b.tensors[0]).reshape(224, 224, 3)))
    msg = pipe.run(timeout=120)
    check(msg is not None and msg.kind == "eos", f"frame capture: {msg}")
    check(len(got) == frames, f"frame capture: {len(got)} of {frames}")
    return np.stack(got)


def _reference_labels(apply_fn, params, frames_u8) -> tuple:
    """(argmax, max logit) per frame of a plain jax.jit of the model (with
    the pipeline's own normalisation) on the same frames, batch by batch,
    on the default device."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    @jax.jit
    def ref(p, u8):
        logits = apply_fn(p, (u8.astype(jnp.float32) + -127.5) / 127.5)
        return jnp.argmax(logits, axis=-1), jnp.max(logits, axis=-1)

    dev_params = jax.device_put(params)
    labels, scores = [], []
    for i in range(0, len(frames_u8), BATCH):
        idx, top = ref(dev_params, frames_u8[i:i + BATCH])
        labels += np.asarray(idx).tolist()
        scores += np.asarray(top, np.float32).tolist()
    return labels, scores


def _retraces(pipeline_name: str) -> int:
    """nns_fuse_retraces_total summed over this pipeline's regions, read
    the way an operator would: from the exported metrics text."""
    import re

    from nnstreamer_tpu.obs import get_registry

    total = 0.0
    for line in get_registry().render_prometheus().splitlines():
        m = re.match(r"nns_fuse_retraces_total\{([^}]*)\}\s+(\S+)", line)
        if m and f'pipeline="{pipeline_name}"' in m.group(1):
            total += float(m.group(2))
    return int(total)


def _observe(obj, methods: tuple, record) -> None:
    """Call ``record(buf)`` on every buffer handed through ``obj``'s
    ``methods`` (the buffer, or a list of them, is the last argument)
    before passing it on. The flagship topology ends on the host, so this
    is how the smoke sees the device-resident buffers in the middle of
    the REAL pipeline instead of re-creating them on the side."""
    for name in methods:
        def wrapped(*args, _orig=getattr(obj, name)):
            bufs = args[-1]
            for buf in bufs if isinstance(bufs, list) else [bufs]:
                record(buf)
            return _orig(*args)
        setattr(obj, name, wrapped)


def _placement(buf):
    """Where a pipeline buffer's first tensor lives, read while it passes
    (a donated input is deleted a moment later): the platforms, and
    (device id, batch rows) per shard. None for a host array."""
    import jax

    t = buf.tensors[0]
    if not isinstance(t, jax.Array):
        return None
    return ({d.platform for d in t.devices()},
            sorted((s.device.id, s.data.shape[0])
                   for s in t.addressable_shards))


def stream_leg(sizes: dict, platform: str, frames_u8, reference: tuple,
               mesh: str = "") -> dict:
    import jax

    import nnstreamer_tpu as nt
    from nnstreamer_tpu.parallel import serve

    frames = sizes["frames"]
    name = f"smoke_stream_{mesh or 'single'}"
    pipe = nt.parse_launch(
        STREAM_DESC.format(frames=frames, model=MODEL_NAME,
                           mesh=f"mesh={mesh} " if mesh else ""),
        pipeline=nt.Pipeline(name=name))
    bufs = []
    pipe.get("sink").connect(bufs.append)
    # the batch the prefetch queue staged, as it leaves for the fused
    # region, and the region's result, as it arrives at the D2H queue
    staged, produced = [], []
    _observe(pipe.get("stage").srcpad, ("push", "push_list"),
             lambda b: staged.append(_placement(b)))
    _observe(pipe.get("tohost"), ("_chain_entry", "_chain_list_entry"),
             lambda b: produced.append(_placement(b)))
    reshard0 = serve.reshard_bytes_total()
    out: dict = {}
    pipe.start()
    try:
        msg = pipe.wait(timeout=900)
        check(msg is not None and msg.kind == "eos",
              f"stream pipeline did not reach EOS: {msg}")
        # still PLAYING: the backend is open, its placement inspectable
        fw = pipe.get("filter").fw
        leaves = jax.tree.leaves(fw._params)
        param_devices = {d for leaf in leaves for d in leaf.devices()}
        check({d.platform for d in param_devices} == {platform},
              f"filter parameters live on {param_devices}, not {platform}")
        out["param_device_ids"] = sorted(d.id for d in param_devices)
        plan = fw._mesh_plan
        check((plan is not None) == bool(mesh),
              f"mesh={mesh!r} but the backend's plan is {plan}")
        if mesh:
            want_ids = sorted(d.id for d in plan.mesh.devices.flat)
            check(len(set(want_ids)) == plan.shard_count == 4,
                  f"mesh {mesh} spans device ids {want_ids}")
        else:
            want_ids = [jax.devices()[0].id]
        check(out["param_device_ids"] == want_ids,
              f"parameters on {out['param_device_ids']}, expected "
              f"{want_ids}")
    finally:
        pipe.stop()
    batches = frames // BATCH
    rows = BATCH // len(want_ids)
    check(len(staged) == len(produced) == batches,
          f"{len(staged)} staged and {len(produced)} produced buffers "
          f"seen for {batches} batches")
    want = ({platform}, [(i, rows) for i in want_ids])
    check(all(p == want for p in staged),
          f"the prefetch queue staged batches on {staged}, expected "
          f"{rows} rows on each of device ids {want_ids} ({platform})")
    check(all(p is not None and p[0] == {platform}
              and [i for i, _ in p[1]] == want_ids for p in produced),
          f"the fused region's output lives on {produced}, expected "
          f"device ids {want_ids} ({platform})")
    out["staged_shards"] = [list(x) for x in want[1]]
    out["output_device_ids"] = want_ids
    labels = [int(i) for b in bufs for i in b.meta["label_index"]]
    scores = [float(v) for b in bufs for v in b.meta["score"]]
    check(len(labels) == frames,
          f"{len(labels)} labels reached the sink, expected {frames}")
    ref_labels, ref_scores = reference
    check(labels == ref_labels,
          f"labels differ from the direct jit's argmax: {labels} vs "
          f"{ref_labels}")
    worst = max(abs(a - b) for a, b in zip(scores, ref_scores))
    tol = 1e-3 + 1e-2 * max(abs(v) for v in ref_scores)
    check(worst <= tol,
          f"top scores differ from the direct jit's by {worst} > {tol}")
    out["frames"] = frames
    out["labels_equal_direct_jit"] = True
    out["distinct_labels"] = len(set(labels))
    out["distinct_scores"] = len(set(scores))
    out["score_max_abs_err"] = worst
    out["retraces"] = _retraces(name)
    check(out["retraces"] == 1,
          f"nns_fuse_retraces_total = {out['retraces']}, expected 1 (one "
          f"input shape)")
    out["reshard_bytes"] = serve.reshard_bytes_total() - reshard0
    check(out["reshard_bytes"] == 0,
          f"{out['reshard_bytes']} bytes were resharded at runtime")
    return out


# --------------------------------------------------------------------------
# server leg
# --------------------------------------------------------------------------
def _lm_client(port: int, prompts: list, results: dict, idx: int) -> None:
    import numpy as np

    import nnstreamer_tpu as nt

    pipe = nt.parse_launch(CLIENT_DESC.format(port=port))
    outs = []
    pipe.get("out").connect(outs.append)
    pipe.start()
    try:
        src = pipe.get("src")
        for p in prompts:
            src.push([np.asarray(p, np.int32)])
        src.end_of_stream()
        msg = pipe.wait(timeout=900)
        results[idx] = (msg, [[np.asarray(t) for t in b.tensors]
                              for b in outs])
    finally:
        pipe.stop()


def server_leg(sizes: dict, on_chip: bool) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    import nnstreamer_tpu as nt
    from nnstreamer_tpu.models.transformer import (
        TransformerConfig,
        build_forward,
        init_params,
    )
    from nnstreamer_tpu.serving import (
        ContinuousBatchingEngine,
        register_engine,
        unregister_engine,
    )

    cfg = TransformerConfig(vocab=32000, d_model=512, n_heads=8,
                            n_layers=sizes["lm_layers"], d_ff=2048,
                            max_seq=512, dtype=jnp.bfloat16)
    params = init_params(cfg, seed=0)
    max_new = sizes["max_new"]
    rng = np.random.default_rng(1)
    prompts = [[rng.integers(1, cfg.vocab, n).tolist() for n in lens]
               for lens in sizes["prompts"]]
    engine = ContinuousBatchingEngine(
        cfg, params, max_streams=8, steps_per_dispatch=8, temperature=0.0,
        block_tokens=16, attention="auto").start()
    register_engine(ENGINE_NAME, engine)
    server = nt.parse_launch(
        SERVER_DESC.format(engine=ENGINE_NAME, max_new=max_new),
        pipeline=nt.Pipeline(name="smoke_lm_server"))
    results: dict = {}
    try:
        server.start()
        port = server.get("ssrc").port
        clients = [threading.Thread(target=_lm_client, name=f"lm-client{i}",
                                    args=(port, ps, results, i))
                   for i, ps in enumerate(prompts)]
        for t in clients:
            t.start()
        for t in clients:
            t.join(timeout=900)
            check(not t.is_alive(), f"{t.name} did not finish")
        out: dict = {"clients": len(prompts), "max_new_tokens": max_new,
                     "prompt_lens": [list(lens) for lens in sizes["prompts"]]}
        # the reference: the full forward pass (plain XLA attention, no
        # cache, no paging) on each prompt, zero-padded to max_seq so all
        # share one program — causal, so the padding changes nothing
        # before it. It must give the served first token (near) the top
        # log-probability, and the value the server reported for it: the
        # short prompts check the XLA prefill buckets, those of 256
        # tokens or more the Pallas one.
        forward = build_forward(cfg)

        @jax.jit
        def ref_logprobs(prm, tokens, last):
            logits = forward(prm, tokens)[0, last]
            return jax.nn.log_softmax(logits.astype(jnp.float32))

        worst_lp = worst_gap = 0.0
        for i, ps in enumerate(prompts):
            check(i in results, f"client {i} died before reporting")
            msg, answers = results[i]
            check(msg is not None and msg.kind == "eos",
                  f"client {i} did not reach EOS: {msg}")
            check(len(answers) == len(ps),
                  f"client {i}: {len(answers)} answers to {len(ps)} prompts")
            for p, (toks, lps) in zip(ps, answers):
                toks = toks.reshape(-1)
                lps = lps.reshape(-1)
                who = f"client {i}, prompt of {len(p)}"
                # elements/lm_serve.py answers a failed request with a
                # single -1 token: a dead engine must not pass
                check(toks.dtype == np.int32 and toks.size == max_new
                      and (toks >= 0).all() and (toks < cfg.vocab).all(),
                      f"{who}: tokens {toks.tolist()} ({toks.dtype}); -1 is "
                      f"the server's error answer")
                check(lps.dtype == np.float32 and lps.size == max_new
                      and np.isfinite(lps).all(), f"{who}: logprobs {lps}")
                padded = np.zeros((1, cfg.max_seq), np.int32)
                padded[0, :len(p)] = p
                ref_lp = np.asarray(ref_logprobs(params, padded, len(p) - 1))
                tok, lp = int(toks[0]), float(lps[0])
                ref, top = float(ref_lp[tok]), float(ref_lp.max())
                check(abs(ref - lp) <= LOGPROB_TOL,
                      f"{who}: first token {tok} served with logprob {lp}, "
                      f"full forward says {ref}")
                check(ref >= top - LOGPROB_TOL,
                      f"{who}: first token {tok} is not the forward pass's "
                      f"argmax ({ref} vs {top})")
                worst_lp = max(worst_lp, abs(ref - lp))
                worst_gap = max(worst_gap, top - ref)
        out["first_token_vs_forward"] = {
            "prompts": sum(len(ps) for ps in prompts),
            "max_logprob_diff": round(worst_lp, 5),
            "max_gap_to_argmax": round(worst_gap, 5)}
        stats = {k: int(v) for k, v in engine.stats.items()
                 if isinstance(v, (int, np.integer))}
        n_prompts = sum(len(ps) for ps in prompts)
        check(stats["prefills"] == n_prompts,
              f"{stats['prefills']} prefills for {n_prompts} prompts")
        out["engine_stats"] = stats
        if on_chip:
            # the prefill bucket >= 256 must be the program with the
            # Pallas flash kernel in it, not the XLA reference
            # engine.params is the tree the engine HOLDS: the float32
            # matrices it was given, narrowed to cfg.dtype at construction
            lowered = engine._prefill_jitted.lower(
                engine.params, jnp.zeros((1, 256), jnp.int32),
                lengths=jnp.asarray([200], jnp.int32)).as_text()
            check("tpu_custom_call" in lowered,
                  "the 256-token prefill program has no Pallas call")
        out["prefill_256_has_pallas_call"] = on_chip
        deadline = time.monotonic() + 10
        while engine._pool.live_blocks() and time.monotonic() < deadline:
            time.sleep(0.05)
        check(engine._pool.live_blocks() == 0,
              f"{engine._pool.live_blocks()} KV blocks still live")
        out["live_blocks"] = 0
        return out
    finally:
        server.stop()
        engine.stop()
        unregister_engine(ENGINE_NAME)


#: the hybrid family's two recurrences at a small size: Mamba-2 beside
#: plain grouped attention, and the gated delta rule beside gated attention
#: at the head shape the paged and flash kernels are built for
HYBRID_CONFIGS = {
    "hybrid": dict(
        layer_types=("mamba", "mamba", "attention", "mamba"),
        n_heads=4, n_kv_heads=2, head_dim=64, attention_scale=0.125,
        ssm_heads=8, ssm_head_dim=64, ssm_state=128, ssm_chunk=256),
    "hybrid_delta": dict(
        layer_types=("linear_attention",) * 3 + ("attention",),
        n_heads=16, n_kv_heads=2, head_dim=256, attention_scale=0.0625,
        rotary_dim=64, rope_theta=1e7, qk_norm=True, attn_gate=True,
        la_key_heads=4, la_value_heads=8, la_key_dim=128, la_value_dim=128,
        la_chunk=64, shared_gate=True, tie_embeddings=False,
        embedding_multiplier=1.0, residual_multiplier=1.0,
        logits_scaling=1.0, rms_eps=1e-6),
}


def hybrid_leg(sizes: dict, on_chip: bool, which: str = "hybrid") -> dict:
    """The hybrid LM family (recurrent + attention mixers, a share of the
    routed experts) at a small size through the engine's paged path: the
    first token and eight decoded tokens of each prompt against the
    family's own full forward over prompt + served tokens — prefill, the
    hand-over of the recurrent state at the prompt's last token, and the
    decode steps through both arenas. ``which``: a key of
    :data:`HYBRID_CONFIGS`."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from nnstreamer_tpu.models import hybrid
    from nnstreamer_tpu.serving import ContinuousBatchingEngine

    cfg = hybrid.HybridConfig(
        vocab=1024, d_model=256, num_experts=8, experts_per_token=3,
        expert_width=128, shared_width=256, experts_held=(0, 4),
        max_seq=512, **HYBRID_CONFIGS[which])
    params = hybrid.init_params(cfg, seed=0)
    new = 9
    rng = np.random.default_rng(2)
    prompts = [rng.integers(1, cfg.vocab, n).astype(np.int32)
               for n in sizes["hybrid_prompts"]]
    forward = jax.jit(hybrid.build_forward(cfg))
    engine = ContinuousBatchingEngine(
        cfg, params, max_streams=4, steps_per_dispatch=8, temperature=0.0,
        block_tokens=16).start()
    try:
        streams = [engine.submit(p, max_new_tokens=new) for p in prompts]
        worst_lp = worst_gap = 0.0
        for p, st in zip(prompts, streams):
            toks = np.asarray(st.result(timeout=600))
            who = f"{which} prompt of {p.size}"
            check(toks.size == new and (toks >= 0).all()
                  and (toks < cfg.vocab).all() and st.finish_reason == "length",
                  f"{who}: tokens {toks.tolist()} ({st.finish_reason})")
            seq = np.zeros((1, cfg.max_seq), np.int32)
            seq[0, :p.size] = p
            seq[0, p.size:p.size + new - 1] = toks[:-1]
            ref = np.asarray(jax.nn.log_softmax(forward(params, seq)[
                0, p.size - 1:p.size - 1 + new].astype(jnp.float32)))
            at = ref[np.arange(new), toks]
            diff = float(np.abs(at - np.asarray(st.logprobs)).max())
            gap = float((ref.max(axis=1) - at).max())
            check(diff <= LOGPROB_TOL and gap <= LOGPROB_TOL,
                  f"{who}: served logprobs {st.logprobs} against the "
                  f"forward's {at.tolist()} (best {ref.max(axis=1).tolist()})")
            worst_lp, worst_gap = max(worst_lp, diff), max(worst_gap, gap)
        snap = engine._pool.snapshot()
        check(snap["live_blocks"] == 0 and snap["state_slots_live"] == 0,
              f"pool still holds {snap}")
        check(engine.stats["moe_tokens_held"] > 0,
              "no token reached a held expert")
        return {"tokens_vs_forward": {
            "prompts": len(prompts), "tokens_each": new,
            "max_logprob_diff": round(worst_lp, 5),
            "max_gap_to_argmax": round(worst_gap, 5)},
            "state_bytes": snap["state_bytes"],
            "expert_matmul": engine.expert_matmul,
            "state_update": engine.state_update,
            "moe_tokens_held": int(engine.stats["moe_tokens_held"]),
            "moe_tokens_absent": int(engine.stats["moe_tokens_absent"])}
    finally:
        engine.stop()


# --------------------------------------------------------------------------
# driver
# --------------------------------------------------------------------------
def _lingering_threads(grace_s: float = 5.0) -> list:
    """Non-daemon threads still alive after every leg stopped what it
    started — they would keep the process from exiting."""
    deadline = time.monotonic() + grace_s
    while True:
        alive = [t.name for t in threading.enumerate()
                 if t is not threading.main_thread() and t.is_alive()
                 and not t.daemon]
        if not alive or time.monotonic() > deadline:
            return alive
        time.sleep(0.1)


def run(rehearse: bool) -> dict:
    t_start = time.monotonic()
    import jax

    if rehearse:
        if os.environ.get("JAX_PLATFORMS", "").strip().lower() != "cpu":
            sys.exit("chip_smoke: --rehearse-cpu needs JAX_PLATFORMS=cpu "
                     "(it must never take the chip)")
    else:
        require_tpu()
    sizes = REHEARSAL if rehearse else FULL
    on_chip = not rehearse
    device = jax.devices()[0]
    n_devices = len(jax.devices())

    from nnstreamer_tpu import native
    from nnstreamer_tpu.pipeline import continuity

    # (the rehearsal leaves the cache alone: XLA:CPU logs an error for
    # every AOT result it loads back, and CPU entries help nobody)
    cache_dir = None if rehearse else continuity.arm_compile_cache()
    legs: dict = {}

    def leg(name, fn, *args, **kw):
        t0 = time.monotonic()
        print(f"chip_smoke: {name} leg ...", file=sys.stderr, flush=True)
        legs[name] = fn(*args, **kw)
        legs[name]["wall_s"] = round(time.monotonic() - t0, 2)
        print(f"chip_smoke: {name} leg passed in {legs[name]['wall_s']} s",
              file=sys.stderr, flush=True)

    rtt = sync_roundtrip_ms()
    leg("kernel", kernel_leg, sizes, on_chip)
    apply_fn, params = _register_mobilenet()
    frames_u8 = _source_frames(sizes["frames"])
    reference = _reference_labels(apply_fn, params, frames_u8)
    leg("stream", stream_leg, sizes, device.platform, frames_u8, reference)
    leg("server", server_leg, sizes, on_chip)
    leg("hybrid", hybrid_leg, sizes, on_chip)
    leg("hybrid_delta", hybrid_leg, sizes, on_chip, "hybrid_delta")
    mesh = None
    if n_devices >= 4:
        # same reference as the single-device leg, so equal labels
        leg("mesh", stream_leg, sizes, device.platform, frames_u8,
            reference, mesh="dp4")
        mesh = {"spec": "dp4", **legs.pop("mesh")}
    check("tensorflow" not in sys.modules,
          "tensorflow was imported into the process that holds the chip")
    lingering = _lingering_threads()
    check(not lingering, f"threads still running: {lingering}")
    cache = continuity.cache_stats()
    result = {
        "device": {"platform": device.platform, "kind": device.device_kind,
                   "count": n_devices},
        "platform": device.platform,
        "device_kind": device.device_kind,
        "n_devices": n_devices,
        "legs": legs,
        "mesh": mesh,
        "sync_roundtrip_ms": rtt,
        "compile_cache": {"dir": cache_dir,
                          "nns_compile_cache_hits_total": cache["hits"],
                          "nns_compile_cache_misses_total": cache["misses"]},
        "native_transport": native.available(),
        "wall_s": round(time.monotonic() - t_start, 2),
        "claim": None,
    }
    if rehearse:
        return {"rehearsal": "cpu — proves nothing about the chip", **result}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="debug this script on CPU XLA at cut sizes "
                         "(needs JAX_PLATFORMS=cpu; prints no \"ok\")")
    args = ap.parse_args(argv)
    try:
        summary = run(args.rehearse_cpu)
    except Exception:  # noqa: BLE001 — report, then make sure no thread a
        # failed leg left behind can keep the process (and the chip) alive
        traceback.print_exc()
        sys.stderr.flush()
        os._exit(1)
    print(json.dumps(summary), flush=True)
    if not args.rehearse_cpu:
        # the verdict line, last and alone: exactly these keys, nothing a
        # reader of the last line would have to skip over
        print(json.dumps({"ok": True, "device": summary["device"]}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
