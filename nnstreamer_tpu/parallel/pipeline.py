"""Pipeline parallelism — GPipe-style microbatch pipelining over mesh
axis ``pp``, TPU-idiomatic: one SPMD program, stages rotate activations
around the ICI ring with ``lax.ppermute``.

New capability beyond the reference: its "pipeline parallelism" is
dataflow threading of stream elements (SURVEY §2.4.1 — GStreamer queue
decoupling, throughput = slowest stage). Here the model itself is cut into
stages: the stacked layer axis L is sharded over ``pp`` (each stage holds
L/pp contiguous blocks), a batch is split into microbatches, and the
classic pipeline schedule runs for ``num_microbatches + pp - 1`` steps. At
each step every stage applies its local blocks to the microbatch it
currently holds, then ppermutes the activation to the next stage — so the
ICI transfer of step t overlaps the matmuls of step t+1 under XLA's
scheduler, and the bubble fraction is (pp-1)/(num_mb+pp-1).

Composes with the other four axes in ONE jitted program via
partial-manual ``shard_map``: the region is manual over {pp, sp} (ring
attention needs manual sp), while tp/ep/dp stay auto — GSPMD keeps
inserting the Megatron-style all-reduces for tp and the expert all-to-all
for ep inside each stage.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from nnstreamer_tpu.models.transformer import (
    TransformerConfig,
    make_layer_body,
)
from nnstreamer_tpu.parallel.ring import ring_attention


def pipelined_block_forward(cfg: TransformerConfig, mesh: Mesh) -> Callable:
    """Returns ``blocks(stage_params, x, positions) -> y`` where

    - ``x``/``y``: activations ``[num_mb, mb_batch, seq, d_model]``,
    - ``positions``: ``[num_mb, mb_batch, seq]`` global rotary positions,
    - ``stage_params``: stacked layer params whose leading L axis is
      sharded over ``pp`` (each stage sees L/pp locally).

    The returned function is already wrapped in shard_map (manual over
    pp and sp) and must be called under the given mesh (inside jit).
    """
    has_sp = "sp" in mesh.axis_names
    manual = {"pp"} | ({"sp"} if has_sp else set())
    attn = (functools.partial(ring_attention, axis_name="sp", causal=True)
            if has_sp else None)
    layer_body = make_layer_body(cfg, attn)

    def stage_fn(stage_params, x, positions):
        """Apply this stage's local blocks (scan over L/pp layers)."""
        (x, _), _ = lax.scan(layer_body, (x, positions), stage_params)
        return x

    def pipeline(stage_params, x, positions):
        n_stages = lax.psum(1, "pp")
        stage = lax.axis_index("pp")
        num_mb = x.shape[0]
        pos0 = positions[0]          # identical for every microbatch
        state = jnp.zeros_like(x[0])
        out = jnp.zeros_like(x)

        def step(carry, t):
            state, out = carry
            # stage 0 ingests microbatch t (clamped; t >= num_mb steps are
            # drain-only), others take what the ring delivered last step
            inp = lax.dynamic_index_in_dim(
                x, jnp.minimum(t, num_mb - 1), 0, keepdims=False)
            cur = jnp.where(stage == 0, inp, state)
            cur = stage_fn(stage_params, cur, pos0)
            # the microbatch finishing at the last stage this step
            oidx = t - (n_stages - 1)
            valid = jnp.logical_and(stage == n_stages - 1, oidx >= 0)
            slot = jnp.maximum(oidx, 0)
            prev = lax.dynamic_index_in_dim(out, slot, 0, keepdims=False)
            out = lax.dynamic_update_index_in_dim(
                out, jnp.where(valid, cur, prev), slot, 0)
            # rotate activations one stage forward around the ICI ring
            perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
            state = lax.ppermute(cur, "pp", perm)
            return (state, out), None

        (state, out), _ = lax.scan(
            step, (state, out), jnp.arange(num_mb + n_stages - 1))
        # results live on the last stage only; psum == broadcast since all
        # other stages contribute zeros
        return lax.psum(jnp.where(stage == n_stages - 1, out,
                                  jnp.zeros_like(out)), "pp")

    seq_spec = "sp" if has_sp else None
    return jax.shard_map(
        pipeline,
        mesh=mesh,
        axis_names=frozenset(manual),
        in_specs=(
            jax.tree.map(lambda _: P("pp"), _stage_param_tree(cfg)),
            P(None, None, seq_spec, None),
            P(None, None, seq_spec),
        ),
        out_specs=P(None, None, seq_spec, None),
        check_vma=False,
    )


def _stage_param_tree(cfg: TransformerConfig) -> Dict[str, int]:
    """Skeleton pytree matching the stacked layer params (values unused)."""
    keys = ["ln1", "qkv", "proj", "ln2"]
    keys += (["router", "w_in", "w_out"] if cfg.num_experts
             else ["w_in", "w_out"])
    return {k: 0 for k in keys}


def pipeline_param_specs(cfg: TransformerConfig) -> Dict[str, P]:
    """PartitionSpecs for the pipelined model: L axis over ``pp``, tp/ep
    exactly as the GSPMD path (parallel.sharded.transformer_param_specs)."""
    specs = {
        "embed": P(None, "tp"),
        "ln1": P("pp", None),
        "qkv": P("pp", None, None, "tp", None),
        "proj": P("pp", "tp", None, None),
        "ln2": P("pp", None),
        "ln_f": P(None),
    }
    if cfg.num_experts:
        specs["router"] = P("pp", None, "ep")
        specs["w_in"] = P("pp", "ep", None, "tp")
        specs["w_out"] = P("pp", "ep", "tp", None)
    else:
        specs["w_in"] = P("pp", None, "tp")
        specs["w_out"] = P("pp", "tp", None)
    return specs


def build_pipelined_forward(cfg: TransformerConfig, mesh: Mesh,
                            num_microbatches: int) -> Callable:
    """apply_fn(params, tokens[int32 num_mb, mb, s]) -> logits
    [num_mb, mb, s, vocab]. Embedding/unembedding run replicated across pp
    under plain GSPMD; only the block stack is pipelined."""
    dtype = cfg.dtype
    blocks = pipelined_block_forward(cfg, mesh)

    def apply_fn(params, tokens):
        num_mb, mb, s = tokens.shape
        if num_mb != num_microbatches:
            raise ValueError(
                f"tokens leading dim {num_mb} != num_microbatches "
                f"{num_microbatches} the step was built for")
        positions = jnp.broadcast_to(
            jnp.arange(s, dtype=jnp.int32)[None, None, :], tokens.shape)
        x = params["embed"][tokens].astype(dtype)   # [num_mb, mb, s, d]
        stage_params = {k: v for k, v in params.items()
                        if k not in ("embed", "ln_f")}
        x = blocks(stage_params, x, positions)
        from nnstreamer_tpu.models.transformer import _rmsnorm

        x = _rmsnorm(x, params["ln_f"])
        return jnp.einsum("mbsd,vd->mbsv", x.astype(jnp.float32),
                          params["embed"])

    return apply_fn
