"""How much work a program has to do, from its shapes: the numerators of the
roofline shares. Kept with the benchmark so that no PR that claims a gain can
change them."""

from __future__ import annotations


def model_flops(apply_fn, params, input_shape) -> float:
    """FLOPs of one call of ``apply_fn(params, float32[input_shape])``, by
    XLA's cost analysis of the lowered (not yet optimised) program: what the
    model asks for, not what the compiler made of it."""
    import jax
    import jax.numpy as jnp

    x = jax.ShapeDtypeStruct(tuple(input_shape), jnp.float32)
    lowered = jax.jit(apply_fn).lower(params, x)
    cost = lowered.cost_analysis() or lowered.compile().cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    return float(cost["flops"])


def kv_bytes_per_token(n_layers: int, n_heads: int, head_dim: int,
                       kv_itemsize: int) -> int:
    """Keys and values of one token over all layers."""
    return 2 * n_layers * n_heads * head_dim * kv_itemsize


def decode_bytes_per_step(param_bytes: int, kv_per_token: int,
                          live_tokens: float) -> float:
    """The least one decode step of a batch has to move through HBM: every
    weight once at its stored width, and the live keys and values of every
    sequence in the batch once. This is the MEMORY roofline of decoding; the
    activations and the new token's writes are left out as negligible."""
    return float(param_bytes) + float(kv_per_token) * float(live_tokens)
