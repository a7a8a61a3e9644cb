"""The one general generator of request traffic. A traffic mix is data: the
distributions of prompt and output lengths, the number of requests in the
set, the number of clients. Every seed gets the SAME set of sizes, in another
order and with other token ids, so that two seeds differ in what the run
happens to reach, not in the work on offer."""

from __future__ import annotations

import math

import numpy as np

SIZES_SEED = 0  # pairs prompt with output lengths; the same for every run


def stratified(spec: dict, n: int) -> np.ndarray:
    """``n`` whole numbers at the quantiles ``(i + 0.5) / n`` of the
    distribution: its shape without a draw's luck."""
    q = (np.arange(n) + 0.5) / n
    lo, hi = float(spec["min"]), float(spec["max"])
    kind = spec["distribution"]
    if kind == "log_uniform":
        v = np.exp(math.log(lo) + q * (math.log(hi) - math.log(lo)))
    elif kind == "uniform":
        v = lo + q * (hi - lo)
    elif kind == "fixed":
        v = np.full(n, lo)
    else:
        raise ValueError(f"traffic: unknown distribution {kind!r}")
    return np.clip(np.rint(v), lo, hi).astype(np.int64)


def request_sizes(workload: dict, seed: int) -> list:
    """``requests`` pairs ``(prompt_tokens, output_tokens)``: one fixed set,
    paired once, in an order that the seed decides."""
    n = int(workload["requests"])
    prompts = stratified(workload["prompt_tokens"], n)
    outputs = stratified(workload["output_tokens"], n)
    outputs = outputs[np.random.default_rng(SIZES_SEED).permutation(n)]
    order = np.random.default_rng(seed).permutation(n)
    return [(int(prompts[i]), int(outputs[i])) for i in order]


def prompt_tokens(seed: int, index: int, length: int, vocab: int) -> np.ndarray:
    """Random token ids of request ``index``; never id 0 (padding)."""
    rng = np.random.default_rng([seed, index])
    return rng.integers(1, vocab, length, dtype=np.int32)
