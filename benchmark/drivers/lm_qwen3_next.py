"""Driver for LM serving cells of the Qwen3-Next configuration (gated delta
rule and gated attention mixers, a share of 512 routed experts, a gated
shared expert, an untied head): the same ``ContinuousBatchingEngine`` under
the same closed loop of clients as ``drivers/lm.py``, whose clients, window
and bucket list it uses as they are, and the same check as
``drivers/lm_hybrid.py``: its ``serve_check`` (what the engine serves for
the check, at the window's occupancy) is called as it is, and what was
served is held to ONE teacher-forced float32 forward of this
configuration's plain reference (``benchmark/reference_qwen3_next.py``).

What differs: the ``HybridConfig`` is read from the published ``qwen3_next``
keys; the bytes of a step come from ``benchmark/work_qwen3_next.py``, which
counts an expert only if the program's counter says it was hit; and the
comparison (``compare_check``) judges the served tokens by their MEAN
where the hybrid driver's takes the largest: a router of 512 outputs picks
ten whose last lies a hair above the eleventh, bfloat16 activations move
that hair at a few tokens of every request, and such a token reads 0.2 to
0.9 in its log-probability whatever the model does (the largest of 64
tokens told rotary positions over the whole head from none at all); the
mean over all compared tokens tells them apart at a tenth of that. Nothing
here knows a cell's name.
"""

from __future__ import annotations

import threading
import time
from collections import Counter

import numpy as np

from benchmark import (
    reference_qwen3_next,
    trace_reduce,
    traffic,
    work_qwen3_next,
)
from benchmark.drivers.lm import WARM_INDEX, _Client, _Window, prefill_buckets
from benchmark.drivers.lm_hybrid import _relative, serve_check


def qwen3_next_config(config: dict):
    import jax.numpy as jnp

    from nnstreamer_tpu.models.hybrid import ATTENTION, DELTA, HybridConfig

    dtypes = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
    if config["mlp_only_layers"] or config["decoder_sparse_step"] != 1 \
            or not config["norm_topk_prob"] or config["rope_scaling"] \
            or config["use_sliding_window"] or config["hidden_act"] != "silu":
        raise ValueError("lm_qwen3_next: every layer ends in the expert "
                         "layer, gates are normalised over the chosen, "
                         "rotary positions are unscaled and no window "
                         "slides: nothing else is built")
    every = config["full_attention_interval"]
    return HybridConfig(
        vocab=config["vocab_size"], d_model=config["hidden_size"],
        layer_types=tuple(ATTENTION if (i + 1) % every == 0 else DELTA
                          for i in range(config["num_hidden_layers"])),
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        attention_scale=config["head_dim"] ** -0.5,
        rotary_dim=int(config["head_dim"] * config["partial_rotary_factor"]),
        rope_theta=float(config["rope_theta"]), qk_norm=True, attn_gate=True,
        la_key_heads=config["linear_num_key_heads"],
        la_value_heads=config["linear_num_value_heads"],
        la_key_dim=config["linear_key_head_dim"],
        la_value_dim=config["linear_value_head_dim"],
        la_conv=config["linear_conv_kernel_dim"],
        la_chunk=config["linear_chunk_size"],
        num_experts=config["router_outputs"],
        experts_per_token=config["num_experts_per_tok"],
        expert_width=config["moe_intermediate_size"],
        shared_width=config["shared_expert_intermediate_size"],
        shared_gate=True, experts_held=tuple(config["experts_held"]),
        tie_embeddings=bool(config["tie_word_embeddings"]),
        embedding_multiplier=1.0, residual_multiplier=1.0, logits_scaling=1.0,
        rms_eps=float(config["rms_norm_eps"]),
        max_seq=config["max_position_embeddings"],
        dtype=dtypes[config["dtype"]],
        param_dtype=dtypes[config["param_dtype"]],
        ssm_state_dtype=dtypes[config["ssm_state_dtype"]])


def compare_check(served: list, params, cfg, workload,
                  reference=None) -> dict:
    """Each record of ``lm_hybrid.serve_check`` against ONE teacher-forced
    forward of the plain reference over its prompt + served tokens
    (``reference_qwen3_next.qwen3_next_check``, or ``reference`` in its
    place: the controls). Over ALL compared tokens (the requests serve as
    many each): the MEAN distance of the log-probability the engine
    reported from the reference's for that token at that position
    (``logprob_tol``) and the mean distance of the served token's reference
    log-probability from the reference's best (``argmax_tol``). For every
    request: the largest distance of one token's log-probability
    (``logprob_max_tol``: loose, it holds a token gone badly wrong). For
    every check lane, what its slot held at the end against the reference's
    state after the same tokens, as ``lm_hybrid.compare_check`` reads it:
    head by head of every delta-rule layer (``state_tol``), of the FIRST
    alone, whose input nothing upstream has rounded (``first_state_tol``:
    the limit that tells the state's precision), and layer by layer for the
    convolution's tail (``conv_tol``). ``by_request`` keeps every reading
    of every request, for the record of both readings behind a limit."""
    import jax
    import jax.numpy as jnp

    limits = {k: float(workload[k + "_tol"]) for k in (
        "logprob", "argmax", "logprob_max", "state", "first_state", "conv")}
    pad, new = int(workload["check_pad_to"]), int(workload["check_new_tokens"])
    reference = reference or reference_qwen3_next.qwen3_next_check
    ref = jax.jit(lambda p, t, first, stop: reference(p, t, first, new, stop,
                                                      cfg))
    bad, by_request = [], []

    def hold(who, read):
        bad.extend({**who, "limit": name + "_tol", "read": value}
                   for name, value in read.items()
                   if not value <= limits[name])  # a NaN is over too

    for item in served:
        n, toks = len(item["prompt"]), np.asarray(item["tokens"], np.int64)
        who = {"prompt_tokens": n, "lane": item["lane"]}
        if len(toks) != new or toks.min() < 0 or toks.max() >= cfg.vocab \
                or item["state"] is not None and item["reason"] != "length":
            bad.append({**who, "reason": item["reason"],
                        "tokens": toks.tolist()})
            continue
        padded = np.zeros(pad, np.int32)
        padded[:n] = item["prompt"]
        padded[n:n + new] = toks  # teacher-forced
        ref_lp, ref_state = ref(params, jnp.asarray(padded), n - 1,
                                n + item["fed"])
        ref_lp = np.asarray(ref_lp)
        at = ref_lp[np.arange(new), toks]
        off = np.abs(at - np.asarray(item["logprobs"]))
        read = {"logprob_max": off.max()}
        if item["state"] is not None:
            heads = _relative(item["state"]["ssm"],
                              np.asarray(ref_state["ssm"]), 2)
            read.update(state=heads.max(), first_state=heads[0].max(),
                        conv=_relative(item["state"]["conv"],
                                       np.asarray(ref_state["conv"]),
                                       1).max())
        read = {k: float(v) for k, v in read.items()}
        hold(who, read)
        by_request.append({**who, "logprob": float(off.mean()),
                           "argmax": float((ref_lp.max(axis=1) - at).mean()),
                           **read})
    whole = {k: float(np.mean([r[k] for r in by_request])) if by_request
             else float("nan") for k in ("logprob", "argmax")}
    hold({"requests": len(by_request)}, whole)

    def worst(name):
        return max((r[name] for r in by_request if name in r), default=0.0)

    return {"requests": len(served), "tokens_each": new,
            "lanes": [item["lane"] for item in served],
            **{k + "_tol": v for k, v in limits.items()},
            "mean_logprob_diff": whole["logprob"],
            "mean_gap_to_argmax": whole["argmax"],
            "max_logprob_diff": worst("logprob_max"),
            "max_state_diff": worst("state"),
            "max_first_state_diff": worst("first_state"),
            "max_conv_diff": worst("conv"), "by_request": by_request,
            "bad": bad, "ok": not bad}


def check_served_tokens(engine, params, cfg, workload, seed,
                        reference=None) -> dict:
    return compare_check(serve_check(engine, cfg, workload, seed), params,
                         cfg, workload, reference)


def build_engine(config: dict, seed: int, phases: dict):
    """``(cfg, params, engine)``: the configuration's engine, started, with
    the seed's weights; the seconds of both go into ``phases``."""
    import jax

    from nnstreamer_tpu.serving import ContinuousBatchingEngine

    t = time.monotonic()
    cfg = qwen3_next_config(config)
    params = jax.block_until_ready(cfg.family.init_params(cfg, seed))
    phases["weights_s"] = time.monotonic() - t
    t = time.monotonic()
    engine = ContinuousBatchingEngine(
        cfg, params, max_streams=config["max_streams"],
        steps_per_dispatch=config["steps_per_dispatch"],
        temperature=config["temperature"],
        block_tokens=config["block_tokens"], attention=config["attention"],
        prefix_cache=config["prefix_cache"]).start()
    phases["engine_s"] = time.monotonic() - t
    return cfg, params, engine


def run_cell(config: dict, workload: dict, seed: int, seconds: float,
             trace: bool, t0: float, workdir: str) -> dict:
    import jax

    if workload["loop"] != "closed":
        raise ValueError(f"lm_qwen3_next driver: loop {workload['loop']!r} "
                         f"is not built")
    phases = {}
    cfg, params, engine = build_engine(config, seed, phases)
    n_clients = int(workload["clients"])
    window = _Window()
    stop = threading.Event()
    clients = []
    traced = None
    memory = {}

    def note_memory(when):
        stats = jax.devices()[0].memory_stats() or {}
        memory[when] = {k: int(stats[k]) for k in (
            "bytes_in_use", "peak_bytes_in_use", "bytes_reserved")
            if k in stats}

    try:
        # warm-up: one request per prefill bucket the lengths can reach, each
        # long enough to run the decode program once; no other shape
        t = time.monotonic()
        spec = workload["prompt_tokens"]
        steps = config["steps_per_dispatch"]
        for b in prefill_buckets(int(spec["min"]), int(spec["max"]),
                                 cfg.max_seq):
            n = min(b, cfg.max_seq - 1 - steps)
            engine.generate(
                traffic.prompt_tokens(seed, WARM_INDEX + b, n, cfg.vocab),
                max_new_tokens=steps + 1, timeout=1100)
        phases["warm_s"] = time.monotonic() - t
        note_memory("after_warm")
        t = time.monotonic()
        served = serve_check(engine, cfg, workload, seed)
        phases["check_s"] = time.monotonic() - t
        note_memory("after_check")

        t = time.monotonic()
        sizes = traffic.request_sizes(workload, seed)
        clients = [_Client(i, engine, sizes, seed, cfg.vocab, n_clients,
                           window, stop) for i in range(n_clients)]
        for c in clients:
            c.start()
        # the warm part of the loop: the clients fall out of step
        deadline = time.monotonic() + 600
        while sum(len(c.requests) for c in clients) \
                < int(workload["warm_requests"]):
            if time.monotonic() > deadline:
                raise RuntimeError("lm loop never warmed")
            time.sleep(0.01)
        phases["ramp_s"] = time.monotonic() - t
        note_memory("after_ramp")
        stats0 = dict(engine.stats)
        window.t_close = time.monotonic() + seconds
        window.t_open = window.t_close - seconds
        timeout_s = float(workload["request_timeout_s"])
        live = []   # context tokens held in the pool, sampled while traced

        def watch(until=None):
            """Cancel any request older than the limit; sleep on to
            ``until``."""
            while True:
                now = time.monotonic()
                for c in clients:
                    c.cancel_if_older(timeout_s, now)
                if until is None or now >= until:
                    return
                time.sleep(min(0.02, until - now))

        if trace:
            span = min(float(workload["trace_seconds"]), seconds)
            watch(window.t_open + (seconds - span) / 2)

            def sample():
                watch()
                live.append(sum(c.live_tokens() for c in clients))

            traced = trace_reduce.profile(workdir, span, tick=sample)
        watch(window.t_close)
        stats1 = dict(engine.stats)
        pool = engine._pool.snapshot()
    finally:
        stop.set()
        for c in clients:
            if c.stream is not None:
                c.stream.cancel()
        for c in clients:
            c.join(timeout=60)
        engine.stop()
    alive = [c.name for c in clients if c.is_alive()]
    # the reference's own seconds are no part of the set-up: it runs after
    # the window, on what the check was served before it
    t = time.monotonic()
    check = compare_check(served, params, cfg, workload)
    check["reference_s"] = time.monotonic() - t

    records = [r for c in clients for r in c.requests + (
        [c.current] if c.current else [])]
    inside = [r for r in records if window.holds(r["submit"])]
    ttft = [1e3 * (r["first"] - r["submit"]) for r in inside
            if r["first"] is not None and r["first"] < window.t_close]
    ended = [r for r in inside
             if r.get("end", window.t_close) < window.t_close]
    bad = [r for r in ended if r["reason"] != "length" or r.get("timed_out")
           or r["received"] != r["want"]]
    arrivals = [t - window.t_open for c in clients for t in c.token_times]
    tokens = len(arrivals)
    stats = {k: int(stats1[k]) - int(stats0[k]) for k in stats1
             if isinstance(stats1[k], (int, np.integer))}
    lanes_live = config["max_streams"] * stats["active_slot_steps"] \
        / max(stats["slot_steps"], 1)
    hit = stats["moe_experts_hit"] / max(stats["moe_layer_steps"], 1)
    out = {
        "correct": bool(check["ok"] and not bad and not alive and tokens > 0
                        and len(ttft) > 0),
        "attempted": len(inside),
        "failed": len(bad),
        "end_to_end": {
            "lm_tokens_per_s": tokens / seconds,
            "setup_s": window.t_open - t0,
        },
        "engine_stats": stats,
        "detail": {
            "check": check, "setup_phases": phases,
            "tokens_in_window": tokens, "requests_submitted": len(inside),
            "requests_finished": len(ended),
            "ttft_samples": len(ttft),
            "ttft_p50_ms": float(np.median(ttft)) if ttft else None,
            "ttft_p90_ms": float(np.percentile(ttft, 90)) if ttft else None,
            "ttft_max_ms": max(ttft, default=None),
            "finish_reasons": dict(Counter(str(r["reason"]) for r in ended)),
            "failed_requests": bad[:4], "clients_left_running": alive,
            "mean_prompt_tokens": float(np.mean([r["prompt"] for r in inside]))
            if inside else None,
            "lanes_live_mean": lanes_live, "experts_hit_per_layer": hit,
            "engine_stats": stats, "pool": pool, "memory": memory,
            "tokens_by_second": np.bincount(
                np.asarray(arrivals, int)).tolist(),
        },
    }
    if traced is not None:
        traced["live_tokens_mean"] = float(np.mean(live)) if live else 0.0
        work = work_qwen3_next.decode_bytes_per_step(
            params, cfg, lanes_live=lanes_live, experts_hit_per_layer=hit,
            live_tokens=traced["live_tokens_mean"])
        out["detail"]["decode_bytes_by_part"] = work.pop(
            "decode_bytes_by_part")
        traced.update(work)
        out["trace"] = traced
    return out
