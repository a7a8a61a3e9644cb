"""Driver for LM serving cells of the DeepSeek-V2 configuration (latent
attention over a latent paged cache, a leading dense layer, a share of the
routed experts, shared experts, an untied head): the same
``ContinuousBatchingEngine`` under the same closed loop of clients as
``drivers/lm.py``, whose clients, window and bucket list it uses as they
are, and the check of ``drivers/lm_hybrid.py``: its ``serve_check`` (what
the engine serves for the check, at the window's occupancy) is called as it
is, and what was served is held to ONE teacher-forced float32 forward of
this configuration's plain reference (``benchmark/reference_deepseek_v2.py``).

What differs: the ``MLAConfig`` is read from the published ``deepseek_v2``
keys; the bytes of a step come from ``benchmark/work_deepseek_v2.py``
(an expert only if hit, the latent rows by the blocks the program counted);
and the check also reads LATENT ROWS. This family keeps no state per lane,
so ``serve_check`` finds nothing in a lane's slot, and a finished stream's
blocks go back to the pool, where the neighbours' next dispatch takes them:
the rows of a stream served beside 63 others cannot be read after the fact.
``serve_rows`` therefore serves the same check prompts once more, one at a
time on the idle engine, and reads each one's rows out of the blocks it
held (``BlockPool.stream_rows``) before anything else is admitted. Their
tokens and log-probabilities are compared too. Nothing here knows a cell's
name.
"""

from __future__ import annotations

import os
import threading
import time
from collections import Counter

import numpy as np

from benchmark import (
    reference_deepseek_v2,
    scope_reduce,
    trace_reduce,
    traffic,
    work_deepseek_v2,
)
from benchmark.drivers.lm import (
    CHECK_INDEX,
    WARM_INDEX,
    _Client,
    _Window,
    prefill_buckets,
)
from benchmark.drivers.lm_hybrid import _relative, serve_check

LIMITS = ("logprob", "argmax", "logprob_max", "rows", "first_rows")
#: the decode program's leaf scopes, as the cell's ``layer_metrics`` list
#: them: a traced run's detail carries the seconds under each
LEAVES = ("mla_q", "mla_kv", "kv_write", "kv_gather", "attend", "mla_out",
          "dense_ffn", "router", "experts", "shared_ffn", "logits", "sample")


def deepseek_v2_config(config: dict):
    import jax.numpy as jnp

    from nnstreamer_tpu.models.mla import MLAConfig

    dtypes = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
    rope = config["rope_scaling"]
    if config["q_lora_rank"] is not None or config["moe_layer_freq"] != 1 \
            or config["n_group"] != 1 or config["topk_method"] != "greedy" \
            or config["scoring_func"] != "softmax" or config["attention_bias"] \
            or config["hidden_act"] != "silu" or rope["type"] != "yarn" \
            or config["num_key_value_heads"] != config["num_attention_heads"]:
        raise ValueError("lm_deepseek_v2: queries come from one matrix, every "
                         "layer after the dense ones routes greedily over one "
                         "group by softmax scores, and rotary frequencies are "
                         "YaRN's: nothing else is built")
    return MLAConfig(
        vocab=config["vocab_size"], d_model=config["hidden_size"],
        n_layers=config["num_hidden_layers"],
        n_heads=config["num_attention_heads"],
        qk_nope_dim=config["qk_nope_head_dim"],
        qk_rope_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"], kv_lora_rank=config["kv_lora_rank"],
        rope_theta=float(config["rope_theta"]),
        rope_factor=float(rope["factor"]),
        rope_original_max=int(rope["original_max_position_embeddings"]),
        rope_beta_fast=float(rope["beta_fast"]),
        rope_beta_slow=float(rope["beta_slow"]),
        rope_mscale=float(rope["mscale"]),
        rope_mscale_all_dim=float(rope["mscale_all_dim"]),
        first_dense_layers=config["first_k_dense_replace"],
        dense_width=config["intermediate_size"],
        num_experts=config["router_outputs"],
        experts_per_token=config["num_experts_per_tok"],
        expert_width=config["moe_intermediate_size"],
        shared_width=config["n_shared_experts"]
        * config["moe_intermediate_size"],
        experts_held=tuple(config["experts_held"]),
        norm_topk_prob=bool(config["norm_topk_prob"]),
        routed_scaling_factor=float(config["routed_scaling_factor"]),
        rms_eps=float(config["rms_norm_eps"]),
        max_seq=config["max_position_embeddings"],
        dtype=dtypes[config["dtype"]],
        param_dtype=dtypes[config["param_dtype"]])


def serve_rows(engine, cfg, workload, seed) -> list:
    """The check prompts once more, ONE AT A TIME on the idle engine, each
    with the rows its blocks are left with: a record as ``serve_check``
    makes them, ``state`` holding ``rows [layers, prompt + fed, rank +
    rope]``. Alone in the pool, nothing is given the stream's blocks
    between its finish and the read."""
    new, steps = int(workload["check_new_tokens"]), engine.K
    fed = steps * -(-(new - 1) // steps)
    out = []
    for i, n in enumerate(workload["check_prompt_tokens"]):
        prompt = traffic.prompt_tokens(seed, CHECK_INDEX + i, int(n),
                                       cfg.vocab)
        stream = engine.submit(prompt, max_new_tokens=new)
        stream.result(timeout=600)
        rows = engine._pool.stream_rows(stream.blocks, int(n) + fed)
        out.append({"prompt": prompt, "tokens": list(stream.tokens[:new]),
                    "logprobs": list(stream.logprobs[:new]), "lane": None,
                    "reason": stream.finish_reason, "fed": fed,
                    "state": {"rows": rows[:, 0, :, :cfg.row_width]}})
    return out


def compare_check(served: list, params, cfg, workload,
                  reference=None) -> dict:
    """Each record of ``lm_hybrid.serve_check`` and of ``serve_rows``
    against ONE teacher-forced forward of the plain reference over its
    prompt + served tokens (``reference_deepseek_v2.deepseek_v2_check``, or
    ``reference`` in its place: the controls). Over ALL compared tokens:
    the MEAN distance of the log-probability the engine reported from the
    reference's for that token at that position (``logprob_tol``) and the
    mean distance of the served token's reference log-probability from the
    reference's best (``argmax_tol``); the mean, because the sixth of 64
    router outputs lies a hair above the seventh at a few tokens of every
    request and bfloat16 moves that hair. For every request: the largest
    distance of one token's log-probability (``logprob_max_tol``: loose, it
    holds a token gone badly wrong). For every record with rows: ``|rows -
    ref| / |ref|`` over all the tokens and columns of a layer, the largest
    of the layers (``rows_tol``: deep layers inherit the rounding of
    everything before them), and over the FIRST layer alone, whose input
    nothing upstream has rounded, row by row, the largest of the rows
    (``first_rows_tol``: the limit that tells the row's precision, and a
    row that was never written or written in the wrong place).
    ``by_request`` keeps every reading of every request."""
    import jax
    import jax.numpy as jnp

    limits = {k: float(workload[k + "_tol"]) for k in LIMITS}
    pad, new = int(workload["check_pad_to"]), int(workload["check_new_tokens"])
    reference = reference or reference_deepseek_v2.deepseek_v2_check
    ref = jax.jit(lambda p, t, first: reference(p, t, first, new, 0, cfg))
    bad, by_request = [], []

    def hold(who, read):
        bad.extend({**who, "limit": name + "_tol", "read": value}
                   for name, value in read.items()
                   if not value <= limits[name])  # a NaN is over too

    for item in served:
        n, toks = len(item["prompt"]), np.asarray(item["tokens"], np.int64)
        rows = (item["state"] or {}).get("rows")
        who = {"prompt_tokens": n, "lane": item["lane"],
               "alone": rows is not None}
        if len(toks) != new or toks.min() < 0 or toks.max() >= cfg.vocab \
                or item["state"] is not None and item["reason"] != "length":
            bad.append({**who, "reason": item["reason"],
                        "tokens": toks.tolist()})
            continue
        padded = np.zeros(pad, np.int32)
        padded[:n] = item["prompt"]
        padded[n:n + new] = toks  # teacher-forced
        ref_lp, ref_state = ref(params, jnp.asarray(padded), n - 1)
        ref_lp = np.asarray(ref_lp)
        at = ref_lp[np.arange(new), toks]
        off = np.abs(at - np.asarray(item["logprobs"]))
        read = {"logprob_max": off.max()}
        if rows is not None:
            want = np.asarray(ref_state["rows"])[:, :rows.shape[1]]
            read.update(rows=_relative(rows, want, 1).max(),
                        first_rows=_relative(rows[0], want[0], 1).max())
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
            "rows_read": sum(r["alone"] for r in by_request),
            **{k + "_tol": v for k, v in limits.items()},
            "mean_logprob_diff": whole["logprob"],
            "mean_gap_to_argmax": whole["argmax"],
            "max_logprob_diff": worst("logprob_max"),
            "max_rows_diff": worst("rows"),
            "max_first_rows_diff": worst("first_rows"),
            "by_request": by_request, "bad": bad, "ok": not bad}


def serve_for_check(engine, cfg, workload, seed) -> list:
    """Everything the check compares: ``serve_check``'s records (busy
    lanes), then ``serve_rows``'s (alone, with rows)."""
    return serve_check(engine, cfg, workload, seed) \
        + serve_rows(engine, cfg, workload, seed)


def check_served_tokens(engine, params, cfg, workload, seed,
                        reference=None) -> dict:
    return compare_check(serve_for_check(engine, cfg, workload, seed),
                         params, cfg, workload, reference)


def build_engine(config: dict, seed: int, phases: dict):
    """``(cfg, params, engine)``: the configuration's engine, started, with
    the seed's weights; the seconds of both go into ``phases``."""
    import jax

    from nnstreamer_tpu.serving import ContinuousBatchingEngine

    t = time.monotonic()
    cfg = deepseek_v2_config(config)
    params = jax.block_until_ready(cfg.family.init_params(cfg, seed))
    phases["weights_s"] = time.monotonic() - t
    t = time.monotonic()
    engine = ContinuousBatchingEngine(
        cfg, params, max_streams=config["max_streams"],
        steps_per_dispatch=config["steps_per_dispatch"],
        temperature=config["temperature"],
        block_tokens=config["block_tokens"], attention=config["attention"],
        prefix_cache=config["prefix_cache"],
        min_bucket=config["min_bucket"]).start()
    phases["engine_s"] = time.monotonic() - t
    return cfg, params, engine


def run_cell(config: dict, workload: dict, seed: int, seconds: float,
             trace: bool, t0: float, workdir: str) -> dict:
    import jax

    if workload["loop"] != "closed":
        raise ValueError(f"lm_deepseek_v2 driver: loop {workload['loop']!r} "
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
        served = serve_for_check(engine, cfg, workload, seed)
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
            traced = trace_reduce.profile(workdir, span, tick=watch)
        watch(window.t_close)
        stats1 = dict(engine.stats)
        pool = engine._pool.snapshot()
        note_memory("after_window")
    finally:
        stop.set()
        for c in clients:
            if c.stream is not None:
                c.stream.cancel()
        for c in clients:
            c.join(timeout=60)
        engine.stop()
    alive = [c.name for c in clients if c.is_alive()]
    records = [r for c in clients for r in c.requests + (
        [c.current] if c.current else [])]
    arrivals = [t - window.t_open for c in clients for t in c.token_times]
    form = {"decode_attention": engine.decode_attention,
            "expert_matmul": engine.expert_matmul,
            "weights": dict(engine.weights)}
    # the reference's own seconds are no part of the set-up: it runs after
    # the window, on what the check was served before it
    t = time.monotonic()
    check = compare_check(served, params, cfg, workload)
    check["reference_s"] = time.monotonic() - t

    inside = [r for r in records if window.holds(r["submit"])]
    ttft = [1e3 * (r["first"] - r["submit"]) for r in inside
            if r["first"] is not None and r["first"] < window.t_close]
    ended = [r for r in inside
             if r.get("end", window.t_close) < window.t_close]
    bad = [r for r in ended if r["reason"] != "length" or r.get("timed_out")
           or r["received"] != r["want"]]
    tokens = len(arrivals)
    stats = {k: int(stats1[k]) - int(stats0[k]) for k in stats1
             if isinstance(stats1[k], (int, np.integer))}
    steps_run = max(stats["dispatches"] * config["steps_per_dispatch"], 1)
    lanes_live = config["max_streams"] * stats["active_slot_steps"] \
        / max(stats["slot_steps"], 1)
    hit = stats["moe_experts_hit"] / max(stats["moe_layer_steps"], 1)
    blocks_read = stats["kv_blocks_live"] / steps_run
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
            "blocks_read_per_step": blocks_read,
            "kv_bytes_per_token_held": int(stats1["kv_bytes_per_token"]),
            "engine_stats": stats, "engine_form": form, "pool": pool,
            "memory": memory,
            "tokens_by_second": np.bincount(
                np.asarray(arrivals, int)).tolist(),
        },
    }
    if traced is not None:
        work = work_deepseek_v2.decode_bytes_per_step(
            params, cfg, lanes_live=lanes_live, experts_hit_per_layer=hit,
            blocks_read_per_step=blocks_read,
            block_tokens=config["block_tokens"])
        out["detail"]["decode_bytes_by_part"] = work.pop(
            "decode_bytes_by_part")
        traced.update(work)
        out["trace"] = traced
        # the decode program's device seconds by leaf scope, as the share
        # metrics will read them (the reduction is kept: one read a file)
        path = scope_reduce.newest_xplane(workdir)
        found = path and scope_reduce.reduce_file(
            path, os.path.getmtime(path), "jit_dispatch", LEAVES)
        if found:
            out["detail"]["decode_seconds_by_scope"] = found["seconds"]
            out["detail"]["decode_executions"] = found["executions"]
    return out
