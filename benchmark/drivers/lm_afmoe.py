"""Driver for LM serving cells of the AFMoE configuration (window and full
attention layers under sandwich norms, a leading dense layer, a share of
sigmoid-scored routed experts, an untied head over a slice of the
vocabulary): the same ``ContinuousBatchingEngine`` under the same closed
loop of clients as ``drivers/lm.py``, whose clients and window it uses as
they are, and the check of ``drivers/lm_hybrid.py``: its ``serve_check``
(what the engine serves for the check, at the window's occupancy) is called
as it is, and what was served is held to ONE teacher-forced float32 forward
of this configuration's plain reference (``benchmark/reference_afmoe.py``).

What differs: the ``AfmoeConfig`` is read from the published ``afmoe`` keys;
the warm-up runs the buckets the ENGINE makes of the traffic's lengths
(``min_bucket`` is the configuration's); the bytes of a step come from
``benchmark/work_afmoe.py`` (an expert only if hit, the full layers' rows by
``kv_blocks_live`` and the window layers' by ``kv_window_blocks_live``);
and the check also reads the rows of BOTH block arenas. A finished stream's
blocks go back to their pools, where the neighbours' next dispatch takes
them, so ``serve_rows`` serves the same check prompts once more, one at a
time on the idle engine, and reads each one's rows out of the blocks it
held (``BlockPool.stream_rows``): the full layers' whole context, and for
each window layer exactly the positions the next token may read. Nothing
here knows a cell's name.
"""

from __future__ import annotations

import os
import re
import threading
import time
from collections import Counter

import numpy as np

from benchmark import reference_afmoe, scope_reduce, trace_reduce, traffic
from benchmark import work_afmoe
from benchmark.drivers.lm import CHECK_INDEX, WARM_INDEX, _Client, _Window
from benchmark.drivers.lm_hybrid import _relative, serve_check

LIMITS = ("logprob", "argmax", "logprob_max", "rows", "first_rows")
#: the decode program's leaf scopes, as the cell's ``layer_metrics`` list
#: them: a traced run's detail carries the seconds under each
LEAVES = ("qkv", "kv_write", "kv_gather", "attend", "attend_window",
          "attn_out", "dense_ffn", "router", "experts", "shared_ffn",
          "logits", "sample")
#: the band prefill kernel's name in a trace's device operations
BAND_KERNEL = "nns_band_flash_prefill"


def afmoe_config(config: dict):
    import jax.numpy as jnp

    from nnstreamer_tpu.models.afmoe import AfmoeConfig

    dtypes = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
    if config["n_group"] != 1 or config["topk_group"] != 1 \
            or config["score_func"] != "sigmoid" \
            or config["hidden_act"] != "silu" \
            or config["rope_scaling"] is not None \
            or not config["mup_enabled"] or config["tie_word_embeddings"] \
            or config["num_shared_experts"] != 1 \
            or config["num_hidden_layers"] != len(config["layer_types"]):
        raise ValueError("lm_afmoe: every expert layer routes over one "
                         "group by sigmoid scores beside one shared expert, "
                         "rotary frequencies are plain, the embedding is "
                         "scaled and the head untied: nothing else is built")
    return AfmoeConfig(
        vocab=config["vocab_size"], d_model=config["hidden_size"],
        layer_types=tuple(config["layer_types"]),
        num_dense_layers=config["num_dense_layers"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"], window=config["sliding_window"],
        rope_theta=float(config["rope_theta"]),
        dense_width=config["intermediate_size"],
        num_experts=config["router_outputs"],
        experts_per_token=config["num_experts_per_tok"],
        expert_width=config["moe_intermediate_size"],
        shared_width=config["num_shared_experts"]
        * config["moe_intermediate_size"],
        experts_held=tuple(config["experts_held"]),
        score_func=config["score_func"],
        norm_topk_prob=bool(config["route_norm"]),
        routed_scaling_factor=float(config["route_scale"]),
        rms_eps=float(config["rms_norm_eps"]),
        max_seq=config["max_position_embeddings"],
        dtype=dtypes[config["dtype"]],
        param_dtype=dtypes[config["param_dtype"]])


def serve_rows(engine, cfg, workload, seed) -> list:
    """The check prompts once more, ONE AT A TIME on the idle engine, each
    with the rows its blocks of both arenas are left with: a record as
    ``serve_check`` makes them, ``state`` holding ``kv [full layers, 2,
    prompt + fed, kv heads, dim]`` and ``win [window layers, 2, tokens, kv
    heads, dim]`` from position ``win_from`` on (the first position its
    window blocks still hold). Alone in the pool, nothing is given the
    stream's blocks between its finish and the read."""
    new, steps = int(workload["check_new_tokens"]), engine.K
    fed = steps * -(-(new - 1) // steps)
    out = []
    for i, n in enumerate(workload["check_prompt_tokens"]):
        prompt = traffic.prompt_tokens(seed, CHECK_INDEX + i, int(n),
                                       cfg.vocab)
        stream = engine.submit(prompt, max_new_tokens=new)
        stream.result(timeout=600)
        held = int(n) + fed
        first, ids = stream.window_blocks
        start = first * engine.block_tokens
        out.append({
            "prompt": prompt, "tokens": list(stream.tokens[:new]),
            "logprobs": list(stream.logprobs[:new]), "lane": None,
            "reason": stream.finish_reason, "fed": fed,
            "state": {
                "kv": engine._pool.stream_rows(stream.blocks, held),
                "win": engine._pool.stream_rows(ids, held - start,
                                                window=True),
                "win_from": start}})
    return out


def _rows_read(state: dict, want: dict, window: int) -> dict:
    """``rows``: ``|rows - ref| / |ref|`` over all the tokens and columns of
    a layer, the largest of the layers of both kinds: the full layers over
    the whole context, the window layers over exactly the positions the
    next token may read (``held - window + 1 .. held - 1``; a window block
    that was given back too early leaves them unread: infinite).
    ``first_rows``: the same over the first layer alone (a window layer,
    its input rounded by nothing upstream), token by token, the largest."""
    kv, win, start = state["kv"], state["win"], int(state["win_from"])
    held = kv.shape[2]
    oldest = max(0, held - window + 1)
    if start > oldest or start + win.shape[2] != held:
        return {"rows": float("inf"), "first_rows": float("inf")}
    mine = win[:, :, oldest - start:]
    ref_w = np.asarray(want["win"])[:, :, oldest:held]
    ref_kv = np.asarray(want["kv"])[:, :, :held]
    by_token = _relative(np.moveaxis(mine[0], 1, 0),
                         np.moveaxis(ref_w[0], 1, 0), 1)
    return {"rows": max(_relative(kv, ref_kv, 1).max(),
                        _relative(mine, ref_w, 1).max()),
            "first_rows": by_token.max()}


def compare_check(served: list, params, cfg, workload, reference=None,
                  stop_at_bad: bool = False) -> dict:
    """Each record of ``lm_hybrid.serve_check`` and of ``serve_rows``
    against ONE teacher-forced forward of the plain reference over its
    prompt + served tokens (``reference_afmoe.afmoe_check``, or
    ``reference`` in its place: the controls). Over ALL compared tokens:
    the MEAN distance of the log-probability the engine reported from the
    reference's for that token at that position (``logprob_tol``) and the
    mean distance of the served token's reference log-probability from the
    reference's best (``argmax_tol``); the mean, because the fourth of 256
    router scores lies a hair above the fifth at a few tokens of every
    request and bfloat16 moves that hair. For every request: the largest
    distance of one token's log-probability (``logprob_max_tol``: loose, it
    holds a token gone badly wrong). For every record with rows
    (``_rows_read``): ``rows_tol`` and ``first_rows_tol``, the limit that
    tells the rows' precision, and a row that was never written or written
    in the wrong place. ``by_request`` keeps every reading of every
    request. ``stop_at_bad`` (the controls): stop at the first request that
    is over a limit of its own; the means are then over what was compared."""
    import jax
    import jax.numpy as jnp

    limits = {k: float(workload[k + "_tol"]) for k in LIMITS}
    pad, new = int(workload["check_pad_to"]), int(workload["check_new_tokens"])
    reference = reference or reference_afmoe.afmoe_check
    ref = jax.jit(lambda p, t, first: reference(p, t, first, new, 0, cfg))
    bad, by_request = [], []

    def hold(who, read):
        bad.extend({**who, "limit": name + "_tol", "read": value}
                   for name, value in read.items()
                   if not value <= limits[name])  # a NaN is over too

    for item in served:
        if stop_at_bad and bad:
            break
        n, toks = len(item["prompt"]), np.asarray(item["tokens"], np.int64)
        state = item["state"] or {}
        who = {"prompt_tokens": n, "lane": item["lane"],
               "alone": "kv" in state}
        if len(toks) != new or toks.min() < 0 or toks.max() >= cfg.vocab \
                or item["state"] is not None and item["reason"] != "length":
            bad.append({**who, "reason": item["reason"],
                        "tokens": toks.tolist()})
            continue
        padded = np.zeros(pad, np.int32)
        padded[:n] = item["prompt"]
        padded[n:n + new] = toks  # teacher-forced
        ref_lp, ref_rows = ref(params, jnp.asarray(padded), n - 1)
        ref_lp = np.asarray(ref_lp)
        at = ref_lp[np.arange(new), toks]
        off = np.abs(at - np.asarray(item["logprobs"]))
        read = {"logprob_max": off.max()}
        if "kv" in state:
            read.update(_rows_read(state, ref_rows, cfg.window))
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

    return {"requests": len(served), "compared": len(by_request),
            "tokens_each": new,
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
    cfg = afmoe_config(config)
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


def band_prefill(path: str, cfg, peak_flops: float):
    """The band prefill kernel in a trace, by its own name: its calls, their
    device seconds, the operations ``work_afmoe.band_attention_flops`` says
    they had to do (a call's prompt length is in its output's shape) and
    their share of the compute peak; None when the trace has none."""
    ops, _, _ = trace_reduce.read_events(path)
    calls, seconds, flops = 0, 0.0, 0.0
    for _, name, _, dur in ops:
        if not scope_reduce.instruction(name).lstrip("%").startswith(
                BAND_KERNEL):
            continue
        shape = re.search(r"\[(\d+),(\d+),(\d+),(\d+)\]", name)
        if shape is None:
            continue
        calls += 1
        seconds += dur / 1e9
        flops += work_afmoe.band_attention_flops(cfg, int(shape.group(3)))
    if not calls or not seconds:
        return None
    return {"calls": calls, "seconds": seconds, "flops": flops,
            "compute_share_pct": 100.0 * flops / seconds / peak_flops}


def run_cell(config: dict, workload: dict, seed: int, seconds: float,
             trace: bool, t0: float, workdir: str) -> dict:
    import jax

    if workload["loop"] != "closed":
        raise ValueError(f"lm_afmoe driver: loop {workload['loop']!r} is "
                         f"not built")
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
        # warm-up: one request per prefill bucket the engine makes of the
        # traffic's and the check's lengths, each long enough to run the
        # decode program once; no other shape
        t = time.monotonic()
        sizes = traffic.request_sizes(workload, seed)
        steps = config["steps_per_dispatch"]
        lengths = [n for n, _ in sizes] + list(workload["check_prompt_tokens"])
        for b in sorted({engine._bucket(int(n)) for n in lengths}):
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
        clients = [_Client(i, engine, sizes, seed, cfg.vocab, n_clients,
                           window, stop) for i in range(n_clients)]
        for c in clients:
            c.start()
        # the warm part of the loop: the clients fall out of step
        deadline = time.monotonic() + 900
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
    # the stopped engine's arenas make room for the reference, whose own
    # seconds are no part of the set-up: it runs after the window, on what
    # the check was served before it
    engine._pool.arena = None
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
    window_blocks_read = stats["kv_window_blocks_live"] / steps_run
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
            "window_blocks_read_per_step": window_blocks_read,
            "kv_bytes_per_token_held": int(stats1["kv_bytes_per_token"]),
            "kv_window_bytes_per_token_held":
            int(stats1["kv_window_bytes_per_token"]),
            "engine_stats": stats, "engine_form": form, "pool": pool,
            "memory": memory,
            "tokens_by_second": np.bincount(
                np.asarray(arrivals, int)).tolist(),
        },
    }
    if traced is not None:
        work = work_afmoe.decode_bytes_per_step(
            params, cfg, lanes_live=lanes_live, experts_hit_per_layer=hit,
            blocks_read_per_step=blocks_read,
            window_blocks_read_per_step=window_blocks_read,
            block_tokens=config["block_tokens"])
        out["detail"]["decode_bytes_by_part"] = work.pop(
            "decode_bytes_by_part")
        traced.update(work)
        out["trace"] = traced
        # the decode program's device seconds by leaf scope, as the share
        # metrics will read them (the reduction is kept: one read a file),
        # and the band prefill kernel's compute roofline share
        path = scope_reduce.newest_xplane(workdir)
        found = path and scope_reduce.reduce_file(
            path, os.path.getmtime(path), "jit_dispatch", LEAVES)
        if found:
            out["detail"]["decode_seconds_by_scope"] = found["seconds"]
            out["detail"]["decode_executions"] = found["executions"]
        if path:
            from benchmark.run import load_json, peaks_for

            peaks = peaks_for(load_json(os.path.dirname(os.path.dirname(
                os.path.abspath(__file__))), "peaks.json"),
                jax.devices()[0].device_kind)
            out["detail"]["band_prefill"] = band_prefill(
                path, cfg, peaks["flops_per_s"])
    return out
