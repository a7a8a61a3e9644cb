"""Driver for LM serving cells of the SambaY configuration (Mamba-1 and
window differential attention below ONE full-attention layer whose cache
the cross-decoder's layers read, gated memory units, a tied head over the
whole vocabulary): the same ``ContinuousBatchingEngine`` under the same
closed loop of clients as ``drivers/lm.py``, whose clients and window it
uses as they are, and the check of ``drivers/lm_hybrid.py``: its
``serve_check`` (what the engine serves for the check, at the window's
occupancy, with the lanes' states read) is called as it is, and what was
served is held to ONE teacher-forced float32 forward of this
configuration's plain reference (``benchmark/reference_sambay.py``).

What differs: the ``SambaYConfig`` is read from the published ``phi4flash``
keys and the ``mamba_*`` sizes the file assumes; the warm-up runs the
buckets the ENGINE makes of the traffic's lengths; the bytes of a step come
from ``benchmark/work_sambay.py``; and the check reads all THREE kinds of
lane memory: ``serve_rows`` serves the check prompts once more, one at a
time on the idle engine, and reads each one's rows out of the blocks it
held in both arenas and its states out of its lane's slot. Nothing here
knows a cell's name.
"""

from __future__ import annotations

import os
import threading
import time
from collections import Counter

import numpy as np

from benchmark import reference_sambay, scope_reduce, trace_reduce, traffic
from benchmark import work_sambay
from benchmark.drivers.lm import CHECK_INDEX, WARM_INDEX, _Client, _Window
from benchmark.drivers.lm_hybrid import _relative, serve_check

LIMITS = ("logprob", "argmax", "logprob_max", "rows", "first_rows", "state",
          "first_state", "conv")
#: the decode program's leaf scopes, as the cell's ``layer_metrics`` list
#: them: a traced run's detail carries the seconds under each
LEAVES = ("ssm_in", "ssm_conv", "ssm_dt", "ssm_update", "ssm_out", "qkv",
          "kv_write", "kv_gather", "attend_window", "attend", "attend_cross",
          "diff_out", "attn_out", "gmu", "dense_ffn", "logits", "sample")


def sambay_config(config: dict):
    import jax.numpy as jnp

    from nnstreamer_tpu.models.sambay import SambaYConfig

    dtypes = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
    if config["model_type"] != "phi4flash" or config["mb_per_layer"] != 2 \
            or config["hidden_act"] != "silu" \
            or not config["tie_word_embeddings"] or config["mlp_bias"] \
            or config["lm_head_bias"] \
            or config["hidden_size"] % config["num_attention_heads"]:
        raise ValueError("lm_sambay: every other layer is of the Mamba "
                         "class, the MLP is gated by silu with no bias and "
                         "the head is the embedding: nothing else is built")
    return SambaYConfig(
        vocab=config["vocab_size"], d_model=config["hidden_size"],
        n_layers=config["num_hidden_layers"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        head_dim=config["hidden_size"] // config["num_attention_heads"],
        window=config["sliding_window"], d_ff=config["intermediate_size"],
        ssm_inner=config["mamba_expand"] * config["hidden_size"],
        ssm_state=config["mamba_d_state"], ssm_conv=config["mamba_d_conv"],
        dt_rank=config["mamba_dt_rank"], ssm_chunk=config["ssm_chunk"],
        ln_eps=float(config["layer_norm_eps"]),
        max_seq=config["max_position_embeddings"],
        dtype=dtypes[config["dtype"]],
        param_dtype=dtypes[config["param_dtype"]],
        ssm_state_dtype=dtypes[config["ssm_state_dtype"]])


def serve_rows(engine, cfg, workload, seed) -> list:
    """The check prompts once more, ONE AT A TIME on the idle engine, each
    with what its lane is left with: a record as ``serve_check`` makes
    them, ``state`` holding ``kv [1, 2, prompt + fed, kv pairs, 128]``,
    ``win [window layers, 2, tokens, kv pairs, 128]`` from position
    ``win_from`` on (the first position its window blocks still hold) and
    the lane's ``ssm`` and ``conv``. Alone in the pool, nothing is given
    the stream's blocks or its lane between its finish and the read."""
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
                "win_from": start,
                **engine._pool.lane_state(stream.lane)}})
    return out


def _pairs(rows, cfg):
    """The reference's ``[.., kv heads, head_dim]`` as the pair entries the
    arenas hold, ``[.., kv pairs, 2 head_dim]``: the same numbers in the
    same order."""
    rows = np.asarray(rows)
    return rows.reshape(rows.shape[:-2] + (cfg.kv_pairs, cfg.pair_width))


def _rows_read(state: dict, want: dict, cfg) -> dict:
    """``rows``: ``|rows - ref| / |ref|`` over all the tokens and columns of
    a layer, the largest of the nine layers that own a cache: the full
    layer over the whole context, the window layers over exactly the
    positions the next token may read (``held - window + 1 .. held - 1``;
    a window block that was given back too early leaves them unread:
    infinite). ``first_rows``: the same over the first window layer alone
    (its input has passed one Mamba layer and nothing else), token by
    token, the largest."""
    kv, win, start = state["kv"], state["win"], int(state["win_from"])
    held = kv.shape[2]
    oldest = max(0, held - cfg.window + 1)
    if start > oldest or start + win.shape[2] != held:
        return {"rows": float("inf"), "first_rows": float("inf")}
    mine = win[:, :, oldest - start:]
    ref_w = _pairs(want["win"], cfg)[:, :, oldest:held]
    ref_kv = _pairs(want["kv"], cfg)[:, :, :held]
    by_token = _relative(np.moveaxis(mine[0], 1, 0),
                         np.moveaxis(ref_w[0], 1, 0), 1)
    return {"rows": max(_relative(kv, ref_kv, 1).max(),
                        _relative(mine, ref_w, 1).max()),
            "first_rows": by_token.max()}


def _state_read(state: dict, want: dict, cfg) -> dict:
    """``state``: ``|S - S_ref| / |S_ref|`` of each Mamba layer's state, the
    largest (deep layers inherit the rounding of everything before them:
    loose, it holds the hand-over and the update); ``first_state``: the
    FIRST layer's alone, whose input nothing upstream has rounded (tight
    enough to tell the state's precision); ``conv``: the same of each
    layer's convolution tail."""
    ref = np.asarray(want["ssm"]).transpose(0, 2, 1)         # [L,n,inner]
    mine = np.asarray(state["ssm"], np.float32)               # [L,blk,n,c]
    mine = mine.transpose(0, 2, 1, 3).reshape(ref.shape)
    by_layer = _relative(mine, ref, 1)
    return {"state": by_layer.max(), "first_state": by_layer[0],
            "conv": _relative(state["conv"], np.asarray(want["conv"]),
                              1).max()}


def compare_check(served: list, params, cfg, workload, wrong=None,
                  stop_at_bad: bool = False) -> dict:
    """Each record of ``lm_hybrid.serve_check`` and of ``serve_rows``
    against ONE teacher-forced forward of the plain reference over its
    prompt + served tokens (``reference_sambay.sambay_check``; ``wrong``
    names the WRONG models to switch on: the controls; the switches are
    traced, so one compiled reference serves them all). Over ALL compared
    tokens: the MEAN distance of the log-probability the engine reported
    from the reference's for that token at that position (``logprob_tol``)
    and the mean distance of the served token's reference log-probability
    from the reference's best (``argmax_tol``). For every request: the
    largest distance of one token's log-probability (``logprob_max_tol``:
    loose, it holds a token gone badly wrong). For every record with rows
    (``_rows_read``): ``rows_tol`` and ``first_rows_tol``; for every record
    with a lane's state, beside busy lanes and alone (``_state_read``):
    ``state_tol``, ``first_state_tol`` and ``conv_tol``. ``by_request``
    keeps every reading of every request. ``stop_at_bad`` (the controls):
    stop at the first request that is over a limit of its own; the means
    are then over what was compared."""
    import jax
    import jax.numpy as jnp

    limits = {k: float(workload[k + "_tol"]) for k in LIMITS}
    pad, new = int(workload["check_pad_to"]), int(workload["check_new_tokens"])
    switches = {name: name in (wrong or ()) for name in reference_sambay.WRONG}
    ref = jax.jit(lambda p, t, first, stop, switches:
                  reference_sambay.sambay_check(p, t, first, new, stop, cfg,
                                                **switches))
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
        ref_lp, left = ref(params, jnp.asarray(padded), n - 1,
                           n + item["fed"], switches)
        ref_lp = np.asarray(ref_lp)
        at = ref_lp[np.arange(new), toks]
        off = np.abs(at - np.asarray(item["logprobs"]))
        read = {"logprob_max": off.max()}
        if "kv" in state:
            read.update(_rows_read(state, left, cfg))
        if "ssm" in state:
            read.update(_state_read(state, left, cfg))
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
            "states_read": sum("state" in r for r in by_request),
            **{k + "_tol": v for k, v in limits.items()},
            "mean_logprob_diff": whole["logprob"],
            "mean_gap_to_argmax": whole["argmax"],
            "max_logprob_diff": worst("logprob_max"),
            "max_rows_diff": worst("rows"),
            "max_first_rows_diff": worst("first_rows"),
            "max_state_diff": worst("state"),
            "max_first_state_diff": worst("first_state"),
            "max_conv_diff": worst("conv"),
            "by_request": by_request, "bad": bad, "ok": not bad}


def serve_for_check(engine, cfg, workload, seed) -> list:
    """Everything the check compares: ``serve_check``'s records (busy
    lanes, the check lanes' states), then ``serve_rows``'s (alone, with
    rows and states)."""
    return serve_check(engine, cfg, workload, seed) \
        + serve_rows(engine, cfg, workload, seed)


def check_served_tokens(engine, params, cfg, workload, seed,
                        wrong=None) -> dict:
    return compare_check(serve_for_check(engine, cfg, workload, seed),
                         params, cfg, workload, wrong)


def build_engine(config: dict, seed: int, phases: dict, params=None,
                 cfg_of=None):
    """``(cfg, params, engine)``: the configuration's engine, started, with
    the seed's weights (or ``params``, made before); the seconds of both go
    into ``phases``. ``cfg_of`` (the controls) turns the configuration
    before the engine is built from it."""
    import jax

    from nnstreamer_tpu.serving import ContinuousBatchingEngine

    t = time.monotonic()
    cfg = sambay_config(config)
    if cfg_of is not None:
        cfg = cfg_of(cfg)
    if params is None:
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
        raise ValueError(f"lm_sambay driver: loop {workload['loop']!r} is "
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
            "state_update": engine.state_update,
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
    blocks_read = stats["kv_blocks_live"] / steps_run
    shared_reads = stats["kv_shared_reads"] / steps_run
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
            "lanes_live_mean": lanes_live,
            "blocks_read_per_step": blocks_read,
            "shared_reads_per_step": shared_reads,
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
        work = work_sambay.decode_bytes_per_step(
            params, cfg, lanes_live=lanes_live,
            blocks_read_per_step=blocks_read,
            shared_reads_per_step=shared_reads,
            window_blocks_read_per_step=window_blocks_read,
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
