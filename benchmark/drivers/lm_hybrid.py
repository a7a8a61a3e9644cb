"""Driver for LM serving cells of the hybrid family (state-space and
attention mixers, a share of the routed experts): the same
``ContinuousBatchingEngine`` under the same closed loop of clients as
``drivers/lm.py``, whose clients, window and bucket list it uses as they
are.

What differs from ``lm.py``: the configuration is a ``HybridConfig`` read
from the published keys; the weights come from the family's own
``init_params`` (made on the device leaf by leaf); and the check holds
EVERY served token of a few seeded prompts, and the recurrent state their
lanes are left with, to the plain reference
(``benchmark/reference_hybrid.py``): one teacher-forced reference forward
over prompt + served tokens gives the log-probabilities at each served
position and the state after the last of them, so prefill, the state
hand-over at the prompt's true last token and the decode steps through
both arenas are all compared, with every other lane busy
(``serve_check``). The reference runs after the window, so its seconds
are no part of ``setup_s``. Nothing here knows a cell's name.
"""

from __future__ import annotations

import itertools
import threading
import time
from collections import Counter

import numpy as np

from benchmark import reference_hybrid, trace_reduce, traffic, work_hybrid
from benchmark.drivers.lm import (
    CHECK_INDEX,
    WARM_INDEX,
    _Client,
    _Window,
    prefill_buckets,
)

#: where the check's filler requests draw their token ids
FILL_INDEX = 3 * 10 ** 9


def hybrid_config(config: dict):
    import jax.numpy as jnp

    from nnstreamer_tpu.models.hybrid import HybridConfig

    dtypes = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
    if config["mamba_n_groups"] != 1 or config["mamba_expand"] * \
            config["hidden_size"] != config["mamba_n_heads"] * \
            config["mamba_d_head"]:
        raise ValueError("lm_hybrid: the mixer is built for one group and "
                         "heads x head size = expand x hidden size")
    return HybridConfig(
        vocab=config["vocab_size"], d_model=config["hidden_size"],
        layer_types=tuple(config["layer_types"]),
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        attention_scale=config["attention_multiplier"],
        ssm_heads=config["mamba_n_heads"],
        ssm_head_dim=config["mamba_d_head"],
        ssm_state=config["mamba_d_state"], ssm_conv=config["mamba_d_conv"],
        ssm_chunk=config["mamba_chunk_size"],
        num_experts=config["router_outputs"],
        experts_per_token=config["num_experts_per_tok"],
        expert_width=config["intermediate_size"],
        shared_width=config["shared_intermediate_size"],
        experts_held=tuple(config["experts_held"]),
        embedding_multiplier=float(config["embedding_multiplier"]),
        residual_multiplier=float(config["residual_multiplier"]),
        logits_scaling=float(config["logits_scaling"]),
        rms_eps=float(config["rms_norm_eps"]),
        max_seq=config["max_position_embeddings"],
        dtype=dtypes[config["dtype"]],
        param_dtype=dtypes[config["param_dtype"]],
        ssm_state_dtype=dtypes[config["ssm_state_dtype"]])


def _wait(done, what: str, seconds: float = 600.0) -> None:
    deadline = time.monotonic() + seconds
    while not done():
        if time.monotonic() > deadline:
            raise RuntimeError(f"lm_hybrid check: {what}")
        time.sleep(0.005)


def serve_check(engine, cfg, workload, seed) -> list:
    """What the engine serves for the check, at the window's occupancy and
    in lanes other than the first. Every lane is first given a seeded
    filler request of the traffic's own prompt lengths, with as many output
    tokens as its window holds. When each has its first token, as many
    fillers as there are check prompts are cancelled, spread evenly over
    the lanes, and the check prompts (``check_new_tokens`` tokens each)
    are admitted to the lanes they leave: their decode steps run beside
    live neighbours, in slots another request used before them. Then the
    fillers are cancelled; with the engine idle, each check lane's slot
    of the state arena is read as the stream's last dispatch left it.

    One record a compared request: the check prompts (with ``state``) and
    the filler beside each (its first ``check_new_tokens`` tokens)."""
    new, steps, lanes = int(workload["check_new_tokens"]), engine.K, engine.B
    lengths = [int(n) for n in workload["check_prompt_tokens"]]
    # a finished stream's lane has run whole dispatches: its state has
    # taken in the first token and all that those dispatches sampled
    fed = steps * -(-(new - 1) // steps)
    if fed > new or len(lengths) > lanes:
        raise ValueError(
            f"lm_hybrid check: {new} tokens end inside a dispatch of "
            f"{steps} steps (its state has taken in {fed}), or more check "
            f"prompts than lanes")
    sizes = traffic.request_sizes(workload, seed)

    def submit(index, n, tokens):
        prompt = traffic.prompt_tokens(seed, index, n, cfg.vocab)
        return prompt, engine.submit(prompt, max_new_tokens=tokens)

    fillers = [submit(FILL_INDEX + i, n, cfg.max_seq - n - steps - 1)
               for i, (n, _) in zip(range(lanes), itertools.cycle(sizes))]
    _wait(lambda: all(s.first_t is not None or s.finished
                      for _, s in fillers), "the fillers never started")
    spread = [(2 * i + 1) * lanes // (2 * len(lengths))
              for i in range(len(lengths))]
    for i in spread:
        fillers[i][1].cancel()
    _wait(lambda: all(fillers[i][1].finished for i in spread),
          "a cancelled filler never left its lane")
    checks = [submit(CHECK_INDEX + i, n, new) for i, n in enumerate(lengths)]
    for _, stream in checks:
        stream.result(timeout=600)
    beside = [fillers[(i + 1) % lanes] for i in spread
              if (i + 1) % lanes not in spread]
    _wait(lambda: all(len(s.tokens) >= new or s.finished for _, s in beside),
          "a filler stopped short")
    for _, stream in fillers:
        stream.cancel()
    _wait(lambda: all(s.finished for _, s in fillers),
          "the fillers never ended")
    return [{"prompt": prompt, "tokens": list(stream.tokens[:new]),
             "logprobs": list(stream.logprobs[:new]), "lane": stream.lane,
             "reason": stream.finish_reason, "fed": fed if held else 0,
             "state": engine._pool.lane_state(stream.lane) if held else None}
            for held, group in ((True, checks), (False, beside))
            for prompt, stream in group]


def _relative(mine, ref, keep: int):
    """``|mine - ref| / |ref|`` over all but the first ``keep`` axes."""
    shape = ref.shape[:keep] + (-1,)
    diff = (np.asarray(mine, np.float32) - ref).reshape(shape)
    return np.linalg.norm(diff, axis=-1) \
        / np.linalg.norm(ref.reshape(shape), axis=-1)


def compare_check(served: list, params, cfg, workload,
                  reference=None) -> dict:
    """Each record of ``serve_check`` against ONE teacher-forced forward of
    the plain reference over its prompt + served tokens
    (``reference_hybrid.hybrid_check``, or ``reference`` in its place: the
    controls). For every served token: the log-probability the engine
    reported is the reference's for that token at that position
    (``logprob_tol``), and the token is the reference's best or as good as
    it (``argmax_tol``). For every check lane: what its slot held at the
    end is the reference's state after the same tokens: head by head of
    every state-space layer (``state_tol``: the largest ``|S - S_ref| /
    |S_ref|`` of a head; deep layers inherit the rounding of everything
    before them, so this one is loose and holds the hand-over and the
    update), the same over the FIRST state-space layer alone, whose input
    nothing upstream has rounded (``first_state_tol``: tight enough to
    tell the state's precision), and layer by layer for the
    convolution's tail (``conv_tol``)."""
    import jax
    import jax.numpy as jnp

    limits = {k: float(workload[k + "_tol"])
              for k in ("logprob", "argmax", "state", "first_state", "conv")}
    pad, new = int(workload["check_pad_to"]), int(workload["check_new_tokens"])
    reference = reference or reference_hybrid.hybrid_check
    ref = jax.jit(lambda p, t, first, stop: reference(p, t, first, new, stop,
                                                      cfg))
    worst = dict.fromkeys(limits, 0.0)
    bad, lanes = [], []
    for item in served:
        n, toks = len(item["prompt"]), np.asarray(item["tokens"], np.int64)
        lanes.append(item["lane"])
        if len(toks) != new or toks.min() < 0 or toks.max() >= cfg.vocab \
                or item["state"] is not None and item["reason"] != "length":
            bad.append({"prompt_tokens": n, "lane": item["lane"],
                        "reason": item["reason"], "tokens": toks.tolist()})
            continue
        padded = np.zeros(pad, np.int32)
        padded[:n] = item["prompt"]
        padded[n:n + new] = toks  # teacher-forced
        ref_lp, ref_state = ref(params, jnp.asarray(padded), n - 1,
                                n + item["fed"])
        ref_lp = np.asarray(ref_lp)
        at = ref_lp[np.arange(new), toks]
        read = {"logprob": np.abs(at - np.asarray(item["logprobs"])),
                "argmax": ref_lp.max(axis=1) - at}
        if item["state"] is not None:
            read["state"] = _relative(item["state"]["ssm"],
                                      np.asarray(ref_state["ssm"]), 2)
            read["first_state"] = read["state"][0]
            read["conv"] = _relative(item["state"]["conv"],
                                     np.asarray(ref_state["conv"]), 1)
        for name, values in read.items():
            worst[name] = max(worst[name], float(values.max()))
            if not values.max() <= limits[name]:  # a NaN is over too
                at_worst = np.unravel_index(int(np.argmax(values)),
                                            values.shape)
                bad.append({"prompt_tokens": n, "lane": item["lane"],
                            "limit": name + "_tol",
                            "read": float(values.max()),
                            "at": [int(i) for i in at_worst]})
    return {"requests": len(served), "tokens_each": new, "lanes": lanes,
            **{k + "_tol": v for k, v in limits.items()},
            "max_logprob_diff": worst["logprob"],
            "max_gap_to_argmax": worst["argmax"],
            "max_state_diff": worst["state"],
            "max_first_state_diff": worst["first_state"],
            "max_conv_diff": worst["conv"], "bad": bad, "ok": not bad}


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
    cfg = hybrid_config(config)
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
        raise ValueError(f"lm_hybrid driver: loop {workload['loop']!r} is "
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
        # warm-up: one request per prefill bucket the lengths can reach, each
        # long enough to run the decode program once; no other shape
        t = time.monotonic()
        spec = workload["prompt_tokens"]
        for b in prefill_buckets(int(spec["min"]), int(spec["max"]), cfg.max_seq):
            n = min(b, cfg.max_seq - 1 - config["steps_per_dispatch"])
            engine.generate(traffic.prompt_tokens(seed, WARM_INDEX + b, n, cfg.vocab),
                            max_new_tokens=config["steps_per_dispatch"] + 1,
                            timeout=1100)
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
        while sum(len(c.requests) for c in clients) < int(workload["warm_requests"]):
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
            """Cancel any request older than the limit; sleep on to ``until``."""
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
    ended = [r for r in inside if r.get("end", window.t_close) < window.t_close]
    bad = [r for r in ended if r["reason"] != "length" or r.get("timed_out")
           or r["received"] != r["want"]]
    arrivals = [t - window.t_open for c in clients for t in c.token_times]
    tokens = len(arrivals)
    stats = {k: int(stats1[k]) - int(stats0[k]) for k in stats1
             if isinstance(stats1[k], (int, np.integer))}
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
            "engine_stats": stats, "pool": pool, "memory": memory,
            "tokens_by_second": np.bincount(
                np.asarray(arrivals, int)).tolist(),
        },
    }
    if traced is not None:
        lanes_live = config["max_streams"] * stats["active_slot_steps"] \
            / max(stats["slot_steps"], 1)
        hit = stats["moe_experts_hit"] / max(stats["moe_layer_steps"], 1)
        traced["live_tokens_mean"] = float(np.mean(live)) if live else 0.0
        traced.update(work_hybrid.decode_bytes_per_step(
            params, cfg, lanes_live=lanes_live, experts_hit_per_layer=hit,
            live_tokens=traced["live_tokens_mean"]))
        out["trace"] = traced
    return out
