"""Driver for LM serving cells: ``ContinuousBatchingEngine`` under a closed
loop of clients.

``run_cell`` makes the weights on the device from the seed, starts the
engine, warms every prefill bucket the traffic can reach and the decode
program, checks a few first tokens against the plain reference
(``benchmark/reference.py``), then runs the clients: each submits a prompt,
reads its ``GenerationStream`` to the end and submits the next. Time to the
first token and token arrivals are taken on the client's side of the stream.
Nothing here knows a cell's name; sizes and traffic come in as dicts.
"""

from __future__ import annotations

import threading
import time
from collections import Counter

import numpy as np

from benchmark import reference, trace_reduce, traffic, work

MIN_BUCKET = 16  # the engine's default smallest prefill bucket
#: request indexes of the check and the warm-up prompts, clear of the loop's
CHECK_INDEX, WARM_INDEX = 10 ** 9, 2 * 10 ** 9


def transformer_config(config: dict):
    import jax.numpy as jnp

    from nnstreamer_tpu.models.transformer import TransformerConfig

    return TransformerConfig(
        vocab=config["vocab_size"], d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        n_layers=config["num_hidden_layers"],
        d_ff=config["intermediate_size"],
        max_seq=config["max_position_embeddings"],
        dtype={"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
            config["dtype"]])


def device_params(cfg, seed: int):
    """The tree, shapes and dtypes of ``models/transformer.py init_params``
    (normal x 0.02 in float32, ones for the norm scales), made on the device
    in one jitted call: ``init_params`` draws every weight in numpy on the
    host and uploads it, which every run would pay."""
    import jax
    import jax.numpy as jnp

    L, D, H, Dh, F = (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.head_dim,
                      cfg.d_ff)
    shapes = {"embed": (cfg.vocab, D), "qkv": (L, D, 3, H, Dh),
              "proj": (L, H, Dh, D), "w_in": (L, D, F), "w_out": (L, F, D)}

    @jax.jit
    def make(key):
        keys = jax.random.split(key, len(shapes))
        p = {name: jax.random.normal(k, shape, jnp.float32) * 0.02
             for (name, shape), k in zip(sorted(shapes.items()), keys)}
        p.update(ln1=jnp.ones((L, D), jnp.float32),
                 ln2=jnp.ones((L, D), jnp.float32),
                 ln_f=jnp.ones((D,), jnp.float32))
        return p

    return jax.block_until_ready(make(jax.random.PRNGKey(seed % (2 ** 31 - 1))))


def prefill_buckets(lo: int, hi: int, max_seq: int) -> list:
    """The padded prompt lengths the engine compiles for prompts of ``lo`` to
    ``hi`` tokens: it doubles from its smallest bucket (``engine._bucket``)."""
    out, b = [], MIN_BUCKET
    while True:
        if b >= lo:
            out.append(min(b, max_seq))
        if b >= hi:
            return out
        b *= 2


class _Window:
    """Open and close instants, published to the clients by the main thread."""

    t_open = None
    t_close = None

    def holds(self, t: float) -> bool:
        return self.t_open is not None and self.t_open <= t < self.t_close


class _Client(threading.Thread):
    """One caller: submit, read to the end, submit the next."""

    def __init__(self, index, engine, sizes, seed, vocab, n_clients, window,
                 stop):
        super().__init__(name=f"benchmark-client{index}", daemon=True)
        self.index, self.engine, self.sizes = index, engine, sizes
        self.seed, self.vocab, self.n_clients = seed, vocab, n_clients
        self.window, self.stop_evt = window, stop
        self.requests = []      # finished or abandoned request records
        self.token_times = []   # arrivals inside the window
        self.current = None     # the record of the request in flight
        self.stream = None

    def run(self):
        k = self.index
        while not self.stop_evt.is_set():
            n_prompt, n_out = self.sizes[k % len(self.sizes)]
            prompt = traffic.prompt_tokens(self.seed, k, n_prompt, self.vocab)
            self._request(prompt, n_out)
            k += self.n_clients

    def _request(self, prompt, n_out):
        rec = {"submit": time.monotonic(), "first": None, "received": 0,
               "prompt": len(prompt), "want": n_out, "reason": None}
        self.current = rec
        try:
            self.stream = self.engine.submit(prompt, max_new_tokens=n_out)
            for _ in self.stream:
                t = time.monotonic()
                if rec["first"] is None:
                    rec["first"] = t
                rec["received"] += 1
                if self.window.holds(t):
                    self.token_times.append(t)
            rec["reason"] = self.stream.finish_reason
        except Exception as e:  # noqa: BLE001 - a refused request is a failed one
            rec["reason"] = f"raised: {e}"
        rec["end"] = time.monotonic()
        self.current = None
        self.requests.append(rec)

    def live_tokens(self) -> int:
        """Context tokens this client's request holds in the KV pool now."""
        rec = self.current
        return rec["prompt"] + rec["received"] if rec and rec["first"] else 0

    def cancel_if_older(self, age_s: float, now: float) -> None:
        rec, stream = self.current, self.stream
        if rec is not None and stream is not None and \
                now - rec["submit"] > age_s:
            rec["timed_out"] = True
            stream.cancel()


def check_first_tokens(engine, params, cfg, config, workload, seed) -> dict:
    """For a few seeded prompts the served first token must be (near) the
    plain reference's argmax, and the log-probability the engine reports for
    it must be the reference's (``chip_smoke.py``'s two comparisons)."""
    import jax
    import jax.numpy as jnp

    tol = float(workload["logprob_tol"])
    pad = int(workload["check_pad_to"])
    ref = jax.jit(lambda p, t, last: reference.transformer_logprobs(
        p, t, last, cfg.n_layers))
    worst_lp = worst_gap = 0.0
    bad = []
    for i, n in enumerate(workload["check_prompt_tokens"]):
        prompt = traffic.prompt_tokens(seed, CHECK_INDEX + i, int(n), cfg.vocab)
        stream = engine.submit(prompt, max_new_tokens=config["steps_per_dispatch"])
        toks = stream.result(timeout=600)
        padded = np.zeros(pad, np.int32)
        padded[:n] = prompt
        ref_lp = np.asarray(ref(params, jnp.asarray(padded), n - 1))
        tok, lp = int(toks[0]), float(stream.logprobs[0])
        diff = abs(float(ref_lp[tok]) - lp)
        gap = float(ref_lp.max()) - float(ref_lp[tok])
        worst_lp, worst_gap = max(worst_lp, diff), max(worst_gap, gap)
        if not (0 <= tok < cfg.vocab and diff <= tol and gap <= tol):
            bad.append({"prompt_tokens": int(n), "token": tok, "served": lp,
                        "reference": float(ref_lp[tok]), "gap": gap})
    return {"prompts": len(workload["check_prompt_tokens"]), "tol": tol,
            "max_logprob_diff": worst_lp, "max_gap_to_argmax": worst_gap,
            "bad": bad, "ok": not bad}


def run_cell(config: dict, workload: dict, seed: int, seconds: float,
             trace: bool, t0: float, workdir: str) -> dict:
    import jax

    from nnstreamer_tpu.serving import ContinuousBatchingEngine

    if workload["loop"] != "closed":
        raise ValueError(f"lm driver: loop {workload['loop']!r} is not built")
    phases = {}
    t = time.monotonic()
    cfg = transformer_config(config)
    params = device_params(cfg, seed)
    phases["weights_s"] = time.monotonic() - t
    t = time.monotonic()
    engine = ContinuousBatchingEngine(
        cfg, params, max_streams=config["max_streams"],
        steps_per_dispatch=config["steps_per_dispatch"],
        temperature=config["temperature"],
        block_tokens=config["block_tokens"], attention=config["attention"],
        prefix_cache=config["prefix_cache"]).start()
    phases["engine_s"] = time.monotonic() - t
    n_clients = int(workload["clients"])
    window = _Window()
    stop = threading.Event()
    clients = []
    traced = None
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
        t = time.monotonic()
        check = check_first_tokens(engine, params, cfg, config, workload, seed)
        phases["check_s"] = time.monotonic() - t

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
            "engine_stats": stats,
            # for looking inside a run: tokens that arrived in each whole
            # second, and (submit offset s, TTFT ms) of every sample
            "tokens_by_second": np.bincount(
                np.asarray(arrivals, int)).tolist(),
            "ttft_by_submit": sorted(
                (round(r["submit"] - window.t_open, 2),
                 round(1e3 * (r["first"] - r["submit"]), 1), r["prompt"])
                for r in inside
                if r["first"] is not None and r["first"] < window.t_close),
        },
    }
    if traced is not None:
        param_bytes = sum(int(x.nbytes) for x in jax.tree.leaves(params))
        kv = work.kv_bytes_per_token(cfg.n_layers, cfg.n_heads, cfg.head_dim,
                                     np.dtype(cfg.dtype).itemsize)
        traced["live_tokens_mean"] = float(np.mean(live)) if live else 0.0
        traced["decode_bytes_per_step"] = work.decode_bytes_per_step(
            param_bytes, kv, traced["live_tokens_mean"])
        out["trace"] = traced
    return out
