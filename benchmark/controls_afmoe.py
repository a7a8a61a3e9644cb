"""The controls of an AFMoE cell's check: faults that the comparison which
decides ``correct`` has to refuse, each run THROUGH that comparison
(``drivers/lm_afmoe.py compare_check``) on the cell's own engine, size and
limits. A limit is set between what the program reads and what its control
reads; this is where the second reading comes from.

    python3 benchmark/controls_afmoe.py \
        --workload trinity_longctx_closed --seed 7 --control no_window,no_gate

One process at a time (a chip holds one). ``--control`` takes one control
or several with commas between; the controls that serve from an engine of
their own (``int8_rows``, ``stale_window_rows``) go alone. A line of JSON
is printed for each, the check's verdict with ``control`` and ``refused``
(what a control has to be; ``none`` has to pass). A control's comparison
stops at the first request that is over a limit of its own (a reference
forward over twelve thousand tokens a request is what a control costs):
its ``compared`` says how many requests its readings are of; the records
served alone, which have rows, are compared first.
"""

from __future__ import annotations

import argparse
import contextlib
import functools
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

#: control -> what is wrong in it
CONTROLS = {
    "none": "nothing: the program and the reference as they are",
    "no_window": "the reference's window layers see everything before a "
                 "query",
    "half_window": "the reference's window is 2048",
    "window_off_by_one": "the reference's window is 4097: ONE key more",
    "rotary_on_full": "the reference turns the full layers' queries and "
                      "keys by rotary positions too",
    "no_rotary_on_window": "the reference's window layers have no "
                           "positions either",
    "no_gate": "the reference leaves the attention's output gate out",
    "softmax_scores": "the reference's router scores are the softmax over "
                      "the 256 outputs, not their sigmoid",
    "no_route_norm": "the reference's gates are the chosen scores as they "
                     "are, not divided by their sum",
    "no_route_scale": "the reference leaves route_scale (2.448) out",
    "no_post_norms": "the reference leaves the two post-norms of a layer "
                     "out (pre-norm only)",
    "no_embed_scale": "the reference's embedding is not scaled by "
                      "sqrt(hidden_size)",
    "renormalise_held": "the reference's gates are renormalised over the "
                        "experts HELD here",
    "no_bias": "the reference chooses by the scores alone, without the "
               "selection bias",
    "stale_window_rows": "the engine hands the window arena the prompt's "
                         "blocks shifted by one: every row a window layer "
                         "holds of the prompt is the row of the position "
                         "one block (16) before",
    "int8_rows": "the engine holds keys and values in 8 bits (one scale a "
                 "head's vector): the nearest precision below the "
                 "configuration's bfloat16",
}
#: the controls that serve from an engine of their own
PATCHED = ("stale_window_rows", "int8_rows")


@contextlib.contextmanager
def _eight_bit_rows():
    """Every key and value the program caches or attends over, prefill and
    decode alike, rounded to 127 steps of its vector's largest value and
    back."""
    import jax.numpy as jnp

    from nnstreamer_tpu.models import afmoe

    real = afmoe._layer_qkv

    def narrow(x):
        x32 = x.astype(jnp.float32)
        scale = jnp.maximum(
            jnp.max(jnp.abs(x32), axis=-1, keepdims=True) / 127.0, 1e-30)
        return (jnp.clip(jnp.round(x32 / scale), -127, 127)
                * scale).astype(x.dtype)

    def rounded(h, lp, positions, kind, cfg):
        q, k, v, gate = real(h, lp, positions, kind, cfg)
        return q, narrow(k), narrow(v), gate

    afmoe._layer_qkv = rounded
    try:
        yield
    finally:
        afmoe._layer_qkv = real


@contextlib.contextmanager
def _stale_window_rows():
    """The prefill's hand-over gives each window block the rows of the
    prompt's block BEFORE it."""
    from nnstreamer_tpu.serving import kvpool

    real = kvpool.BlockPool.scatter_prefill

    def shifted(self, cache1, block_ids, lane=None, window_ids=(),
                window_first=0):
        return real(self, cache1, block_ids, lane=lane,
                    window_ids=window_ids,
                    window_first=max(window_first - 1, 0))

    kvpool.BlockPool.scatter_prefill = shifted
    try:
        yield
    finally:
        kvpool.BlockPool.scatter_prefill = real


def run_controls(config: dict, workload: dict, seed: int, controls):
    """One verdict a control, in the order given, each yielded as soon as
    it is reached. What the engine serves for the check is served ONCE for
    all the controls that are a wrong reference (and ``none``): they differ
    in what it is held to, not in what is served. A control of ``PATCHED``
    serves from an engine of its own, and a chip holds one engine of this
    size a process: it goes in a call of its own."""
    from benchmark import reference_afmoe
    from benchmark.drivers import lm_afmoe

    unknown = [c for c in controls if c not in CONTROLS]
    if unknown:
        raise ValueError(f"controls_afmoe: no control {unknown[0]!r}")
    patched = [c for c in controls if c in PATCHED]
    if patched and len(controls) != 1:
        raise ValueError(f"controls_afmoe: {patched[0]} serves from its own "
                         f"engine; ask for it alone")
    patch = {"int8_rows": _eight_bit_rows,
             "stale_window_rows": _stale_window_rows}.get(
        controls[0], contextlib.nullcontext)
    with patch():
        cfg, params, engine = lm_afmoe.build_engine(config, seed, {})
        try:
            records = lm_afmoe.serve_for_check(engine, cfg, workload, seed)
        finally:
            engine.stop()
    engine._pool.arena = None  # room for the reference
    alone_first = sorted(records, key=lambda r: "kv" not in (r["state"] or {}))
    for control in controls:
        wrong = control not in ("none",) + PATCHED
        reference = functools.partial(reference_afmoe.afmoe_check,
                                      **{control: True}) if wrong else None
        check = lm_afmoe.compare_check(
            records if control == "none" else alone_first, params, cfg,
            workload, reference, stop_at_bad=control != "none")
        yield {**check, "control": control, "refused": not check["ok"]}


def run_control(config: dict, workload: dict, seed: int, control: str) -> dict:
    return next(run_controls(config, workload, seed, [control]))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--control", required=True,
                    help="one of " + ", ".join(sorted(CONTROLS))
                    + ", or several with commas between (one line each)")
    args = ap.parse_args(argv)

    from benchmark import run as bench_run

    cell = bench_run.load_cell(args.workload)
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(bench_run.ROOT, ".jax_cache"))
    os.makedirs(os.environ["JAX_COMPILATION_CACHE_DIR"], exist_ok=True)
    bench_run.require_tpu(int(cell["entry"]["chips"]))
    from nnstreamer_tpu.pipeline import continuity

    continuity.arm_compile_cache()
    for verdict in run_controls(cell["config"], cell["workload"], args.seed,
                                args.control.split(",")):
        print(json.dumps(verdict), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
