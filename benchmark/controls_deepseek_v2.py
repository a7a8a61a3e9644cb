"""The controls of a DeepSeek-V2 cell's check: faults that the comparison
which decides ``correct`` has to refuse, each run THROUGH that comparison
(``drivers/lm_deepseek_v2.py check_served_tokens``) on the cell's own
engine, size and limits. A limit is set between what the program reads and
what its control reads; this is where the second reading comes from.

    python3 benchmark/controls_deepseek_v2.py \
        --workload dsv2lite_longctx_closed --seed 7 --control int8_rows

One process at a time (a chip holds one). ``--control`` takes one control
or several with commas between (``int8_rows`` alone: it serves from its own
engine); a line of JSON is printed for each, the check's verdict with
``control`` and ``refused`` (what a control has to be; ``none`` has to
pass).
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
    "renormalised_gates": "the reference's gates are renormalised over the "
                          "chosen six (norm_topk_prob true): the nearest "
                          "wrong expert layer",
    "no_mscale": "the reference's softmax scale is 192 ** -0.5 without "
                 "YaRN's mscale ** 2",
    "plain_rotary": "the reference turns queries and keys by the plain "
                    "rotary frequencies, not YaRN's",
    "no_kv_norm": "the reference leaves kv_a_layernorm out: the latent goes "
                  "into the row and through wkv_b as projected",
    "no_shared": "the reference leaves the shared experts out",
    "int8_rows": "the engine holds the latent row in 8 bits (one scale a "
                 "row): the nearest precision below the configuration's "
                 "bfloat16",
}
#: the controls that are a wrong reference: its keyword
WRONG_REFERENCE = {"renormalised_gates": "renormalise",
                   "no_mscale": "no_mscale", "plain_rotary": "plain_rotary",
                   "no_kv_norm": "no_kv_norm", "no_shared": "no_shared"}


@contextlib.contextmanager
def _eight_bit_rows():
    """Every row the program caches, prefill and decode alike, rounded to
    127 steps of its largest value and back."""
    import jax.numpy as jnp

    from nnstreamer_tpu.models import mla

    real = mla._latent_rows

    def rounded(h, lp, positions, cfg):
        row = real(h, lp, positions, cfg)
        r32 = row.astype(jnp.float32)
        scale = jnp.maximum(
            jnp.max(jnp.abs(r32), axis=-1, keepdims=True) / 127.0, 1e-30)
        return (jnp.clip(jnp.round(r32 / scale), -127, 127)
                * scale).astype(row.dtype)

    mla._latent_rows = rounded
    try:
        yield
    finally:
        mla._latent_rows = real


def run_controls(config: dict, workload: dict, seed: int, controls):
    """One verdict a control, in the order given, each yielded as soon as
    it is reached. What the engine serves for the check is served ONCE for
    all the controls that are a wrong reference (and ``none``): they differ
    in what it is held to, not in what is served. ``int8_rows`` serves from
    an engine of its own, and a chip holds one engine of this size a
    process: it goes in a call of its own."""
    from benchmark import reference_deepseek_v2
    from benchmark.drivers import lm_deepseek_v2

    unknown = [c for c in controls if c not in CONTROLS]
    if unknown:
        raise ValueError(f"controls_deepseek_v2: no control {unknown[0]!r}")
    patched = {c == "int8_rows" for c in controls}
    if len(patched) != 1:
        raise ValueError("controls_deepseek_v2: int8_rows serves from its "
                         "own engine; ask for it alone")
    with _eight_bit_rows() if patched == {True} else contextlib.nullcontext():
        cfg, params, engine = lm_deepseek_v2.build_engine(config, seed, {})
        try:
            records = lm_deepseek_v2.serve_for_check(engine, cfg, workload,
                                                     seed)
        finally:
            engine.stop()
    for control in controls:
        reference = functools.partial(
            reference_deepseek_v2.deepseek_v2_check,
            **{WRONG_REFERENCE[control]: True}) \
            if control in WRONG_REFERENCE else None
        check = lm_deepseek_v2.compare_check(records, params, cfg, workload,
                                             reference)
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
