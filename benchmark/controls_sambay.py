"""The controls of a SambaY cell's check: faults that the comparison which
decides ``correct`` has to refuse, each run THROUGH that comparison
(``drivers/lm_sambay.py compare_check``) on the cell's own engine, size and
limits. A limit is set between what the program reads and what its control
reads; this is where the second reading comes from.

    python3 benchmark/controls_sambay.py \
        --workload phi4flash_reason_closed --seed 7 --control all

One process a call (a chip holds one); the weights are made once. What the
engine serves for the check is served ONCE for ``none`` and for every
control that is a wrong reference (``reference_sambay.WRONG``): they differ
in what it is held to, and their switches are traced, so one compiled
reference serves them all. A control of ``PATCHED`` is a wrong PROGRAM: it
serves from an engine of its own, built after the one before it has given
its arenas up. ``--control`` takes one control, several with commas
between, or ``all``. A line of JSON is printed for each, the check's
verdict with ``control`` and ``refused`` (what a control has to be;
``none`` has to pass), with ``by_request``, every reading of every request,
so that a limit can be set between the program's readings and a control's
after the run; the records served alone, which have rows and states, are
compared first.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

#: control -> what is wrong in it
CONTROLS = {
    "none": "nothing: the program and the reference as they are",
    "no_diff": "the reference's attention is plain: lambda = 0, the second "
               "softmax of a pair is never subtracted",
    "no_pair_norm": "the reference leaves the RMSNorm over a pair's 128 "
                    "columns out",
    "no_lambda_scale": "the reference leaves the factor (1 - lambda0) out",
    "wrong_lambda_layer": "the reference's lambda0 is that of layer l // 2",
    "no_window": "the reference's window layers see everything before a "
                 "query",
    "half_window": "the reference's window is 256",
    "window_off_by_one": "the reference's window is 513: ONE key more",
    "memory_after_gate": "the reference's memory is taken AFTER the gate "
                         "silu(z)",
    "stale_memory": "the reference's gated memory units read the memory of "
                    "the token BEFORE (one step stale)",
    "no_gmu_silu": "the reference's gated memory units leave the silu out",
    "cross_reads_window": "the reference's cross layers read the last "
                          "window layer's rows, the window's alone",
    "no_D": "the reference leaves the skip D * xc out",
    "no_dt_bias": "the reference leaves the step's bias out",
    "state_unchanged": "the decode step leaves a lane's slot (recurrent "
                       "state and convolution tail) as the prefill handed "
                       "it over",
    "bf16_state": "the engine keeps the recurrent state in bfloat16: the "
                  "nearest precision below the configuration's float32",
    "int8_rows": "the engine holds keys and values in 8 bits (one scale a "
                 "pair's row): the nearest precision below the "
                 "configuration's bfloat16",
}
#: the controls that serve from an engine of their own
PATCHED = ("state_unchanged", "bf16_state", "int8_rows")


@contextlib.contextmanager
def _frozen_state():
    from nnstreamer_tpu.models import sambay

    real = sambay._mamba_decode

    def frozen(h, lp, slot, tail, live, cfg):
        out, memory, _, _ = real(h, lp, slot, tail, live, cfg)
        return out, memory, slot, tail

    sambay._mamba_decode = frozen
    try:
        yield
    finally:
        sambay._mamba_decode = real


@contextlib.contextmanager
def _eight_bit_rows():
    """Every key and value row the program caches, prefill and decode
    alike, rounded to 127 steps of its largest value and back."""
    import jax.numpy as jnp

    from nnstreamer_tpu.models import sambay

    real = sambay._qkv

    def narrow(x):
        x32 = x.astype(jnp.float32)
        scale = jnp.maximum(
            jnp.max(jnp.abs(x32), axis=-1, keepdims=True) / 127.0, 1e-30)
        return (jnp.clip(jnp.round(x32 / scale), -127, 127)
                * scale).astype(x.dtype)

    def rounded(h, lp, cfg, queries=True):
        q, kv = real(h, lp, cfg, queries)
        return q, narrow(kv)

    sambay._qkv = rounded
    try:
        yield
    finally:
        sambay._qkv = real


def patch(control: str):
    """The context under which ``control``'s engine is built and serves."""
    return {"state_unchanged": _frozen_state,
            "int8_rows": _eight_bit_rows}.get(control,
                                              contextlib.nullcontext)


def patched_config(cfg, control: str):
    """``cfg`` as ``control``'s engine takes it."""
    import jax.numpy as jnp

    if control == "bf16_state":
        return dataclasses.replace(cfg, ssm_state_dtype=jnp.bfloat16)
    return cfg


def run_controls(config: dict, workload: dict, seed: int, controls,
                 params_of=None, stop_at_bad: bool = False):
    """One verdict a control, in the order given, each yielded as soon as
    it is reached. ``params_of`` (the tests) turns the seed's weights
    before anything is served from them. ``stop_at_bad``: a control's
    comparison stops at the first request over a limit of its own (a
    reference forward over two thousand tokens is a second here, so the
    default compares every request and keeps every reading)."""
    from benchmark import reference_sambay
    from benchmark.drivers import lm_sambay

    controls = list(CONTROLS) if list(controls) == ["all"] else list(controls)
    unknown = [c for c in controls if c not in CONTROLS]
    if unknown:
        raise ValueError(f"controls_sambay: no control {unknown[0]!r}")
    params = None
    shared = None       # the records every wrong reference is held against

    def serve(control):
        nonlocal params
        if params is None and params_of is not None:
            cfg = lm_sambay.sambay_config(config)
            params = params_of(cfg.family.init_params(cfg, seed))
        with patch(control)():
            cfg, params, engine = lm_sambay.build_engine(
                config, seed, {}, params=params,
                cfg_of=lambda c: patched_config(c, control))
            try:
                records = lm_sambay.serve_for_check(engine, cfg, workload,
                                                    seed)
            finally:
                engine.stop()
        engine._pool.arena = None  # room for the reference, the next engine
        return cfg, records

    for control in controls:
        if control in PATCHED:
            cfg, records = serve(control)
        else:
            shared = shared or serve("none")
            cfg, records = shared
        alone_first = sorted(records,
                             key=lambda r: "kv" not in (r["state"] or {}))
        wrong = {control: True} \
            if control in reference_sambay.WRONG else None
        check = lm_sambay.compare_check(
            records if control == "none" else alone_first, params,
            lm_sambay.sambay_config(config), workload, wrong,
            stop_at_bad=stop_at_bad and control != "none")
        yield {**check, "control": control, "refused": not check["ok"]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--control", required=True,
                    help="one of " + ", ".join(sorted(CONTROLS))
                    + ", several with commas between, or all (a line each)")
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
