"""The controls of a Qwen3-Next cell's check: faults that the comparison
which decides ``correct`` has to refuse, each run THROUGH that comparison
(``drivers/lm_qwen3_next.py check_served_tokens``) on the cell's own engine,
size and limits. A limit is set between what the program reads and what its
control reads; this is where the second reading comes from.

    python3 benchmark/controls_qwen3_next.py --workload qwen3next_chat_closed \
        --seed 7 --control bf16_state

One control a process (each builds its own engine, and a chip holds one).
The last line printed is the check's verdict as JSON, with ``control`` and
``refused`` (what a control has to be; ``none`` has to pass).
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
                          "held experts: the nearest wrong expert layer",
    "beta_one": "the reference writes every correction at full strength "
                "(beta fixed at 1): the delta rule without its write gate",
    "full_rotary": "the reference turns the whole of each query and key "
                   "head, not its first quarter",
    "bf16_state": "the engine keeps the delta-rule state in bfloat16: the "
                  "nearest precision below the configuration's float32",
    "state_unchanged": "the decode step leaves a lane's slot (delta-rule "
                       "state and convolution tail) as the prefill handed "
                       "it over",
}
#: the controls that are a wrong reference: its keyword
WRONG_REFERENCE = {"renormalised_gates": "renormalise_held",
                   "beta_one": "beta_one", "full_rotary": "full_rotary"}


@contextlib.contextmanager
def _frozen_state():
    from nnstreamer_tpu.models import hybrid

    real = hybrid._la_decode

    def frozen(h, lp, state, tail, live, cfg):
        out, _, _ = real(h, lp, state, tail, live, cfg)
        return out, state, tail

    hybrid._la_decode = frozen
    try:
        yield
    finally:
        hybrid._la_decode = real


def run_control(config: dict, workload: dict, seed: int, control: str) -> dict:
    from benchmark import reference_qwen3_next
    from benchmark.drivers import lm_qwen3_next

    if control not in CONTROLS:
        raise ValueError(f"controls_qwen3_next: no control {control!r}")
    if control == "bf16_state":
        config = {**config, "ssm_state_dtype": "bfloat16"}
    reference = functools.partial(
        reference_qwen3_next.qwen3_next_check,
        **{WRONG_REFERENCE[control]: True}) \
        if control in WRONG_REFERENCE else None
    with _frozen_state() if control == "state_unchanged" \
            else contextlib.nullcontext():
        cfg, params, engine = lm_qwen3_next.build_engine(config, seed, {})
        try:
            check = lm_qwen3_next.check_served_tokens(
                engine, params, cfg, workload, seed, reference)
        finally:
            engine.stop()
    return {**check, "control": control, "refused": not check["ok"]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--control", required=True, choices=sorted(CONTROLS))
    args = ap.parse_args(argv)

    from benchmark import run as bench_run

    cell = bench_run.load_cell(args.workload)
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(bench_run.ROOT, ".jax_cache"))
    os.makedirs(os.environ["JAX_COMPILATION_CACHE_DIR"], exist_ok=True)
    bench_run.require_tpu(int(cell["entry"]["chips"]))
    from nnstreamer_tpu.pipeline import continuity

    continuity.arm_compile_cache()
    print(json.dumps(run_control(cell["config"], cell["workload"], args.seed,
                                 args.control)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
