"""A share of a peak, in percent, for the part of a program that lies under
some of its named scopes: the work that part has to do in one step (a number
the driver left in the trace section under ``work``), over the device time
under those scopes in one step, over the device's peak (``peak``: a key of
the device's entry in ``peaks.json``).

The time is self time by leaf scope, as ``scope_share`` reads it
(``benchmark/scope_reduce.py``), but taken execution by execution: the
MEDIAN execution of the traced seconds, over ``steps`` (a key of the
configuration: steps fused into one execution). A trace cuts the executions
at its edges short, and a sum over all of them divided by their number would
read the time low and the share high.

Returns None when there is no trace, no work, fewer than three executions,
or the program cannot give its text."""

import os
import statistics

from benchmark import scope_reduce, trace_reduce
from benchmark.readers.scope_share import MIN_KNOWN


def seconds_by_execution(ops, modules, program, names, leaves, count):
    """For each execution of ``program``: (self time under the scopes in
    ``count``, self time of all its operations, the part whose instruction
    ``names`` knows), in seconds."""
    out = []
    leaves = frozenset(leaves)
    for dev in sorted({m[0] for m in modules}):
        for start, dur in sorted(
                (s, d) for d_, n, s, d in modules if d_ == dev
                and trace_reduce.program_name(n) == program):
            inside = [(n, s, d) for d_, n, s, d in ops if d_ == dev
                      and start <= s and s + d <= start + dur]
            under = total = known = 0.0
            for name, self_ns in scope_reduce.self_times(inside):
                op_name = names.get(scope_reduce.instruction(name))
                total += self_ns
                known += self_ns if op_name is not None else 0.0
                if scope_reduce.scope_of(op_name or "", leaves) in count:
                    under += self_ns
            out.append((under / 1e9, total / 1e9, known / 1e9))
    return out


def read(run, program, leaves, count, work, peak, steps):
    amount = (run.get("trace") or {}).get(work)
    if not amount:
        return None
    from benchmark.run import WORKDIR

    path = scope_reduce.newest_xplane(WORKDIR)
    if path is None:
        return None
    try:
        from nnstreamer_tpu.serving.engine import decode_program_text

        text = decode_program_text()
    except Exception:  # noqa: BLE001 - a metric is left out, never a failed run
        return None
    if not text:
        return None
    ops, modules, _ = trace_reduce.read_events(path)
    runs = seconds_by_execution(ops, modules, program,
                                scope_reduce.op_names(text), leaves,
                                frozenset(count))
    total = sum(r[1] for r in runs)
    if len(runs) < 3 or not total or \
            sum(r[2] for r in runs) < MIN_KNOWN * total:
        return None
    per_step = statistics.median(r[0] for r in runs) / run["config"][steps]
    if not per_step:
        return None
    return 100.0 * amount / per_step / run["peaks"][peak]
