"""Device time of one program's executions, by the ``jax.named_scope`` the
operations were traced under.

The trace's ``XLA Ops`` line names an operation by its whole HLO
instruction (``%fusion.168 = bf16[...] fusion(...)``) and, with this libtpu
(0.0.34), carries no ``op_name`` stat (looked at by hand, PR 25: the stats of
an event are ``device_offset_ps``, ``device_duration_ps`` and a time scale).
XLA numbers instructions anew at every compile, so the names say nothing
across commits. The optimized program's own text does carry, on each
instruction's line, ``metadata={op_name="jit(dispatch)/.../nns.decode/.../
kv_gather/gather"}``: the path of named scopes. So the reduction takes
instruction -> ``op_name`` from that text (the program gives it:
``nnstreamer_tpu.serving.engine.decode_program_text``) and sums **self
time** by scope: an event's duration less what the events nested in it on
the same line cover, so that a ``while`` loop and its body are not counted
twice. An instruction the compiler made (a copy, a cast, a loop carry) has
no ``op_name`` or none under a leaf scope, and counts as ``unscoped``.

Like ``trace_reduce.reduce_events`` the reduction works on plain tuples, so a
test feeds it a hand-made list.
"""

from __future__ import annotations

import functools
import glob
import os
import re
from collections import defaultdict

from benchmark import trace_reduce

UNSCOPED = "unscoped"


def newest_xplane(workdir: str):
    """The traced run's ``.xplane.pb`` where ``trace_reduce.profile`` left
    it, the newest one; None when there is none."""
    files = glob.glob(os.path.join(trace_reduce.trace_dir(workdir), "plugins",
                                   "profile", "*", "*.xplane.pb"))
    return max(files, key=os.path.getmtime) if files else None


def instruction(event_name: str) -> str:
    """``%fusion.168 = bf16[8,128]{1,0} fusion(...)`` -> ``%fusion.168``."""
    return event_name.split(" = ", 1)[0].strip()


def op_names(program_text: str) -> dict:
    """instruction -> its ``op_name`` ('' where the compiler gave none), from
    the optimized program's text."""
    out = {}
    for line in program_text.splitlines():
        m = re.match(r"\s*(?:ROOT )?(%[^\s=]+) = ", line)
        if m:
            name = re.search(r'op_name="([^"]*)"', line)
            out[m.group(1)] = name.group(1) if name else ""
    return out


def scope_of(op_name: str, leaves) -> str:
    """The innermost part of the path that is one of ``leaves``."""
    for part in reversed(op_name.split("/")):
        if part in leaves:
            return part
    return UNSCOPED


def self_times(events):
    """``events``: ``(name, start_ns, duration_ns)`` of one device line.
    Yields ``(name, self_ns)``: the duration less what events nested in it
    cover. An event that starts inside another is nested in it (a line's
    events nest or follow each other; one that overruns its parent is cut
    to it)."""
    stack = []  # [name, end, self]
    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and start >= stack[-1][1]:
            done = stack.pop()
            yield done[0], done[2]
        end = start + dur
        if stack:
            end = min(end, stack[-1][1])
            stack[-1][2] -= end - start
        stack.append([name, end, end - start])
    while stack:
        done = stack.pop()
        yield done[0], done[2]


def by_scope(ops, modules, program: str, names: dict, leaves) -> dict:
    """``ops`` and ``modules``: ``(device, name, start_ns, duration_ns)`` as
    ``trace_reduce.read_events`` gives them. Self time of the operations
    inside the executions of ``program``, summed by leaf scope.

    - ``seconds``: scope -> seconds; what lies under no leaf is ``unscoped``.
    - ``total_s``: their sum, which is the union of the operations.
    - ``known_s``: the part whose instruction was found in ``names``. The
      rest ran, but the text is another program's: the caller refuses a
      reduction that knows too little.
    - ``executions``: executions of the program seen.
    """
    leaves = frozenset(leaves)
    seconds = defaultdict(float)
    known = 0.0
    executions = 0
    for dev in sorted({m[0] for m in modules}):
        runs = sorted((s, s + d) for d_, n, s, d in modules if d_ == dev
                      and trace_reduce.program_name(n) == program)
        executions += len(runs)
        inside = [(n, s, d) for d_, n, s, d in ops if d_ == dev
                  and any(a <= s and s + d <= b for a, b in runs)]
        for name, self_ns in self_times(inside):
            op_name = names.get(instruction(name))
            if op_name is not None:
                known += self_ns / 1e9
            seconds[scope_of(op_name or "", leaves)] += self_ns / 1e9
    return {"seconds": dict(seconds), "total_s": sum(seconds.values()),
            "known_s": known, "executions": executions}


@functools.lru_cache(maxsize=2)
def reduce_file(path: str, mtime: float, program: str, leaves: tuple):
    """The reduction of one trace file, or None when the program cannot say
    what its instructions are (as a commit before PR 25 cannot). Kept, so
    that the metrics that share a file read it once."""
    try:
        from nnstreamer_tpu.serving.engine import decode_program_text

        text = decode_program_text()
    except Exception:  # noqa: BLE001 - a metric is left out, never a failed run
        return None
    if not text:
        return None
    ops, modules, _ = trace_reduce.read_events(path)
    return by_scope(ops, modules, program, op_names(text), leaves)
