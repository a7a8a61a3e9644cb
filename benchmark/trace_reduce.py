"""From a JAX profiler trace to numbers: device busy and idle time, device
time per program, the operations that took most time, the longest idle gaps.

The profiler writes ``<dir>/plugins/profile/<time>/*.xplane.pb``. On a TPU
each chip is a plane ``/device:TPU:<n>`` whose line ``XLA Ops`` holds one
event per executed operation and whose line ``XLA Modules`` holds one event
per executed program (``jit_<function>(<fingerprint>)``). The reduction
itself (`reduce_events`) works on plain tuples so that a test can feed it a
hand-made list.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
import shutil
import time
from collections import defaultdict

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
TOP = 10
#: only the device trace is read. With the host tracer on (its default) the
#: traced stream pipeline ran at a fifth of its speed, so the trace showed a
#: device far idler than it is (my chip run, PR 24: 56-112 batches in 3 s
#: against 616 with it off); the Python tracer and the HLO protos only make
#: the file large and the stop slow
OPTIONS = {"python_tracer_level": 0, "host_tracer_level": 0,
           "enable_hlo_proto": False}


def trace_dir(workdir: str) -> str:
    return os.path.join(workdir, "profile")


def profile(workdir: str, span: float, tick=None, at_end=None) -> dict:
    """Trace the next ``span`` seconds of whatever the process is doing and
    reduce the trace. Blocks; calls ``tick()`` every 20 ms meanwhile and
    ``at_end()`` when the span ends, before the slow stop. ``window_s`` is the
    traced span by the host's clock."""
    import jax

    shutil.rmtree(trace_dir(workdir), ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    for key, value in OPTIONS.items():
        setattr(options, key, value)
    jax.profiler.start_trace(trace_dir(workdir), profiler_options=options)
    t_a = time.monotonic()
    try:
        while time.monotonic() - t_a < span:
            if tick is not None:
                tick()
            time.sleep(0.02)
        t_b = time.monotonic()
        if at_end is not None:
            at_end()
    finally:
        jax.profiler.stop_trace()
    files = sorted(glob.glob(os.path.join(
        trace_dir(workdir), "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise RuntimeError(f"the profiler wrote no trace under "
                           f"{trace_dir(workdir)}")
    out = reduce_file(files[-1])
    out["window_s"] = t_b - t_a
    out["trace_bytes"] = os.path.getsize(files[-1])
    out["stop_and_reduce_s"] = time.monotonic() - t_b
    return out


def read_events(path: str):
    """(ops, modules, line_names): ops and modules are lists of
    ``(device, name, start_ns, duration_ns)`` from every device plane."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    ops, modules, names = [], [], {}
    for plane in data.planes:
        if not plane.name.startswith("/device:"):
            continue
        names[plane.name] = []
        for line in plane.lines:
            names[plane.name].append(line.name)
            into = {OPS_LINE: ops, MODULES_LINE: modules}.get(line.name)
            if into is None:
                continue
            for ev in line.events:
                into.append((plane.name, ev.name, float(ev.start_ns),
                             float(ev.duration_ns)))
    return ops, modules, names


def reduce_file(path: str) -> dict:
    ops, modules, names = read_events(path)
    out = reduce_events(ops, modules)
    out["device_lines"] = names
    return out


def union(intervals):
    """Disjoint, sorted ``[start, end]`` pairs covering the same points as
    ``intervals`` (pairs of start and end, in any order, overlapping or
    nested)."""
    merged = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return merged


def program_name(event_name: str) -> str:
    """``jit_dispatch(1234567)`` -> ``jit_dispatch``."""
    return re.sub(r"\(.*\)$", "", event_name).strip()


def op_name(event_name: str) -> str:
    """The trace names an operation by its whole HLO line; keep its name and
    the shape it makes: ``%copy.37 = bf16[8,128]{1,0} copy(...)`` ->
    ``%copy.37 bf16[8,128]``."""
    m = re.match(r"(\S+) = \(?(\w+\[[^\]]*\])?", event_name)
    return " ".join(g for g in m.groups() if g) if m else event_name[:80]


def reduce_events(ops, modules) -> dict:
    """``ops`` and ``modules``: ``(device, name, start_ns, duration_ns)``.

    - ``busy_s``: the union of the intervals in which an operation ran,
      per device, averaged over the devices seen (programs stand in where
      a device reports no operations).
    - ``programs``: per program name, the summed duration of its
      executions and their count, over all devices.
    - ``device_ops``: the operations with the largest summed duration.
    - ``idle_gaps``: the gaps between programs, summed by the program that
      ended the gap, largest first.
    """
    devices = sorted({e[0] for e in ops} | {e[0] for e in modules})
    busy = []
    gaps = defaultdict(float)
    for dev in devices:
        evs = [e for e in ops if e[0] == dev] or \
            [e for e in modules if e[0] == dev]
        merged = union((s, s + d) for _, _, s, d in evs)
        busy.append(sum(b - a for a, b in merged) / 1e9)
        # idle gaps lie between programs; the one that starts where a gap
        # ends names it (what the host was waiting to dispatch)
        starts = sorted((s, program_name(n)) for d, n, s, _ in modules
                        if d == dev)
        times = [s for s, _ in starts]
        between = union((s, s + d) for d_, _, s, d in modules if d_ == dev)
        for (_, end), (nxt, _) in zip(between, between[1:]):
            owner = starts[bisect.bisect_left(times, nxt)][1]
            gaps[f"before {owner}"] += (nxt - end) / 1e9
    programs = defaultdict(lambda: {"seconds": 0.0, "count": 0})
    for _, name, _, dur in modules:
        p = programs[program_name(name)]
        p["seconds"] += dur / 1e9
        p["count"] += 1
    by_op = defaultdict(float)
    for _, name, _, dur in ops:
        by_op[op_name(name)] += dur / 1e9

    def top(d):
        return [[k, v] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:TOP]]

    every = ops + modules
    return {
        "devices": len(devices),
        "device_span_s": (max(s + d for _, _, s, d in every)
                          - min(s for _, _, s, _ in every)) / 1e9
        if every else 0.0,
        "busy_s": sum(busy) / len(busy) if busy else 0.0,
        "programs": dict(programs),
        "device_ops": top(by_op),
        "idle_gaps": top(gaps),
    }
