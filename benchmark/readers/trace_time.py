"""Device milliseconds from the profiler trace, per execution.

``programs``: the programs whose device time is summed (their names in the
trace's ``XLA Modules`` line, without the fingerprint); ``null`` takes the
device-busy time of the whole traced window instead. ``per``: the programs
whose executions are counted as the divisor (default: ``programs``).
``steps``: a key of the configuration by whose value the divisor is
multiplied (steps fused into one execution)."""


def read(run, programs=None, per=None, steps=None):
    trace = run.get("trace")
    if not trace:
        return None
    seen = trace["programs"]
    if programs is None:
        seconds = trace["busy_s"]
    else:
        seconds = sum(seen[p]["seconds"] for p in programs if p in seen)
    count = sum(seen[p]["count"] for p in (per or programs or ()) if p in seen)
    if steps is not None:
        count *= run["config"][steps]
    if not count or not seconds:
        return None
    return 1e3 * seconds / count
