"""A share of a peak, in percent: the work one execution has to do (a number
the driver computed from shapes with ``benchmark/work.py`` and left in the
trace section under ``work``), over the device time of one execution (read as
``trace_time`` reads it, with the arguments under ``time``), over the device's
peak (``peak``: a key of the device's entry in ``peaks.json``)."""

from benchmark.readers import trace_time


def read(run, work, peak, time):
    ms = trace_time.read(run, **time)
    amount = (run.get("trace") or {}).get(work)
    if ms is None or not amount:
        return None
    return 100.0 * amount / (ms / 1e3) / run["peaks"][peak]
