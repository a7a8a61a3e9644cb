"""A mean from ``engine.stats`` counters, each taken as its growth over the
measured window: (the sum of ``plus`` less the sum of ``minus``) over
``per``, times ``scale``. Counts and clock sums made by the program where
the work happens; None when a counter is missing (a commit before PR 25) or
the divisor is zero."""


def read(run, plus, per, minus=(), scale=1.0):
    stats = run.get("engine_stats")
    if not stats or not stats.get(per) or \
            any(k not in stats for k in (*plus, *minus)):
        return None
    total = sum(stats[k] for k in plus) - sum(stats[k] for k in minus)
    return scale * total / stats[per]
