"""The ratio of two ``engine.stats`` counters, each taken as its growth over
the measured window, in percent. Counts from the program, no clock."""


def read(run, numerator, denominator):
    stats = run.get("engine_stats")
    if not stats or not stats.get(denominator):
        return None
    return 100.0 * stats[numerator] / stats[denominator]
