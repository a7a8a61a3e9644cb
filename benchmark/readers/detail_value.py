"""A number the driver already worked out and put on the detail line (the line
before the last), by its key: a client-side statistic that is no end-to-end
metric of the cell."""


def read(run, key):
    return run.get("detail", {}).get(key)
