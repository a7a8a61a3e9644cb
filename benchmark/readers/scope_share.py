"""The share, in percent, of a program's device time that lies under some of
its named scopes (``benchmark/scope_reduce.py``: self time of the ``XLA Ops``
events inside the program's executions, by the innermost leaf scope of each
operation's ``op_name``).

``program``: the program's name in the trace's ``XLA Modules`` line.
``leaves``: every leaf scope the program has; the same list for every metric
of one program, so that their shares and ``unscoped`` sum to 100.
``count``: the leaves this metric counts; ``"unscoped"`` is what lies under
none.

Returns None when no trace file is there, the program (a commit before
PR 25) cannot give its text, or the program did not run in the trace."""

import os

from benchmark import scope_reduce

#: the share of the time whose instructions the program's text must name, or
#: the text is not this program's and nothing is read
MIN_KNOWN = 0.99


def read(run, program, leaves, count):
    if not run.get("trace"):
        return None
    from benchmark.run import WORKDIR

    path = scope_reduce.newest_xplane(WORKDIR)
    if path is None:
        return None
    found = scope_reduce.reduce_file(path, os.path.getmtime(path), program,
                                     tuple(leaves))
    if not found or not found["total_s"] or \
            found["known_s"] < MIN_KNOWN * found["total_s"]:
        return None
    return 100.0 * sum(found["seconds"].get(s, 0.0) for s in count) \
        / found["total_s"]
