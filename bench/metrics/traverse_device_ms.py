"""Device milliseconds per dispatch of the layout's traversal programs, from the trace."""

from bench.devtrace import traversal


def read(run):
    p = traversal(run.trace)
    return None if p is None else 1e3 * p[0] / p[1]
