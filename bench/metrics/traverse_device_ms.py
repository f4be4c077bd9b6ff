"""Device milliseconds per dispatch of ``batched_traverse_resume``, from the trace."""

from bench.metrics._traverse import program


def read(run):
    p = program(run)
    return None if p is None else 1e3 * p[0] / p[1]
