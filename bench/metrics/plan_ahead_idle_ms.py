"""Device-idle ms per traversal dispatch under ``repro.plan_ahead``.

The next queries planned after the dispatch. Read from the trace by
``bench/phasetrace.py``; nothing where the trace holds no scopes or phases.
"""

from bench.phasetrace import per_dispatch


def read(run):
    return per_dispatch(run.trace).get("plan_ahead_idle_ms")
