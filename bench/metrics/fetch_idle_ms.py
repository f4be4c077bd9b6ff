"""Device-idle ms per traversal dispatch under ``repro.fetch``.

The wait for the carry and its copy back to the host. Read from the trace by
``bench/phasetrace.py``; nothing where the trace holds no scopes or phases.
"""

from bench.phasetrace import per_dispatch


def read(run):
    return per_dispatch(run.trace).get("fetch_idle_ms")
