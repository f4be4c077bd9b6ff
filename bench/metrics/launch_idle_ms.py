"""Device-idle ms per traversal dispatch under ``repro.launch``.

The slot-table and carry upload and the traversal's dispatch. Read from the trace by
``bench/phasetrace.py``; nothing where the trace holds no scopes or phases.
"""

from bench.phasetrace import per_dispatch


def read(run):
    return per_dispatch(run.trace).get("launch_idle_ms")
