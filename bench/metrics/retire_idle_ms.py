"""Device-idle ms per traversal dispatch under ``repro.retire``.

Finished lanes retired, counters read, the budgeter fed. Read from the trace by
``bench/phasetrace.py``; nothing where the trace holds no scopes or phases.
"""

from bench.phasetrace import per_dispatch


def read(run):
    return per_dispatch(run.trace).get("retire_idle_ms")
