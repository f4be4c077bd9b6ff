"""Device-idle ms per traversal dispatch under ``repro.admit``.

Queued queries taken into vacant slots, with the plans made for them.
Read from the trace by ``bench/phasetrace.py``;
nothing where the trace holds no scopes or phases.
"""

from bench.phasetrace import per_dispatch


def read(run):
    return per_dispatch(run.trace).get("admit_idle_ms")
