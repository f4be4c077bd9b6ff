"""Device ms per traversal dispatch under the scope ``accumulate``.

The scatter-add of the gathered impacts into each lane's accumulator.
Read from the trace by ``bench/phasetrace.py``;
nothing where the trace holds no scopes or phases.
"""

from bench.phasetrace import per_dispatch


def read(run):
    return per_dispatch(run.trace).get("accumulate_device_ms")
