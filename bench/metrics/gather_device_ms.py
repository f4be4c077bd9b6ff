"""Device ms per traversal dispatch under the scope ``gather``.

The block metadata, the prune mask and the block-row gathers of each range step.
Read from the trace by ``bench/phasetrace.py``;
nothing where the trace holds no scopes or phases.
"""

from bench.phasetrace import per_dispatch


def read(run):
    return per_dispatch(run.trace).get("gather_device_ms")
