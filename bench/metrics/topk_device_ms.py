"""Device ms per traversal dispatch under the scope ``topk``.

The top-k of the accumulator and its merge into each lane's heap. Read from the trace by
``bench/phasetrace.py``; nothing where the trace holds no scopes or phases.
"""

from bench.phasetrace import per_dispatch


def read(run):
    return per_dispatch(run.trace).get("topk_device_ms")
