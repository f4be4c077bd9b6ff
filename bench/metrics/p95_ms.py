"""95th percentile of due-to-answer latency over every query due in the window."""

import numpy as np


def read(run):
    return float(np.percentile(run.latency_ms, 95)) if run.latency_ms.size else None
