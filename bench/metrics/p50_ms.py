"""Median due-to-answer latency over every query due in the window."""

import numpy as np


def read(run):
    return float(np.percentile(run.latency_ms, 50)) if run.latency_ms.size else None
