"""Share of the HBM roofline the traversal programs reach in the traced window.

Bytes: every posting the program's counters say it scored in the window,
at 5 B (an int32 docid and an int8 impact), whatever reads them. Least
time: those bytes at the chip's peak HBM bandwidth. Share: least time over
the programs' device time in the same window, in percent.
"""

from bench.devtrace import traversal

BYTES_PER_POSTING = 5


def read(run):
    p = traversal(run.trace)
    if p is None or not run.peaks or run.postings_traced <= 0:
        return None
    least_s = run.postings_traced * BYTES_PER_POSTING / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / p[0]
