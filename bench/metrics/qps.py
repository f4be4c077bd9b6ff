"""Queries answered inside the window, per second of the window."""


def read(run):
    return run.completed_in_window / run.seconds
