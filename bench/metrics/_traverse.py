"""Device time of the traversal program in the traced window (shared by readers)."""

PROGRAM = "batched_traverse_resume"


def program(run):
    """(device seconds, dispatches) of the traversal program, or None."""
    if run.trace is None:
        return None
    p = run.trace["programs"].get(PROGRAM)
    if not p or not p["dispatches"] or p["seconds"] <= 0:
        return None
    return p["seconds"], p["dispatches"]
