"""Mean host milliseconds of one ``BatchEngine.plan`` call (harness span)."""


def read(run):
    return 1e3 * sum(run.plan_s) / len(run.plan_s) if run.plan_s else None
