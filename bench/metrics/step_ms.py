"""Mean wall milliseconds of one ``InflightServer.step``, less the planning inside it.

What is left is the server loop's own work: admission, slot-table upload,
dispatch, the carry's round trip (which waits for the device) and retiring.
"""


def read(run):
    return 1e3 * sum(run.step_self_s) / len(run.step_self_s) if run.step_self_s else None
