"""Seconds from process start to the first due query: inputs, index, upload, compile."""


def read(run):
    return run.setup_s
