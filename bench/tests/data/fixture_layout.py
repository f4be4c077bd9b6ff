"""A configuration's own ``layout`` module, as a test names it: the default
layout, each call of it recorded in ``CALLS``."""

from bench.layouts import single

CALLS: list[str] = []
PROGRAMS = single.PROGRAMS


def make_engine(index, cfg, devices):
    CALLS.append("make_engine")
    return single.make_engine(index, cfg, devices)


def warm(engine, cfg, queries, log=print):
    CALLS.append("warm")
    return single.warm(engine, cfg, queries, log)


def make_server(engine, cfg, traffic, spans, obs):
    CALLS.append("make_server")
    return single.make_server(engine, cfg, traffic, spans, obs)


def lead_in(server, engine, queries, log=print):
    CALLS.append("lead_in")
    return single.lead_in(server, engine, queries, log)


def program_hlo(server):
    CALLS.append("program_hlo")
    return single.program_hlo(server)
