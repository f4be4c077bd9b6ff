"""The default layout: one ``Engine`` on one chip, served by ``InflightServer``.

The engine is ``core.range_daat.Engine`` at the default scorer and docid
format; the serving loop is ``InflightServer`` over ``BatchEngine``, with
the traffic's budgeter (``SlaBudgeter``, the ``Reactive`` policy under an
SLA). Every server step dispatches ``batched_traverse_resume`` once.
"""

from __future__ import annotations

import time

import numpy as np

from bench.phasetrace import traverse_hlo as program_hlo
from bench.system import Spans

__all__ = ["PROGRAMS", "lead_in", "make_engine", "make_server", "plan_widths", "program_hlo", "warm"]

PROGRAMS = ("batched_traverse_resume",)


def make_engine(index, cfg: dict, devices):
    """The engine on the cell's one device (JAX's default), its index uploaded."""
    import jax

    from repro.core import Engine

    if list(devices) != jax.devices()[:1]:
        raise ValueError(f"this layout serves from JAX's default device alone, not {list(devices)}")
    engine = Engine(index, k=int(cfg["k"]), impact_dtype=cfg["impact_dtype"])
    jax.block_until_ready(engine.dix)
    return engine


def make_server(engine, cfg: dict, traffic: dict, spans: Spans, obs):
    """A fresh ``InflightServer`` with the cell's budgeter, planning timed.

    ``obs`` is the server's instrumentation handle: ``NOOP``, or one that
    only names the step's phases in a profiler trace.
    """
    from repro.core.anytime import Reactive
    from repro.serving import BatchEngine, BucketSpec, InflightServer, SlaBudgeter

    class TimedBatchEngine(BatchEngine):
        def plan(self, q_terms):
            with spans.phase("plan"):
                t0 = time.perf_counter()
                p = super().plan(q_terms)
                spans.plan.append(time.perf_counter() - t0)
                spans.plan_start.append(t0)
            return p

    sla = traffic.get("sla_ms")
    if sla is None:
        budgeter = SlaBudgeter(sla_ms=float("inf"))
    else:
        budgeter = SlaBudgeter(sla_ms=float(sla), policy=Reactive(**traffic.get("policy", {})))
    n_slots = int(cfg["n_slots"])
    return InflightServer(
        TimedBatchEngine(engine, BucketSpec(max_batch=n_slots)),
        budgeter,
        n_slots=n_slots,
        quantum=int(cfg["quantum"]),
        obs=obs,
    )


def plan_widths(index, queries: np.ndarray) -> np.ndarray:
    """Block-table width of each query's plan: its widest range's block count."""
    nb = np.zeros((index.n_terms + 1, index.n_ranges), np.int64)
    term = np.repeat(np.arange(index.n_terms), np.diff(index.tr_ptr))
    nb[term, index.tr_range] = index.tr_blk_end - index.tr_blk_start
    q = np.where(queries >= 0, queries, index.n_terms)  # padding -> zero row
    return nb[q].sum(axis=1).max(axis=1)


def _serve_until_idle(server) -> bool:
    """Step until every submitted query is answered; False if one never is.

    A query takes at most one step per range; a program that never
    finishes it is left to the window's check to catch.
    """
    for _ in range(2 * server.n_ranges + 2):
        if not (server.pending or server.active):
            return True
        server.step()
    return not (server.pending or server.active)


def warm(engine, cfg: dict, queries: np.ndarray, log=print) -> list[int]:
    """Compile the plan conversion of every width bucket the traffic reaches.

    A plan's block table is padded to its width bucket and converted on the
    device, one program per bucket; one query of each bucket is planned here
    so that none compiles in the window. The traversal program compiles in
    ``lead_in``, at the one width the window runs at.
    """
    from repro.serving import BucketSpec

    spec = BucketSpec(max_batch=int(cfg["n_slots"]))
    widths = plan_widths(engine.index, queries)
    buckets = np.asarray([spec.width_bucket(int(w)) for w in widths])
    ladder = sorted(set(buckets.tolist()))
    for w in ladder:
        engine.plan(queries[int(np.argmax(buckets == w))])
    log(f"warm: plan widths {ladder} (plans {int(widths.min())}..{int(widths.max())} blocks)")
    return ladder


def lead_in(server, engine, queries: np.ndarray, log=print) -> int:
    """Bring a fresh server to the width a long-running one has.

    The slot table only widens, to the widest plan served so far, and every
    step pays for the table's width. So a long-running server sits at the
    width of the widest plan its traffic has sent. ``queries`` are the
    mix's lead-in (the server's past) and the window's own; the widest of
    them is served here, through the server's own path, so the window runs
    at one width and compiles nothing.
    """
    widths = plan_widths(engine.index, queries)
    server.submit(queries[int(np.argmax(widths))])
    if not _serve_until_idle(server):
        log("lead-in: the widest query was not answered")
    width = server.buffers.front.width
    log(f"lead-in: widest of {len(queries)} plans {int(widths.max())} blocks, slot table width {width}")
    return width
