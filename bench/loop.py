"""The open loop: queries submitted when due, the server stepped in between.

One thread, the serving process's own: it submits every query that is due,
steps the server once, and sleeps only when nothing is due or in flight.
Latency runs from each query's due time to the end of the step that
answered it, so a stall delays every query due behind it. Once the window
has closed no more queries are due, and the loop keeps stepping until every
query is answered or the grace period has run out.

Stalls are named, not hidden: every pass of the loop that takes longer than
``STALL_S`` beyond what it meant to (a sleep counts only past its due time)
is kept with the main thread's CPU seconds in it and the garbage
collector's pause inside it. A stall with little CPU time is the thread
blocked or descheduled; one with CPU time and a collection is the collector.
"""

from __future__ import annotations

import dataclasses
import gc
import time

import numpy as np

from bench.system import Spans

__all__ = ["Drive", "GcClock", "Stall", "TraceWindow", "drive"]

GRACE_S = 60.0  # how long after the window a late answer still counts
STALL_S = 0.2  # a loop pass this much longer than meant is a stall


@dataclasses.dataclass
class TraceWindow:
    """A profiler trace of ``[start_s, start_s + seconds)`` of the window."""

    start_s: float
    seconds: float
    directory: str
    started: float | None = None  # host seconds since the window's start
    stopped: float | None = None


@dataclasses.dataclass
class Stall:
    at_s: float  # window seconds at the pass's start
    phase: str  # "step", "submit", "sleep", or "trace" (the profiler started or stopped)
    wall_ms: float  # beyond what the pass meant to take
    thread_ms: float  # the main thread's CPU time in the pass
    gc_ms: float  # collector pauses in the pass


class GcClock:
    """Pauses of Python's garbage collector while installed, by generation."""

    def __init__(self):
        self.total_s = 0.0
        self.longest_s = 0.0
        self.by_gen = [0, 0, 0]
        self._t = None

    def _cb(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            d = time.perf_counter() - self._t
            self.total_s += d
            self.longest_s = max(self.longest_s, d)
            self.by_gen[info["generation"]] += 1
            self._t = None

    def __enter__(self):
        gc.callbacks.append(self._cb)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self._cb)


@dataclasses.dataclass
class Drive:
    answered_s: np.ndarray  # [n] seconds from window start; nan = never
    submitted_s: np.ndarray  # [n]
    served: list  # [n] ServedQuery or None
    postings: list  # per step: postings the step scored (program counter)
    step_end_s: list  # per step: end time, seconds from window start
    backlog: list  # per step: (time, submitted - answered)
    compiles_in_window: int
    t0: float  # perf_counter at the window's start
    stalls: list  # Stall, longest first, at most MAX_STALLS
    gc: GcClock
    longest_step_ms: float


MAX_STALLS = 5


def _step_postings(server, before_rid, before_post, served) -> int:
    """Postings one step scored, from the program's per-lane counters."""
    prev = {int(r): int(p) for r, p in zip(before_rid, before_post) if r >= 0}
    post = np.asarray(server.carry.state.postings, np.int64)
    total = 0
    for lane in np.nonzero(server.slot_rid >= 0)[0]:
        total += int(post[lane]) - prev.get(int(server.slot_rid[lane]), 0)
    for sq in served:
        total += int(sq.result.postings) - prev.get(int(sq.rid), 0)
    return total


def drive(
    server,
    queries: np.ndarray,
    due: np.ndarray,
    seconds: float,
    spans: Spans,
    clock=None,
    trace: TraceWindow | None = None,
    grace_s: float = GRACE_S,
) -> Drive:
    import jax

    n = len(due)
    answered = np.full(n, np.nan)
    submitted = np.full(n, np.nan)
    served_by = [None] * n
    rid_idx: dict[int, int] = {}
    postings, step_end, backlog = [], [], []
    n_answered = 0
    i = 0
    tracing = False
    compiles0 = clock.compiles if clock is not None else 0

    stalls: list[Stall] = []
    longest_step = 0.0
    with GcClock() as gcc:
        t0 = time.perf_counter()
        while True:
            p_wall, p_cpu, p_gc = time.perf_counter(), time.thread_time(), gcc.total_s
            meant, phase = 0.0, "submit"
            now = p_wall - t0
            if trace is not None:
                if not tracing and trace.started is None and now >= trace.start_s:
                    jax.profiler.start_trace(trace.directory)
                    tracing, phase = True, "trace"
                    trace.started = time.perf_counter() - t0
                    window_span = spans.phase("window")
                    window_span.__enter__()
                elif tracing and now >= trace.started + trace.seconds:
                    window_span.__exit__(None, None, None)
                    trace.stopped = time.perf_counter() - t0
                    jax.profiler.stop_trace()
                    tracing, phase = False, "trace"
            with spans.phase("submit"):
                while i < n and due[i] <= now:
                    rid_idx[server.submit(queries[i])] = i
                    submitted[i] = now
                    i += 1
            if server.pending or server.active:
                phase = "step" if phase == "submit" else phase
                before_rid = server.slot_rid.copy()
                before_post = np.asarray(server.carry.state.postings).copy()
                n_plan = len(spans.plan)
                with spans.phase("step"):
                    ts = time.perf_counter()
                    served = server.step()
                    te = time.perf_counter()
                spans.step.append(te - ts)
                spans.step_start.append(ts)
                spans.step_plan.append(sum(spans.plan[n_plan:]))
                longest_step = max(longest_step, te - ts)
                postings.append(_step_postings(server, before_rid, before_post, served))
                step_end.append(te - t0)
                for sq in served:
                    j = rid_idx.pop(sq.rid)
                    answered[j] = te - t0
                    served_by[j] = sq
                n_answered += len(served)
                backlog.append((te - t0, i - n_answered))
            elif i < n:
                phase = "sleep" if phase == "submit" else phase
                with spans.phase("sleep"):
                    meant = max(0.0, due[i] - (time.perf_counter() - t0))
                    time.sleep(meant)
            elif tracing:
                time.sleep(0.001)
            else:
                break
            over = time.perf_counter() - p_wall - meant
            if over > STALL_S and not tracing:
                stalls.append(Stall(
                    at_s=now, phase=phase, wall_ms=1e3 * over,
                    thread_ms=1e3 * (time.thread_time() - p_cpu), gc_ms=1e3 * (gcc.total_s - p_gc),
                ))
                stalls = sorted(stalls, key=lambda x: -x.wall_ms)[:MAX_STALLS]
            if now > seconds + grace_s and not tracing:
                break
    compiles = (clock.compiles if clock is not None else 0) - compiles0
    return Drive(
        answered, submitted, served_by, postings, step_end, backlog, compiles, t0,
        stalls, gcc, 1e3 * longest_step,
    )
