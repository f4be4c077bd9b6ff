"""Continuous in-flight batching: a slot-based anytime serving loop.

``MicroBatchServer`` serves stop-and-go: collect a batch, pad, dispatch,
drain — under vmap every lane pays for the slowest lane's iterations
(``lax.cond`` lowers to ``select``), so one straggler query holds its whole
micro-batch hostage. That convoy effect is exactly the p99 behaviour the
anytime machinery exists to kill.

``InflightServer`` instead keeps one persistent device program hot and
treats each batch lane as a *slot* holding one query's traversal state —
top-k heap, range cursor, cumulative work counters, exit flags — stepped a
fixed range *quantum* at a time via ``range_daat.batched_traverse_resume``.
A query that exits (safe, budget, or exhausted) frees its slot mid-flight;
an admitted query from the queue swaps in on the next quantum without
recompiling and without waiting for its former batchmates. Vacant slots
ride along parked (``exit_budget`` raised): the resume loop leaves their
carry untouched, but under ``vmap`` the loop's condition is a per-lane
select, so a parked lane is still scored, and its result dropped, on
every iteration that another lane runs.

The carry stays on the device. The carry a dispatch returns is the next
dispatch's input; it is uploaded once, when the server is built. Between
dispatches the host records a per-lane code (keep, fresh or parked) for
every admission and park, and a step with any lane changed uploads that
code and runs one small program (``_reset_lanes``) that writes the lanes'
fresh or parked values. Right after the traversal is dispatched, a second
small program (``_pack_carry``) lays the carry out as one int32 array, and
its copy to the host starts then, under the planning that follows; the
fetch waits on that one array and unpacks it into ``self.carry``, the
host mirror that retirement reads.

Correctness contract (pinned tier-1 in tests/test_inflight.py): the carry
every dispatch receives is, leaf for leaf and bitwise, the host mirror —
the previous dispatch's carry with admitted lanes reset fresh and retired
lanes parked — so a query served across N quanta is *identical* — doc
ids, scores, work counters, exit reason — to the same query served by one
``device_traverse`` call.

Staging is double-buffered (``bucketing.DoubleBuffer``): the front
``SlotTable``'s snapshot is what the in-flight dispatch reads, lane writes
(clears for exits, admissions from the queue) land in the back table, and
the buffers swap between dispatches. Combined with JAX's async dispatch,
host-side query planning (``_plan_lookahead``) overlaps device execution
instead of serialising with it.

Budgets are fixed at *admission time* from the shared ``SlaBudgeter``
machinery: the rate EWMA learns postings/ms/lane from per-step device time,
while Reactive Eq. (7) judges each query's end-to-end latency (queue wait
included) at completion — the split introduced for `MicroBatchServer`'s
queue-aware feedback applies unchanged here.
"""

from __future__ import annotations

import functools
from collections import deque
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.clustered_index import BLOCK
from repro.core.range_daat import (
    QueryPlan,
    TraverseCarry,
    batched_init_carry,
    batched_traverse_resume,
    carry_done,
)
from repro.serving.batch_engine import (
    INT32_MAX,
    BatchEngine,
    lane_result,
)
from repro.obs import NOOP
from repro.obs.profiler import jit_cache_size
from repro.serving.bucketing import DoubleBuffer
from repro.serving.microbatch import ServedQuery, SlaBudgeter, result_exit_reason

__all__ = ["InflightServer"]


# Per-lane codes of what changed in the host mirror since the last dispatch.
KEEP, FRESH, PARKED = 0, 1, 2


def _carry_to_device(carry: TraverseCarry) -> TraverseCarry:
    return jax.tree_util.tree_map(jnp.asarray, carry)


def _lane_values(k: int) -> tuple[TraverseCarry, TraverseCarry]:
    """One-lane host carries holding a fresh lane's values and a parked one's."""
    return batched_init_carry(1, k, parked=False), batched_init_carry(1, k, parked=True)


@functools.partial(jax.jit, static_argnames="k")
def _reset_lanes(carry: TraverseCarry, code: jnp.ndarray, *, k: int) -> TraverseCarry:
    """``carry`` with each ``FRESH`` lane reset fresh and each ``PARKED`` lane parked.

    The values are ``_lane_values``', the ones ``InflightServer`` writes
    into its host mirror, so the two stay bitwise equal. ``code`` is
    ``[n]`` over the leaves' leading (lane) axis; ``KEEP`` lanes pass
    through.
    """
    fresh, parked = _lane_values(k)

    def lane(x, f, p):
        c = code.reshape(code.shape + (1,) * (x.ndim - 1))
        return jnp.where(c == FRESH, f, jnp.where(c == PARKED, p, x))

    return jax.tree_util.tree_map(lane, carry, fresh, parked)


@jax.jit
def _pack_carry(carry: TraverseCarry) -> jnp.ndarray:
    """Every leaf of a lane-major carry as int32 columns of one ``[n, 2k+5]`` array."""
    return jnp.concatenate(
        [
            x.reshape(x.shape[0], -1).astype(jnp.int32)
            for x in jax.tree_util.tree_leaves(carry)
        ],
        axis=1,
    )


def _carry_to_host(packed: jnp.ndarray, like: TraverseCarry) -> TraverseCarry:
    """The step's one device-to-host copy: ``_pack_carry``'s array, unpacked.

    Leaves come back with ``like``'s shapes and dtypes, as views into one
    host array (bools as casts). np.array (not asarray): the host copy is
    mutated in place on lane admission/parking and must not alias a
    device buffer.
    """
    flat = np.array(packed)
    leaves, tree = jax.tree_util.tree_flatten(like)
    out, col = [], 0
    for x in leaves:
        w = x[0].size
        out.append(flat[:, col : col + w].reshape(x.shape).astype(x.dtype, copy=False))
        col += w
    return jax.tree_util.tree_unflatten(tree, out)


class InflightServer:
    """Slot-swapping continuous serving loop over one device program.

    Parameters
    ----------
    bengine: planning + engine access (``BatchEngine``); the in-flight path
        dispatches ``batched_traverse_resume`` itself rather than going
        through ``run_batch``.
    budgeter: ``SlaBudgeter`` (or subclass) — admission-time postings
        budgets plus the Eq. (7) feedback loop.
    n_slots: batch lanes in the persistent program. Unlike micro-batching
        there is no batch-size ladder: one program per (n_slots, width).
    quantum: ranges traversed per dispatch per lane. Small quanta swap
        slots promptly (better p99 under skew) at more dispatch overhead;
        large quanta amortise dispatch but re-introduce convoy time up to
        ``quantum - 1`` ranges.
    """

    def __init__(
        self,
        bengine: BatchEngine,
        budgeter: SlaBudgeter,
        n_slots: int = 8,
        quantum: int = 1,
        clock=None,
        obs=NOOP,
    ):
        if n_slots < 1:
            raise ValueError(f"n_slots must be >= 1, got {n_slots}")
        if quantum < 1:
            raise ValueError(f"quantum must be >= 1, got {quantum}")
        self.bengine = bengine
        self.engine = bengine.engine
        self.budgeter = budgeter
        self.n_slots = int(n_slots)
        self.quantum = int(quantum)
        self.obs = obs
        # Same clock-resolution rule as MicroBatchServer: explicit wins,
        # else the instrumentation handle's (wall clock on NOOP).
        self.clock = clock if clock is not None else obs.clock
        self.n_ranges = int(self.engine.index.n_ranges)

        self.buffers = DoubleBuffer(
            self.n_slots, self.n_ranges, bengine.spec.min_width
        )
        # Every lane starts parked (vacant). The device carry is uploaded
        # here, once; ``carry`` is its host mirror, refreshed by each fetch.
        k = self.engine.k
        self.carry = batched_init_carry(self.n_slots, k, parked=True)
        self._dcarry = _carry_to_device(self.carry)
        self._lane_init = _lane_values(k)
        self._lane_code = np.full(self.n_slots, KEEP, np.int32)
        if obs.enabled:
            obs.count("carry_uploads", server="inflight")

        self.slot_rid = np.full(self.n_slots, -1, dtype=np.int64)
        self.slot_t_enq = np.zeros(self.n_slots, dtype=np.float64)
        self.slot_t_adm = np.zeros(self.n_slots, dtype=np.float64)
        self.slot_quanta = np.zeros(self.n_slots, dtype=np.int64)
        self._prev_postings = np.zeros(self.n_slots, dtype=np.int64)

        self._queue: deque[tuple[int, np.ndarray, float]] = deque()
        self._planned: deque[tuple[int, QueryPlan, float]] = deque()
        self._next_rid = 0

        self.compiled_shapes: set[tuple[int, int]] = set()
        self.steps_run = 0
        self.admissions = 0

    # ------------------------------------------------------------- ingress
    def submit(self, q_terms: np.ndarray) -> int:
        rid = self._next_rid
        self._next_rid += 1
        self._queue.append((rid, np.asarray(q_terms), self.clock()))
        if self.obs.enabled:
            self.obs.count("submitted", server="inflight")
            self.obs.trace_begin(rid)
        return rid

    @property
    def pending(self) -> int:
        """Queued + planned, not yet holding a slot."""
        return len(self._queue) + len(self._planned)

    @property
    def active(self) -> int:
        """Slots currently occupied by an in-flight query."""
        return int((self.slot_rid >= 0).sum())

    # ----------------------------------------------------------- admission
    def _plan_lookahead(self, limit: int, step: int) -> None:
        """Plan up to ``limit`` queued queries ahead of slot availability.

        Called right after a dispatch goes out: under async dispatch the
        planning work (term lookup, block-table build, range ordering)
        runs on the host while the device is still scoring the quantum.
        """
        while self._queue and len(self._planned) < limit:
            rid, q_terms, t_enq = self._queue.popleft()
            with self.obs.phase("plan", server="inflight", step=step):
                plan = self.bengine.plan(q_terms)
            self._planned.append((rid, plan, t_enq))

    def _admission_budget(self, plan: QueryPlan) -> int:
        b = np.asarray(self.budgeter.budgets(1, plans=[plan]), dtype=np.int64)
        if b.ndim == 2:  # sharded budgeter: one engine serves the sum
            b = b.sum(axis=1)
        return int(min(int(b[0]), INT32_MAX))

    def _reset_carry_lane(self, lane: int, parked: bool) -> None:
        """Reset ``lane`` in the host mirror; the next launch does it on the device."""
        values = self._lane_init[int(parked)]
        for x, v in zip(jax.tree_util.tree_leaves(self.carry), jax.tree_util.tree_leaves(values)):
            x[lane] = v[0]
        self._lane_code[lane] = PARKED if parked else FRESH

    def _reset_device_lanes(self) -> None:
        """Replay the mirror's lane resets on the device carry, if any lane changed.

        One ``[n_slots]`` upload and one small dispatch; a step with no
        admission or park since the last dispatch does neither.
        """
        code = self._lane_code
        if not code.any():
            return
        self._dcarry = _reset_lanes(self._dcarry, jnp.asarray(code), k=self.engine.k)
        self._lane_code = np.full(self.n_slots, KEEP, np.int32)
        if self.obs.enabled:
            self.obs.count("carry_resets", server="inflight")
            for kind, c in (("fresh", FRESH), ("parked", PARKED)):
                lanes = int((code == c).sum())
                if lanes:
                    self.obs.count("carry_lanes_reset", lanes, server="inflight", kind=kind)

    def _admit(self, lane: int, rid: int, plan: QueryPlan, t_enq: float) -> None:
        width = self.bengine.spec.width_bucket(plan.blk_tab.shape[1])
        if width > self.buffers.back.width:
            # Width growth is the only program-shape change; the pow2
            # ladder bounds how many (n_slots, width) compiles can occur.
            self.buffers.grow_width(width)
        budget = self._admission_budget(plan)
        self.buffers.back.write_lane(lane, plan, budget=budget)
        self._reset_carry_lane(lane, parked=False)
        self.slot_rid[lane] = rid
        self.slot_t_enq[lane] = t_enq
        self.slot_quanta[lane] = 0
        self._prev_postings[lane] = 0
        self.admissions += 1
        if self.obs.enabled:
            now = self.clock()
            self.slot_t_adm[lane] = now
            self.obs.count("admissions", server="inflight")
            if budget >= INT32_MAX:
                # Unlimited (inf-SLA) admissions would pin the histogram's
                # p50 at the INT32_MAX sentinel; count them separately and
                # keep the budget distribution finite-only (ISSUE 9).
                self.obs.count("unlimited_admissions", server="inflight")
            else:
                self.obs.observe("budget_postings", budget, server="inflight")
            self.obs.trace_span(rid, "queue", t_enq, now)
            self.obs.trace_attr(rid, budget_postings=budget, slot=lane)

    def _park(self, lane: int) -> None:
        self.buffers.back.clear_lane(lane)
        self._reset_carry_lane(lane, parked=True)
        self.slot_rid[lane] = -1
        self.slot_quanta[lane] = 0
        self._prev_postings[lane] = 0

    def _admit_vacant(self, step: int) -> None:
        for lane in np.nonzero(self.slot_rid < 0)[0]:
            if not self._planned:
                self._plan_lookahead(1, step)
                if not self._planned:
                    break
            rid, plan, t_enq = self._planned.popleft()
            self._admit(int(lane), rid, plan, t_enq)

    # ------------------------------------------------------------ stepping
    def step(self) -> list[ServedQuery]:
        """One quantum: admit, dispatch, fetch, retire exited slots.

        Each part runs inside an ``obs.phase`` (``admit``, ``launch`` with
        ``upload`` and ``dispatch`` in it, ``plan_ahead``, ``fetch``,
        ``retire``; ``plan`` around every plan), numbered by the step's
        ``steps_run`` so that a query's ``dispatch`` span joins them.
        """
        obs = self.obs
        n = self.steps_run + 1
        with obs.phase("admit", server="inflight", step=n):
            self._admit_vacant(n)
        if self.active == 0:
            return []
        eng = self.engine

        prof = obs.profiler if obs.enabled else None
        with obs.phase("launch", server="inflight", step=n) as launch:
            with obs.phase("upload", server="inflight", step=n):
                self.buffers.swap()  # pending lane writes go live
                front = self.buffers.front
                if prof is not None:
                    cache0 = jit_cache_size(batched_traverse_resume)
                t0 = self.clock()
                blk, rest, order, bounds, budget, maxr = front.device_arrays()
                self._reset_device_lanes()
            with obs.phase("dispatch", server="inflight", step=n):
                out = batched_traverse_resume(
                    eng.dix,
                    blk,
                    rest,
                    order,
                    bounds,
                    budget,
                    maxr,
                    self._dcarry,
                    s_pad=eng.s_pad,
                    k=eng.k,
                    quantum=self.quantum,
                    impl=eng.impl,
                    docs_format=eng.docs_format,
                )
                self._dcarry = out
                # Start the carry's one copy home now: it runs as soon as the
                # device is done, under the planning below.
                packed = _pack_carry(out)
                packed.copy_to_host_async()
        self.compiled_shapes.add((self.n_slots, front.width))
        self.steps_run = n

        # Async dispatch: the device is scoring; overlap host-side planning
        # for the admissions this step's exits will make room for.
        with obs.phase("plan_ahead", server="inflight", step=n) as ahead:
            self._plan_lookahead(self.n_slots, n)

        with obs.phase("fetch", server="inflight", step=n) as fetch:
            if prof is not None:
                # Timing-only sync: splits the device wait out of the carry
                # fetch below. Results are untouched.
                jax.block_until_ready(packed)  # analysis: allow[HOSTSYNC]
                t_dev1 = obs.clock()
            self.carry = _carry_to_host(packed, self.carry)  # blocks until the quantum lands
            t1 = self.clock()
        step_ms = (t1 - t0) * 1e3

        with obs.phase("retire", server="inflight", step=n):
            if prof is not None:
                # The host split reads the phase boundaries: one set of
                # clock readings a step, plus the fence's.
                prof.record_dispatch(
                    "inflight",
                    (self.n_slots, front.width),
                    cache_before=cache0,
                    cache_after=jit_cache_size(batched_traverse_resume),
                    plan_ms=ahead.ms,
                    dispatch_ms=launch.ms,
                    device_ms=(t_dev1 - fetch.t0) * 1e3,
                    transfer_ms=(fetch.t1 - t_dev1) * 1e3,
                )
                prof.record_hbm_once("inflight", eng.dix._asdict())
            return self._retire(t0, t1, step_ms)

    def _retire(self, t0: float, t1: float, step_ms: float) -> list[ServedQuery]:
        """Count the quantum's work, answer and park exited lanes, feed the budgeter."""
        active = self.slot_rid >= 0
        postings = np.asarray(self.carry.state.postings, dtype=np.int64)
        delta = int((postings[active] - self._prev_postings[active]).sum())
        self._prev_postings[active] = postings[active]
        self.slot_quanta[active] += 1

        obs = self.obs
        if obs.enabled:
            obs.observe("step_ms", step_ms, server="inflight")
            # Every slot gathers its whole block table each range step, so
            # this is the percent of gathered lanes that held a real posting.
            lanes = self.n_slots * self.quantum * self.buffers.front.width * BLOCK
            obs.observe("gather_fill", 100.0 * delta / lanes, server="inflight")
            obs.gauge(
                "slot_occupancy", float(active.sum()) / self.n_slots,
                server="inflight",
            )
            obs.gauge("queue_depth", float(self.pending), server="inflight")
            for lane in np.nonzero(active)[0]:
                # The quantum's host-observed wall time, shared by every
                # lane riding this dispatch.
                obs.trace_span(
                    int(self.slot_rid[lane]), "dispatch", t0, t1,
                    step_ms=round(step_ms, 4), step=self.steps_run,
                )

        served: list[ServedQuery] = []
        done = carry_done(self.carry, self.n_ranges) & active
        vals = self.carry.state.vals
        ids = self.carry.state.ids
        blocks = self.carry.state.blocks
        sla = getattr(self.budgeter, "sla_ms", None)
        for lane in np.nonzero(done)[0]:
            lane = int(lane)
            sq = ServedQuery(
                rid=int(self.slot_rid[lane]),
                result=lane_result(
                    vals,
                    ids,
                    postings,
                    blocks,
                    self.carry.i,
                    self.carry.exit_safe,
                    self.carry.exit_budget,
                    lane,
                ),
                latency_ms=(t1 - self.slot_t_enq[lane]) * 1e3,
                batch_size=self.n_slots,
                quanta=int(self.slot_quanta[lane]),
            )
            served.append(sq)
            if obs.enabled:
                reason = result_exit_reason(sq.result)
                obs.count("served_queries", server="inflight", reason=reason)
                obs.observe("latency_ms", sq.latency_ms, server="inflight")
                obs.observe("quanta", sq.quanta, server="inflight")
                obs.trace_span(
                    sq.rid, "service", float(self.slot_t_adm[lane]), t1,
                    quanta=sq.quanta,
                )
                attrs = dict(
                    server="inflight",
                    latency_ms=round(sq.latency_ms, 4),
                    exit_reason=reason,
                    quanta=sq.quanta,
                )
                if sla is not None and sla != float("inf"):
                    attrs["sla_ms"] = float(sla)
                obs.trace_attr(sq.rid, **attrs)
                obs.trace_end(sq.rid)
            self._park(lane)

        # Rate EWMA from device step time; Eq. (7) from end-to-end latency
        # of the queries that completed this quantum (none: rate-only).
        self.budgeter.observe(
            step_ms,
            delta,
            int(active.sum()),
            latencies_ms=[s.latency_ms for s in served],
        )
        return served

    # -------------------------------------------------------------- loops
    def run_until_idle(self, max_steps: int = 1_000_000) -> list[ServedQuery]:
        out: list[ServedQuery] = []
        steps = 0
        while self.pending or self.active:
            out.extend(self.step())
            steps += 1
            if steps >= max_steps:
                raise RuntimeError(
                    f"in-flight loop still busy after {max_steps} steps "
                    f"(pending={self.pending} active={self.active})"
                )
        return out

    def replay(self, queries: Sequence[np.ndarray]) -> list[ServedQuery]:
        """Offline replay: enqueue everything, slot-swap until drained."""
        for q in queries:
            self.submit(q)
        return self.run_until_idle()
