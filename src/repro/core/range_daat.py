"""Range-aware anytime DAAT traversal (paper §3, DESIGN.md §2).

Two execution modes over the same per-range scoring step:

  * host-driven — one jitted ``score_range_step`` per range with the go/no-go
    decision taken on the host between steps (the paper's steady_clock loop;
    this is also how a real TPU deployment would interleave device steps with
    SLA decisions), used by core.anytime;
  * device-driven — ``device_traverse`` runs the whole query in a
    ``lax.while_loop`` with a postings budget (the deterministic JASS-style
    proxy), fully jittable and vmappable for batched serving.

Baselines share this engine via flags (DESIGN.md §2 table):
  ordering="boundsum"|"docid"  ×  bounds="range"|"global"  ×  safe/budget/fixed.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bound_sum
from repro.core.clustered_index import BLOCK, ClusteredIndex, pack_dir_entries
from repro.kernels.range_scorer import ops as scorer_ops

__all__ = [
    "DOCS_FORMATS",
    "DeviceIndex",
    "IMPACT_BIAS",
    "IMPACT_DTYPES",
    "TopKState",
    "TraverseCarry",
    "TraverseResult",
    "QueryPlan",
    "Engine",
    "init_state",
    "init_carry",
    "batched_init_carry",
    "carry_done",
    "carry_result",
    "merge_topk",
    "pack_impacts",
    "score_range_step",
    "device_traverse",
    "batched_traverse",
    "batched_traverse_resume",
    "topk_docs",
    "batched_topk_docs",
    "exit_reason",
    "exit_reasons",
]

IMPACT_BIAS = scorer_ops.IMPACT_BIAS
IMPACT_DTYPES = ("int32", "int8")
DOCS_FORMATS = scorer_ops.DOCS_FORMATS


def pack_impacts(impacts: np.ndarray, impact_dtype: str) -> np.ndarray:
    """Host impacts (true int32 codes) -> device storage representation.

    ``"int32"`` uploads impacts verbatim; ``"int8"`` stores the biased code
    ``impact - IMPACT_BIAS`` so 8-bit quantized impacts (range [1, 255])
    fit a signed byte — 1 B/posting in HBM, widened back inside the scorer
    gather (DESIGN.md §8). Requires every impact <= 2^8 - 1; the caller
    (``Engine``) enforces this via the quantizer's bit width.
    """
    if impact_dtype == "int32":
        return np.asarray(impacts, np.int32)
    if impact_dtype == "int8":
        return (np.asarray(impacts, np.int64) - IMPACT_BIAS).astype(np.int8)
    raise ValueError(f"impact_dtype {impact_dtype!r} not in {IMPACT_DTYPES}")


class DeviceIndex(NamedTuple):
    """jnp mirror of the host index (flat arrays only — a valid pytree).

    Under ``docs_format="packed"`` (DESIGN.md §12), ``docs`` shrinks to a
    (1,)-placeholder (never gathered) and the three ``pack_*`` leaves carry
    the bit-packed delta stream plus its per-block merged directory
    (``pack_dir_entries``), parallel to ``blk_start``. They default to
    None in the raw-int32 layout; None leaves vanish from the pytree, so
    vmap/shard_map over either shape works unchanged.
    """

    docs: jnp.ndarray  # [nnz] int32 (packed: [1] placeholder)
    impacts: jnp.ndarray  # [nnz] int32, or int8 biased by IMPACT_BIAS (§8)
    blk_start: jnp.ndarray  # [NB] int32
    blk_len: jnp.ndarray  # [NB] int32
    blk_maximp: jnp.ndarray  # [NB] int32
    bounds_dense: jnp.ndarray  # [V, R] int32
    range_starts: jnp.ndarray  # [R] int32
    range_sizes: jnp.ndarray  # [R] int32
    pack_words: jnp.ndarray | None = None  # [n_words] uint32 delta stream
    pack_dir: jnp.ndarray | None = None  # [NB] int32 merged (start | width)
    pack_first: jnp.ndarray | None = None  # [NB] int32 first docid per block


class TopKState(NamedTuple):
    vals: jnp.ndarray  # [k] int32, sorted descending (0 = empty slot)
    ids: jnp.ndarray  # [k] int32 (-1 = empty)
    postings: jnp.ndarray  # scalar int32 — postings scored so far
    blocks: jnp.ndarray  # scalar int32 — blocks processed so far


def init_state(k: int) -> TopKState:
    return TopKState(
        vals=jnp.zeros((k,), jnp.int32),
        ids=jnp.full((k,), -1, jnp.int32),
        postings=jnp.zeros((), jnp.int32),
        blocks=jnp.zeros((), jnp.int32),
    )


def theta(state: TopKState) -> jnp.ndarray:
    """Heap-entry threshold: k-th largest score so far (0 while unfilled)."""
    return state.vals[-1]


def _merge_topk(
    vals_a: jnp.ndarray,
    ids_a: jnp.ndarray,
    vals_b: jnp.ndarray,
    ids_b: jnp.ndarray,
    k: int,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Deterministic top-k merge: higher score first, then smaller docid.

    The (score desc, docid asc) total order makes tie-breaking identical to
    the host oracle, so safe traversals reproduce the oracle ranking
    *exactly*, not merely as a score multiset. Sorting 2k int32 elements is
    cheap (k <= 1000) and stays in int32 for the TPU target. Delegates to
    ``merge_topk`` so the comparator is structurally shared with the
    sharded broker merge — the bitwise-parity contract of DESIGN.md §4.
    """
    return merge_topk(
        jnp.concatenate([vals_a, vals_b]), jnp.concatenate([ids_a, ids_b]), k
    )


def merge_topk(vals: jnp.ndarray, ids: jnp.ndarray, k: int) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Top-k of flat (vals, ids) candidates under the heap's total order.

    Identical comparator to ``_merge_topk`` — score descending, docid
    ascending, empty slots (id < 0) last — so any grouping of candidates
    (incremental per-range merges on one device, or per-shard heaps merged
    by a broker) yields the same k winners bit-for-bit (DESIGN.md §4).
    """
    i_key = jnp.where(ids >= 0, ids, jnp.iinfo(jnp.int32).max)
    sel = jnp.lexsort((i_key, -vals))[:k]
    return vals[sel], ids[sel]


@functools.partial(
    jax.jit,
    static_argnames=("s_pad", "k", "impl", "prune_blocks", "docs_format"),
)
def score_range_step(
    dix: DeviceIndex,
    state: TopKState,
    blk_ids: jnp.ndarray,  # [B] int32/int64, -1 padded
    rest: jnp.ndarray,  # [B] int32 — bound sum of *other* terms for pruning
    range_start: jnp.ndarray,  # scalar int32
    *,
    s_pad: int,
    k: int,
    impl: str = "xla",
    prune_blocks: bool = True,
    docs_format: str = "int32",
) -> TopKState:
    """Score one range and merge its top-k into the running state."""
    th = theta(state)
    safe_ids = jnp.clip(blk_ids, 0).astype(jnp.int32)
    starts = dix.blk_start[safe_ids]
    lens = dix.blk_len[safe_ids]
    maximp = dix.blk_maximp[safe_ids]
    keep = blk_ids >= 0
    if prune_blocks:
        # Block-level refinement (paper "Improved Pruning With Local Range
        # Bounds"): a block survives only if its own max impact plus the other
        # terms' bounds can reach the current threshold. A doc that only ties
        # the k-th score still enters the heap when its docid is smaller
        # (score desc, docid asc), so equality must keep the block.
        keep = keep & (maximp + rest >= th)

    pack_kw = {}
    if docs_format == "packed":
        # Per-block packed directory rows travel with the block table; the
        # shared word stream goes through whole (DESIGN.md §12).
        pack_kw = dict(
            pack_words=dix.pack_words,
            pack_dir=dix.pack_dir[safe_ids],
            pack_firsts=dix.pack_first[safe_ids],
        )
    acc = scorer_ops.score_blocks(
        dix.docs,
        dix.impacts,
        starts,
        lens,
        keep,
        range_start,
        s_pad=s_pad,
        impl=impl,
        docs_format=docs_format,
        **pack_kw,
    )

    vals, loc = jax.lax.top_k(acc, k)
    cand_ids = jnp.where(vals > 0, loc.astype(jnp.int32) + range_start, -1)
    nv, ni = _merge_topk(state.vals, state.ids, vals.astype(jnp.int32), cand_ids, k)
    return TopKState(
        vals=nv,
        ids=ni,
        postings=state.postings + jnp.sum(jnp.where(keep, lens, 0), dtype=jnp.int32),
        blocks=state.blocks + jnp.sum(keep, dtype=jnp.int32),
    )


class TraverseResult(NamedTuple):
    state: TopKState
    ranges_processed: jnp.ndarray  # int32
    exit_safe: jnp.ndarray  # bool — stopped because remaining bounds < theta
    exit_budget: jnp.ndarray  # bool — stopped by postings budget / fixed-n


class TraverseCarry(NamedTuple):
    """Resumable mid-flight traversal state (DESIGN.md §11).

    Exactly the ``device_traverse`` while_loop carry: the cursor into the
    processing order, the running top-k heap state (whose ``postings``
    counter is cumulative, so the postings budget keeps its meaning across
    calls), and the two exit flags. Every leaf is int32/bool, so a carry
    round-trips bitwise through host numpy — a query stepped ``quantum``
    ranges at a time over many dispatches finishes with leaves identical
    to one uninterrupted ``device_traverse`` call.
    """

    i: jnp.ndarray  # int32 — cursor into the processing order
    state: TopKState
    exit_safe: jnp.ndarray  # bool
    exit_budget: jnp.ndarray  # bool


def init_carry(k: int) -> TraverseCarry:
    """A fresh single-query carry (cursor 0, empty heap, no exit flags)."""
    return TraverseCarry(
        i=jnp.zeros((), jnp.int32),
        state=init_state(k),
        exit_safe=jnp.zeros((), bool),
        exit_budget=jnp.zeros((), bool),
    )


def batched_init_carry(n: int, k: int, parked: bool = False) -> TraverseCarry:
    """[n]-lane host (numpy) carry, fresh on every lane.

    ``parked=True`` raises every lane's ``exit_budget`` flag: a parked lane
    is inert — the resume loop's condition fails before any work — which is
    how vacant in-flight slots ride along in a dispatch at zero cost.
    """
    return TraverseCarry(
        i=np.zeros(n, np.int32),
        state=TopKState(
            vals=np.zeros((n, k), np.int32),
            ids=np.full((n, k), -1, np.int32),
            postings=np.zeros(n, np.int32),
            blocks=np.zeros(n, np.int32),
        ),
        exit_safe=np.zeros(n, bool),
        exit_budget=np.full(n, parked, bool),
    )


def carry_done(carry: TraverseCarry, n_ranges: int) -> np.ndarray:
    """Host-side completion mask: an exit flag fired, or the order is spent.

    Matches ``device_traverse``'s exit condition exactly — a lane whose
    flags are still False but whose cursor reached R exited "exhausted".
    """
    return (
        np.asarray(carry.exit_safe)
        | np.asarray(carry.exit_budget)
        | (np.asarray(carry.i) >= n_ranges)
    )


def carry_result(carry: TraverseCarry) -> TraverseResult:
    """View a (finished) carry as the equivalent ``TraverseResult``."""
    return TraverseResult(
        state=carry.state,
        ranges_processed=carry.i,
        exit_safe=carry.exit_safe,
        exit_budget=carry.exit_budget,
    )


def _traverse_loop(
    dix: DeviceIndex,
    blk_tab: jnp.ndarray,  # [R, B]
    rest_tab: jnp.ndarray,  # [R, B]
    order: jnp.ndarray,  # [R]
    ordered_bounds: jnp.ndarray,  # [R]
    carry: TraverseCarry,
    budget: jnp.ndarray,  # scalar int32
    maxr: jnp.ndarray,  # scalar int32
    *,
    s_pad: int,
    k: int,
    quantum: int | None,
    safe_stop: bool,
    prune_blocks: bool,
    impl: str,
    docs_format: str = "int32",
) -> TraverseCarry:
    """The one range-at-a-time while_loop both entry points share.

    ``quantum=None`` runs to an exit condition (``device_traverse``);
    ``quantum=Q`` additionally stops after Q loop iterations, returning the
    carry mid-flight. The per-iteration arithmetic is identical either way,
    which is what makes resumed traversals bitwise-equal to uninterrupted
    ones: the same ``score_range_step`` calls happen against the same
    states, only sliced across more dispatches. (The iteration that
    discovers an exit condition scores nothing and leaves the cursor alone,
    so resuming past a quantum boundary re-derives the same flags.)
    """
    R = blk_tab.shape[0]

    def cond(c):
        steps, i, state, stop_safe, stop_budget = c
        live = (i < R) & ~stop_safe & ~stop_budget
        if quantum is not None:
            live = live & (steps < quantum)
        return live

    def body(c):
        steps, i, state, stop_safe, stop_budget = c
        r = order[i]
        bound = ordered_bounds[i]
        th = theta(state)
        # Safe termination: every remaining range is bounded by this one,
        # strictly below theta — a remaining doc tied at theta with a
        # smaller docid would still displace the k-th entry.
        s_safe = safe_stop & (bound < th) & (th > 0)
        s_budget = (state.postings >= budget) | (i >= maxr)
        do = ~(s_safe | s_budget)

        def run(st):
            return score_range_step(
                dix,
                st,
                blk_tab[r],
                rest_tab[r],
                dix.range_starts[r],
                s_pad=s_pad,
                k=k,
                impl=impl,
                prune_blocks=prune_blocks,
                docs_format=docs_format,
            )

        # A select, not lax.cond: under vmap a cond on a per-lane predicate
        # becomes a select anyway, and would first broadcast every closed-over
        # operand, the whole index included, to one copy per lane.
        state = jax.tree.map(functools.partial(jnp.where, do), run(state), state)
        return (steps + 1, i + jnp.where(do, 1, 0), state, s_safe, s_budget)

    c0 = (
        jnp.zeros((), jnp.int32),
        jnp.asarray(carry.i, jnp.int32),
        carry.state,
        jnp.asarray(carry.exit_safe, bool),
        jnp.asarray(carry.exit_budget, bool),
    )
    _, i, state, s_safe, s_budget = jax.lax.while_loop(cond, body, c0)
    return TraverseCarry(i=i, state=state, exit_safe=s_safe, exit_budget=s_budget)


@functools.partial(
    jax.jit,
    static_argnames=(
        "s_pad", "k", "impl", "prune_blocks", "safe_stop",
        "docs_format",
    ),
)
def device_traverse(
    dix: DeviceIndex,
    blk_tab: jnp.ndarray,  # [R, B] int32, -1 padded — per-range block ids
    rest_tab: jnp.ndarray,  # [R, B] int32
    order: jnp.ndarray,  # [R] int32 — processing order of ranges
    ordered_bounds: jnp.ndarray,  # [R] int32 — BoundSum of order[i] (0 if unused)
    *,
    s_pad: int,
    k: int,
    budget_postings: jnp.ndarray | int = 2**31 - 1,
    max_ranges: jnp.ndarray | int = 2**31 - 1,
    safe_stop: bool = True,
    prune_blocks: bool = True,
    impl: str = "xla",
    docs_format: str = "int32",
) -> TraverseResult:
    """Whole-query traversal in a lax.while_loop (device-side anytime mode)."""
    carry = _traverse_loop(
        dix,
        blk_tab,
        rest_tab,
        order,
        ordered_bounds,
        init_carry(k),
        jnp.asarray(budget_postings, jnp.int32),
        jnp.asarray(max_ranges, jnp.int32),
        s_pad=s_pad,
        k=k,
        quantum=None,
        safe_stop=safe_stop,
        prune_blocks=prune_blocks,
        impl=impl,
        docs_format=docs_format,
    )
    return carry_result(carry)


@functools.partial(
    jax.jit,
    static_argnames=(
        "s_pad", "k", "impl", "prune_blocks", "safe_stop",
        "docs_format",
    ),
)
def batched_traverse(
    dix: DeviceIndex,
    blk_tabs: jnp.ndarray,  # [N, R, B] int32, -1 padded
    rest_tabs: jnp.ndarray,  # [N, R, B] int32
    orders: jnp.ndarray,  # [N, R] int32
    ordered_bounds: jnp.ndarray,  # [N, R] int32
    budgets: jnp.ndarray,  # [N] int32 — per-query postings budgets
    max_ranges: jnp.ndarray,  # [N] int32 — per-query range budgets
    *,
    s_pad: int,
    k: int,
    safe_stop: bool = True,
    prune_blocks: bool = True,
    impl: str = "xla",
    docs_format: str = "int32",
) -> TraverseResult:
    """vmapped ``device_traverse`` over a stacked batch of query plans.

    The index is broadcast (in_axes=None); every plan leaf and both budgets
    map over the leading batch axis, so one lagging query cannot consume
    another query's budget — each lane carries its own stop flags and the
    while_loop simply runs until the *last* lane finishes, with finished
    lanes masked to no-ops by their own ``exit_*`` state. The returned
    ``TraverseResult`` has batched leaves: ``state.vals`` is [N, k],
    ``ranges_processed`` / ``exit_safe`` / ``exit_budget`` are [N].
    """

    def one(bt, rt, o, ob, bud, mr):
        return device_traverse(
            dix,
            bt,
            rt,
            o,
            ob,
            s_pad=s_pad,
            k=k,
            budget_postings=bud,
            max_ranges=mr,
            safe_stop=safe_stop,
            prune_blocks=prune_blocks,
            impl=impl,
            docs_format=docs_format,
        )

    return jax.vmap(one)(
        blk_tabs, rest_tabs, orders, ordered_bounds, budgets, max_ranges
    )


@functools.partial(
    jax.jit,
    static_argnames=(
        "s_pad", "k", "quantum", "impl", "prune_blocks", "safe_stop",
        "docs_format",
    ),
)
def batched_traverse_resume(
    dix: DeviceIndex,
    blk_tabs: jnp.ndarray,  # [N, R, B] int32, -1 padded
    rest_tabs: jnp.ndarray,  # [N, R, B] int32
    orders: jnp.ndarray,  # [N, R] int32
    ordered_bounds: jnp.ndarray,  # [N, R] int32
    budgets: jnp.ndarray,  # [N] int32 — per-lane postings budgets
    max_ranges: jnp.ndarray,  # [N] int32 — per-lane range budgets
    carry: TraverseCarry,  # [N]-batched leaves
    *,
    s_pad: int,
    k: int,
    quantum: int,
    safe_stop: bool = True,
    prune_blocks: bool = True,
    impl: str = "xla",
    docs_format: str = "int32",
) -> TraverseCarry:
    """Resumable entry point: advance every lane at most ``quantum`` ranges.

    The in-flight serving primitive (DESIGN.md §11). Each lane carries one
    query's mid-flight ``TraverseCarry``; one dispatch steps all lanes by a
    bounded number of while_loop iterations and returns the updated carries.
    Lanes whose exit flags are already set (finished queries, parked slots)
    fail the loop condition immediately and ride along untouched, so a
    mixed batch of fresh, mid-flight, and vacant lanes costs one program.

    Chaining dispatches until ``carry_done`` is bitwise-equivalent to one
    ``device_traverse`` call per lane — same heap, counters, and exit flags
    (tests/test_inflight.py pins this tier-1).
    """

    def one(bt, rt, o, ob, bud, mr, c):
        return _traverse_loop(
            dix,
            bt,
            rt,
            o,
            ob,
            c,
            jnp.asarray(bud, jnp.int32),
            jnp.asarray(mr, jnp.int32),
            s_pad=s_pad,
            k=k,
            quantum=quantum,
            safe_stop=safe_stop,
            prune_blocks=prune_blocks,
            impl=impl,
            docs_format=docs_format,
        )

    return jax.vmap(one)(
        blk_tabs, rest_tabs, orders, ordered_bounds, budgets, max_ranges, carry
    )


def topk_docs(state: TopKState) -> tuple[np.ndarray, np.ndarray]:
    """(docids, scores) for one query's state with empty slots stripped."""
    vals = np.asarray(state.vals)
    ids = np.asarray(state.ids)
    keep = ids >= 0
    return ids[keep], vals[keep]


def batched_topk_docs(state: TopKState) -> list[tuple[np.ndarray, np.ndarray]]:
    """Per-query (docids, scores) lists from a vmapped [N, k] state."""
    vals = np.asarray(state.vals)
    ids = np.asarray(state.ids)
    out = []
    for n in range(ids.shape[0]):
        keep = ids[n] >= 0
        out.append((ids[n][keep], vals[n][keep]))
    return out


def exit_reason(safe: bool, budget: bool) -> str:
    """Collapse the two exit flags into the host-facing reason string."""
    if safe:
        return "safe"
    if budget:
        return "budget"
    return "exhausted"


def exit_reasons(result: TraverseResult) -> list[str]:
    """Per-query exit reason strings from a batched ``TraverseResult``."""
    safe = np.asarray(result.exit_safe).reshape(-1)
    budget = np.asarray(result.exit_budget).reshape(-1)
    return [exit_reason(bool(s), bool(b)) for s, b in zip(safe, budget)]


# --------------------------------------------------------------------------
# Host-facing engine
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class QueryPlan:
    """Device-ready per-query traversal inputs."""

    q_terms: np.ndarray  # [L] int32, -1 padded
    blk_tab: jnp.ndarray  # [R, B] int32
    rest_tab: jnp.ndarray  # [R, B] int32
    order: jnp.ndarray  # [R] int32
    ordered_bounds: jnp.ndarray  # [R] int32
    order_host: np.ndarray  # same as order, on host
    bounds_host: np.ndarray  # ordered bounds, on host


def _next_pow2(n: int, lo: int = 32) -> int:
    v = lo
    while v < n:
        v *= 2
    return v


class Engine:
    """Cluster-skipping anytime query engine over a built index.

    ``ordering``: "boundsum" (the paper's proposal) or "docid" (range-
    oblivious baseline). ``bounds``: "range" (U[t,r], enables safe stop and
    tight block pruning) or "global" (listwise U_t only — the Default-index
    baseline; safe stop then uses the whole-collection bound).
    ``impact_dtype``: "int32" (default) or "int8" — native 8-bit postings
    impacts in HBM, widened only inside the scorer gather (DESIGN.md §8).
    ``docs_format``: "int32" (default) or "packed" — bit-packed per-block
    docid deltas in HBM, decoded inside the scorer (DESIGN.md §12); bitwise
    identical results by contract.
    """

    def __init__(
        self,
        index: ClusteredIndex,
        k: int = 10,
        ordering: str = "boundsum",
        bounds: str = "range",
        impl: str = "xla",
        impact_dtype: str = "int32",
        docs_format: str = "int32",
        obs=None,
    ):
        from repro.obs import NOOP  # local: obs is import-cycle-free by design

        self.obs = obs if obs is not None else NOOP
        self.index = index
        self.k = k
        self.ordering = ordering
        self.bounds = bounds
        self.impl = impl
        if impact_dtype not in IMPACT_DTYPES:
            raise ValueError(f"impact_dtype {impact_dtype!r} not in {IMPACT_DTYPES}")
        if impact_dtype == "int8" and index.quantizer.bits > 8:
            raise ValueError(
                f"impact_dtype='int8' needs quantizer.bits <= 8, "
                f"got {index.quantizer.bits}"
            )
        self.impact_dtype = impact_dtype
        if docs_format not in DOCS_FORMATS:
            raise ValueError(f"docs_format {docs_format!r} not in {DOCS_FORMATS}")
        self.docs_format = docs_format
        self.s_pad = int(
            (index.max_range_size + BLOCK - 1) // BLOCK * BLOCK
        ) or BLOCK
        if docs_format == "packed":
            packed = index.packed_postings()
            # The raw docid array stays on the host; the scorer never
            # gathers it, so a (1,)-placeholder keeps the pytree shape.
            docs_dev = jnp.zeros((1,), jnp.int32)
            pack_dev = dict(
                pack_words=jnp.asarray(packed.words, jnp.uint32),
                pack_dir=jnp.asarray(pack_dir_entries(packed), jnp.int32),
                pack_first=jnp.asarray(packed.blk_first, jnp.int32),
            )
        else:
            docs_dev = jnp.asarray(index.docs, jnp.int32)
            pack_dev = {}
        self.dix = DeviceIndex(
            docs=docs_dev,
            impacts=jnp.asarray(pack_impacts(index.impacts, impact_dtype)),
            blk_start=jnp.asarray(index.blk_start, jnp.int32),
            blk_len=jnp.asarray(index.blk_len, jnp.int32),
            blk_maximp=jnp.asarray(index.blk_maximp, jnp.int32),
            bounds_dense=jnp.asarray(index.bounds_dense, jnp.int32),
            range_starts=jnp.asarray(index.range_starts, jnp.int32),
            range_sizes=jnp.asarray(index.arrangement.range_sizes, jnp.int32),
            **pack_dev,
        )

    @classmethod
    def from_artifact(cls, path: str, **kwargs) -> "Engine":
        """Load a saved index artifact (``repro.index_io``) into an engine.

        ``impact_dtype`` and ``docs_format`` default to how the artifact
        was saved, so an int8/packed artifact serves int8/packed in HBM
        unless overridden.
        """
        from repro import index_io  # local: index_io sits above core

        index = index_io.load_index(path)
        manifest = index_io.read_manifest(path)
        kwargs.setdefault("impact_dtype", manifest["impact_dtype"])
        kwargs.setdefault("docs_format", manifest.get("docs_format", "int32"))
        return cls(index, **kwargs)

    # ------------------------------------------------------------- planning
    def plan(self, q_terms: np.ndarray) -> QueryPlan:
        q = np.asarray(q_terms, dtype=np.int32).reshape(-1)
        blk, rest_range = self.index.query_block_table(q)
        R, width = blk.shape
        pad = _next_pow2(width)
        if pad != width:
            blk = np.pad(blk, ((0, 0), (0, pad - width)), constant_values=-1)
            rest_range = np.pad(rest_range, ((0, 0), (0, pad - width)))

        bsums = self.index.bounds_dense[q[q >= 0]].sum(axis=0).astype(np.int64)
        if self.bounds == "global":
            # Listwise bounds only: rest = sum of other terms' GLOBAL bounds.
            gsum = int(self.index.term_bound[q[q >= 0]].sum())
            rest = np.where(
                blk >= 0,
                gsum - self.index.term_bound[self.index.blk_term[blk.clip(0)]],
                0,
            ).astype(np.int32)
            # Safe stop bound per range = whole-collection bound (loose).
            per_range_bound = np.full(R, gsum, dtype=np.int64)
        else:
            rest = rest_range.astype(np.int32)
            per_range_bound = bsums

        if self.ordering == "boundsum":
            order = np.argsort(-bsums, kind="stable").astype(np.int32)
        elif self.ordering == "docid":
            order = np.arange(R, dtype=np.int32)
        else:
            raise ValueError(f"unknown ordering {self.ordering!r}")
        # The device tables are int32; a BoundSum past 2^31 must saturate,
        # not wrap — a negative bound satisfies `bound < theta` immediately
        # and defeats safe termination. Saturation only errs conservative
        # (the traversal keeps going). The host copy keeps true int64 mass
        # for budget allocation (`query_shard_mass`).
        bounds_host = per_range_bound[order].astype(np.int64)
        ordered_bounds = np.clip(bounds_host, 0, 2**31 - 1).astype(np.int32)

        return QueryPlan(
            q_terms=q,
            blk_tab=jnp.asarray(blk, jnp.int32),
            rest_tab=jnp.asarray(rest, jnp.int32),
            order=jnp.asarray(order, jnp.int32),
            ordered_bounds=jnp.asarray(ordered_bounds, jnp.int32),
            order_host=order,
            bounds_host=bounds_host,
        )

    # ------------------------------------------------------- execution modes
    def init_state(self) -> TopKState:
        return init_state(self.k)

    def step(self, plan: QueryPlan, state: TopKState, i: int) -> TopKState:
        """Host-driven: score the i-th range of the plan's order."""
        r = int(plan.order_host[i])
        return score_range_step(
            self.dix,
            state,
            plan.blk_tab[r],
            plan.rest_tab[r],
            self.dix.range_starts[r],
            s_pad=self.s_pad,
            k=self.k,
            impl=self.impl,
            prune_blocks=True,
            docs_format=self.docs_format,
        )

    def traverse(
        self,
        plan: QueryPlan,
        budget_postings: int = 2**31 - 1,
        max_ranges: int = 2**31 - 1,
        safe_stop: bool = True,
        prune_blocks: bool = True,
    ) -> TraverseResult:
        """Device-driven whole-query traversal."""
        res = device_traverse(
            self.dix,
            plan.blk_tab,
            plan.rest_tab,
            plan.order,
            plan.ordered_bounds,
            s_pad=self.s_pad,
            k=self.k,
            budget_postings=budget_postings,
            max_ranges=max_ranges,
            safe_stop=safe_stop,
            prune_blocks=prune_blocks,
            impl=self.impl,
            docs_format=self.docs_format,
        )
        if self.obs.enabled:
            # Reading the exit flags forces a device sync; instrumentation
            # is allowed to cost time, never to change results.
            self.obs.count(
                "engine_queries",
                reason=exit_reason(bool(res.exit_safe), bool(res.exit_budget)),
            )
            self.obs.observe("engine_postings", int(res.state.postings))
        return res

    # ----------------------------------------------------------------- util
    def topk_docs(self, state: TopKState):
        """(docids, scores) with empty slots stripped, host-side.

        Accepts a single-query [k] state or a vmapped [N, k] state; the
        latter returns a per-query list of (docids, scores) pairs.
        """
        if np.asarray(state.ids).ndim == 2:
            return batched_topk_docs(state)
        return topk_docs(state)
