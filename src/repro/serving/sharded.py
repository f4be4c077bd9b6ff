"""Range-sharded multi-device anytime retrieval (DESIGN.md §4).

The cluster-skipping index is separable at range boundaries — blocks never
straddle them and every (term, range) bound is self-contained — so the
natural distribution unit is a contiguous band of ranges, not a random
document split. ``core.clustered_index.shard_device_index`` carves the
built index into postings-mass-balanced bands; this module executes the
same ``device_traverse`` per shard and merges the per-shard heaps into a
global top-k under the heap's total order (score desc, docid asc), which
makes the merged list *bitwise identical* to the single-device traversal
whenever every shard runs its ranges to completion.

Two execution paths produce identical numbers:

  * **vmap** — the shard axis is a vmapped batch dimension on one device
    (development / single-host fallback; also what the parity tests pin);
  * **shard_map** — one shard per mesh device, broker merge via
    ``all_gather`` inside the compiled step (the deployment path; tests
    force host devices with ``XLA_FLAGS=--xla_force_host_platform_
    device_count``).

Budgets are per (query, shard): a global postings budget is split
proportionally to each shard's postings mass (``split_postings_budget``),
so the anytime knob degrades all shards evenly instead of truncating
whichever shard happens to be slow. Fidelity accounting: a shard that
exits on budget reports the max BoundSum of its unprocessed ranges; the
merged result carries ``fidelity_bound`` = max over shards, and any
document missing from the merged list scores at most that bound.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core.clustered_index import (
    BLOCK,
    IndexShard,
    shard_cuts,
    shard_device_index,
)
from repro.core.range_daat import (
    DeviceIndex,
    Engine,
    QueryPlan,
    device_index,
    device_traverse,
    merge_topk,
)
from repro.distributed.sharding import retrieval_mesh
from repro.obs.profiler import jit_cache_size
from repro.serving.bucketing import (
    BucketSpec,
    batch_ladder,
    dummy_plan,
    iter_bucket_chunks,
)

__all__ = [
    "INT32_MAX",
    "ShardedEngine",
    "ShardedBatchEngine",
    "ShardedResult",
    "apply_down_mask",
    "index_specs",
    "place_index",
    "sharded_batched_traverse",
    "shard_exit_reason",
]

INT32_MAX = 2**31 - 1


# --------------------------------------------------------------------------
# Device dispatch — vmap path (single device) and shard_map path (mesh)
# --------------------------------------------------------------------------


def _merge_gathered(vals, gids, k):
    """[N, S, k] per-shard heaps -> ([N, k], [N, k]) merged global top-k."""
    n = vals.shape[0]
    return jax.vmap(lambda v, i: merge_topk(v, i, k))(
        vals.reshape(n, -1), gids.reshape(n, -1)
    )


@functools.partial(
    jax.jit,
    static_argnames=(
        "s_pad", "k", "safe_stop", "prune_blocks", "impl",
        "docs_format",
    ),
)
def sharded_batched_traverse(
    dix: DeviceIndex,  # stacked shard-major leaves [S, ...]
    doc_base: jnp.ndarray,  # [S] int32 global docid offset per shard
    blk: jnp.ndarray,  # [N, S, R, B] int32, -1 padded
    rest: jnp.ndarray,  # [N, S, R, B] int32
    order: jnp.ndarray,  # [N, S, R] int32 (shard-local range ids)
    bounds: jnp.ndarray,  # [N, S, R] int32
    budgets: jnp.ndarray,  # [N, S] int32 per-(query, shard) postings budget
    maxr: jnp.ndarray,  # [N, S] int32 per-(query, shard) range budget
    *,
    s_pad: int,
    k: int,
    safe_stop: bool = True,
    prune_blocks: bool = True,
    impl: str = "xla",
    docs_format: str = "int32",
):
    """(batch x shard) traversal on one device: vmap over both axes.

    Returns ``(vals [N,k], ids [N,k] GLOBAL docids, postings [N,S],
    blocks [N,S], ranges [N,S], exit_safe [N,S], exit_budget [N,S])``.
    """

    def one(dix1, bt, rt, o, ob, bud, mr):
        return device_traverse(
            dix1,
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

    over_shards = jax.vmap(one, in_axes=(0, 0, 0, 0, 0, 0, 0))
    res = jax.vmap(over_shards, in_axes=(None, 0, 0, 0, 0, 0, 0))(
        dix, blk, rest, order, bounds, budgets, maxr
    )
    # Leaves are [N, S, ...]; lift local docids to global, then broker-merge.
    vals = res.state.vals  # [N, S, k]
    gids = jnp.where(res.state.ids >= 0, res.state.ids + doc_base[None, :, None], -1)
    out_v, out_i = _merge_gathered(vals, gids, k)
    return (
        out_v,
        out_i,
        res.state.postings,
        res.state.blocks,
        res.ranges_processed,
        res.exit_safe,
        res.exit_budget,
    )


def _stack_padded(arrs: Sequence[np.ndarray]) -> np.ndarray:
    """Stack arrays along a new leading axis, zero-padding axis 0 to the
    longest (at least 1, so an empty leaf still has an element to gather)."""
    n = max(a.shape[0] for a in arrs) or 1
    out = np.zeros((len(arrs), n) + arrs[0].shape[1:], arrs[0].dtype)
    for s, a in enumerate(arrs):
        out[s, : a.shape[0]] = a
    return out


def index_specs(axis: str, docs_format: str) -> DeviceIndex:
    """PartitionSpecs of a stacked [S, ...] ``DeviceIndex``: the shard axis
    over mesh ``axis``, every other dim (and any other mesh axis, such as
    a replica axis) replicated."""
    row = P(axis, None)  # a vector per shard
    grid = P(axis, None, None)  # a matrix per shard
    pack = {}
    if docs_format == "packed":
        pack = dict(pack_words=row, pack_dir=row, pack_first=row)
    return DeviceIndex(
        blk_docs=None if docs_format == "packed" else grid,
        blk_imps=grid,
        blk_len=row,
        blk_maximp=row,
        bounds_dense=grid,
        range_starts=row,
        range_sizes=row,
        **pack,
    )


def place_index(dix: DeviceIndex, doc_base, mesh, axis: str, docs_format: str):
    """Put a stacked host index on ``mesh``, one shard per ``axis`` slot.

    Each device receives only its own shard's rows (replicated across any
    other mesh axis), once, so a mesh dispatch never reshards the index.
    """
    specs = index_specs(axis, docs_format)
    return (
        jax.device_put(dix, jax.tree.map(lambda p: NamedSharding(mesh, p), specs)),
        jax.device_put(doc_base, NamedSharding(mesh, P(axis))),
    )


def make_mesh_dispatch(
    mesh,
    axis: str,
    *,
    s_pad: int,
    k: int,
    safe_stop: bool,
    prune_blocks: bool,
    impl: str,
    data_axis: str | None = None,
    docs_format: str = "int32",
):
    """Compile the (batch x shard) step with one shard per mesh device.

    Same input/output contract as ``sharded_batched_traverse``; the shard
    axis is laid over the mesh via ``jax.shard_map`` (the index placed by
    ``place_index``) and the broker merge is an ``all_gather`` + lexsort top-k inside
    the compiled program, so one dispatch serves the whole batch on all
    shards (DESIGN.md §4).

    ``data_axis`` names a second mesh axis carrying query parallelism: the
    batch dimension of every plan table, budget, and output is sharded over
    it while the index arrays stay sharded over ``axis`` only (replicated
    across replicas) — the replicated-shard-group layout of DESIGN.md §9.
    The per-query math is untouched, so an N-replica dispatch is bitwise
    identical to running the same queries on one replica.
    """
    def body(dix, doc_base, blk, rest, order, bounds, budgets, maxr):
        dix1 = jax.tree.map(lambda a: a[0], dix)  # local shard, leading 1 off
        base = doc_base[0]

        def one(bt, rt, o, ob, bud, mr):
            return device_traverse(
                dix1,
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

        res = jax.vmap(one)(
            blk[:, 0], rest[:, 0], order[:, 0], bounds[:, 0],
            budgets[:, 0], maxr[:, 0],
        )
        gids = jnp.where(res.state.ids >= 0, res.state.ids + base, -1)
        g = lambda x: jnp.moveaxis(  # noqa: E731 — gather [S, ...] -> [N, S, ...]
            jax.lax.all_gather(x, axis), 0, 1
        )
        out_v, out_i = _merge_gathered(g(res.state.vals), g(gids), k)
        diag = g  # [N, S] per-shard counters/flags
        return (
            out_v,
            out_i,
            diag(res.state.postings),
            diag(res.state.blocks),
            diag(res.ranges_processed),
            diag(res.exit_safe),
            diag(res.exit_budget),
        )

    da = data_axis  # None -> batch replicated on every shard device (§4)
    fn = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(
            index_specs(axis, docs_format),
            P(axis),
            P(da, axis, None, None),
            P(da, axis, None, None),
            P(da, axis, None),
            P(da, axis, None),
            P(da, axis),
            P(da, axis),
        ),
        out_specs=tuple(P(da) for _ in range(7)),
        check_vma=False,
    )
    return jax.jit(fn)


# --------------------------------------------------------------------------
# Host-facing results
# --------------------------------------------------------------------------


def apply_down_mask(
    budgets: np.ndarray, maxr: np.ndarray, down_mask
) -> tuple[np.ndarray, np.ndarray]:
    """Zero dead shards' budget columns so they exit before any work.

    A down shard (health ledger, DESIGN.md §9) is given ``budget = 0`` and
    ``max_ranges = 0``: the device while_loop exits at i=0 having processed
    nothing, and every one of its ranges lands in the skipped-bounds
    accounting — which is exactly the "unprocessed BoundSum mass" the
    degraded fidelity bound must carry.
    """
    if down_mask is None:
        return budgets, maxr
    down = np.asarray(down_mask, bool)
    if down.shape != (budgets.shape[-1],):
        raise ValueError(
            f"down_mask shape {down.shape} != ({budgets.shape[-1]},)"
        )
    if not down.any():
        return budgets, maxr
    budgets = np.array(budgets, copy=True)
    maxr = np.array(maxr, copy=True)
    budgets[..., down] = 0
    maxr[..., down] = 0
    return budgets, maxr


def shard_exit_reason(safe: bool, budget: bool, rp: int, r_loc: int) -> str:
    """Per-shard exit reason with structural padding folded away.

    Shards are stacked to a common range count R_max; a shard with fewer
    ranges runs inert padded slots past ``r_loc``, whose zero bounds can
    trip the device safe/budget flags. Having processed all ``r_loc`` real
    ranges IS exhaustion, whatever flag fired at the padding.
    """
    if rp >= r_loc:
        return "exhausted"
    if safe:
        return "safe"
    if budget:
        return "budget"
    return "exhausted"


class ShardedResult(NamedTuple):
    """Merged global top-k plus per-shard diagnostics for one query."""

    doc_ids: np.ndarray  # [<=k] int32 GLOBAL docids, score-desc / docid-asc
    scores: np.ndarray  # [<=k] int32
    shard_postings: np.ndarray  # [S] int64
    shard_blocks: np.ndarray  # [S] int64
    shard_ranges: np.ndarray  # [S] int64 ranges processed (<= r_loc)
    shard_exit_reasons: tuple  # [S] of "safe" | "budget" | "exhausted" | "down"
    fidelity_bound: int  # max BoundSum over all unprocessed ranges (0 if none)
    exact: bool  # merged list provably equals the exhaustive top-k (see below)

    @property
    def postings(self) -> int:
        return int(self.shard_postings.sum())

    @property
    def blocks(self) -> int:
        return int(self.shard_blocks.sum())

    @property
    def exit_budget(self) -> bool:
        return "budget" in self.shard_exit_reasons


# --------------------------------------------------------------------------
# Engines
# --------------------------------------------------------------------------


class ShardedEngine:
    """Range-sharded executor over a single built ``ClusteredIndex``.

    Wraps a single-device ``Engine`` (whose ``plan`` stays the global
    planner) with ``n_shards`` shard-local device indexes. ``use_mesh``:
    None = auto (mesh when the runtime has >= n_shards devices), True =
    require a mesh, False = force the single-device vmap path.
    """

    def __init__(
        self,
        engine: Engine,
        n_shards: int,
        use_mesh: bool | None = None,
        mesh_axis: str = "shard",
        shards: list[IndexShard] | None = None,
        obs=None,
    ):
        from repro.obs import NOOP

        self.obs = obs if obs is not None else NOOP
        self.engine = engine
        self.k = engine.k
        self.s_pad = engine.s_pad
        self.impl = engine.impl
        self.impact_dtype = engine.impact_dtype
        self.docs_format = engine.docs_format
        if shards is None:
            shards = shard_device_index(engine.index, n_shards)
        elif len(shards) != n_shards:
            raise ValueError(
                f"preloaded shard count {len(shards)} != n_shards {n_shards}"
            )
        self.shards: list[IndexShard] = shards
        self.n_shards = len(self.shards)
        self.cuts = shard_cuts(self.shards)
        self.r_loc = np.asarray([sh.n_ranges for sh in self.shards], np.int64)
        self.r_max = int(self.r_loc.max())
        self.mass = np.asarray([sh.postings for sh in self.shards], np.int64)
        self.doc_base_host = np.asarray(
            [sh.doc_base for sh in self.shards], np.int64
        )

        # Each shard's device layout comes from ``device_index``, then every
        # leaf is stacked shard-major, zero-padded to the largest shard's
        # length. Padded blocks, words and ranges are never addressed: a
        # shard's block ids stay below its own NB. bounds_dense is a
        # planning-time structure (traversal reads bounds via the plan
        # tables), so the device mirror carries a placeholder; the real
        # shard-local bounds live on IndexShard.bounds_dense.
        per_shard = [
            device_index(sh, self.impact_dtype, self.docs_format)._replace(
                bounds_dense=np.zeros((1, 1), np.int32)
            )
            for sh in self.shards
        ]
        host = jax.tree.map(lambda *leaves: _stack_padded(leaves), *per_shard)
        doc_base = self.doc_base_host.astype(np.int32)

        if use_mesh is None:
            use_mesh = self.n_shards > 1 and jax.device_count() >= self.n_shards
        self.mesh = retrieval_mesh(self.n_shards, mesh_axis) if use_mesh else None
        if self.mesh is None:
            self.dix = jax.tree.map(jnp.asarray, host)
            self.doc_base = jnp.asarray(doc_base)
        else:
            self.dix, self.doc_base = place_index(
                host, doc_base, self.mesh, mesh_axis, self.docs_format
            )
        self._mesh_axis = mesh_axis
        self._mesh_fns: dict = {}

    @classmethod
    def from_artifact(
        cls,
        path: str,
        n_shards: int,
        shards_path: str | None = None,
        use_mesh: bool | None = None,
        mesh_axis: str = "shard",
        **engine_kwargs,
    ) -> "ShardedEngine":
        """Build a sharded engine from saved artifacts (DESIGN.md §8, §10).

        ``path`` is a ``clustered_index`` artifact or a delta-chain head
        (the global planner needs the full index, which a chain head
        materializes on load); ``shards_path`` optionally names a saved
        ``index_shards`` artifact to reuse instead of re-partitioning —
        rejected when its recorded ``source_fingerprint`` does not match
        the loaded (materialized) index, so a stale shard set cannot
        silently serve against a rebuilt *or extended* index: after an
        append, re-carve shards against the new chain head.
        """
        from repro import index_io  # local: index_io sits above serving

        engine = Engine.from_artifact(path, **engine_kwargs)
        shards = None
        if shards_path is not None:
            src = index_io.read_manifest(shards_path).get("source_fingerprint")
            if src is None:
                # An unverifiable shard set is as dangerous as a stale one:
                # mismatched docid spaces serve garbage with no error. Use
                # load_shards + ShardedEngine(shards=...) to bypass.
                raise index_io.ArtifactError(
                    f"shard artifact {shards_path} records no "
                    f"source_fingerprint; re-save with "
                    f"source_fingerprint=index.fingerprint()"
                )
            if src != engine.index.fingerprint():
                raise index_io.ArtifactError(
                    f"shard artifact {shards_path} was carved from index "
                    f"{src}, but {path} has fingerprint "
                    f"{engine.index.fingerprint()} — rebuild the shards"
                )
            shards = index_io.load_shards(shards_path)
        return cls(
            engine, n_shards, use_mesh=use_mesh, mesh_axis=mesh_axis,
            shards=shards,
        )

    # ------------------------------------------------------------- planning
    def plan(self, q_terms: np.ndarray) -> QueryPlan:
        return self.engine.plan(q_terms)

    def shard_plan(self, plan: QueryPlan, width: int | None = None):
        """Slice a global plan into stacked shard-local tables.

        Returns numpy ``(blk [S, R_max, B], rest, order, bounds)`` with
        block ids remapped through each shard's ``blk_map``, range rows in
        shard-local coordinates, and the global processing order restricted
        per shard (relative order preserved, so BoundSum-descending stays
        descending within every shard). Shards with fewer than R_max ranges
        point their padded order slots at an all--1 row — a no-op range.
        """
        g_blk = np.asarray(plan.blk_tab)
        g_rest = np.asarray(plan.rest_tab)
        w = g_blk.shape[1]
        B = width or w
        S, Rm = self.n_shards, self.r_max
        blk = np.full((S, Rm, B), -1, np.int32)
        rest = np.zeros((S, Rm, B), np.int32)
        order = np.zeros((S, Rm), np.int32)
        bounds = np.zeros((S, Rm), np.int32)
        for s, sh in enumerate(self.shards):
            rl = sh.n_ranges
            rows = g_blk[sh.range_lo : sh.range_hi]
            blk[s, :rl, :w] = np.where(rows >= 0, sh.blk_map[rows.clip(0)], -1)
            rest[s, :rl, :w] = g_rest[sh.range_lo : sh.range_hi]
            sel = (plan.order_host >= sh.range_lo) & (plan.order_host < sh.range_hi)
            order[s, :rl] = plan.order_host[sel] - sh.range_lo
            bounds[s, :rl] = np.clip(plan.bounds_host[sel], 0, INT32_MAX)
            if rl < Rm:
                order[s, rl:] = rl  # row rl is all -1: inert padding
        return blk, rest, order, bounds

    def query_shard_mass(self, plan: QueryPlan) -> np.ndarray:
        """[S] int64 per-shard BoundSum mass for this query's terms.

        Sum of the plan's per-range BoundSums over each shard's range band —
        the quantity shard-aware budget allocation splits postings budgets
        by (DESIGN.md §9): a shard whose ranges cannot score for this query
        carries zero mass and deserves none of its budget.
        """
        per_range = np.zeros(int(self.cuts[-1]), np.int64)
        per_range[plan.order_host] = plan.bounds_host
        return np.add.reduceat(per_range, self.cuts[:-1]).astype(np.int64)

    # -------------------------------------------------------------- budgets
    def split_postings_budget(self, budgets) -> np.ndarray:
        """[N] global postings budgets -> [N, S] proportional to shard mass.

        Ceil so shard slices never sum below the global budget; a *positive*
        budget is floored at one block per shard (mirror of
        ``SlaBudgeter.floor`` — a meaningful global budget must not starve a
        small shard below one block), while budget <= 0 stays 0 on every
        shard — same "no work, exit on budget" meaning as the unsharded
        engine. Unbounded stays unbounded.
        """
        b = np.asarray(budgets, np.int64).reshape(-1)
        shares = self.mass / max(int(self.mass.sum()), 1)
        out = np.ceil(b[:, None] * shares[None, :])
        out = np.where(b[:, None] > 0, np.maximum(out, BLOCK), 0)
        out = np.where(b[:, None] >= INT32_MAX, INT32_MAX, out)
        return np.clip(out, 0, INT32_MAX).astype(np.int32)

    def split_range_budget(self, maxr) -> np.ndarray:
        """[N] global range caps -> [N, S] proportional to shard range counts."""
        m = np.asarray(maxr, np.int64).reshape(-1)
        shares = self.r_loc / max(int(self.r_loc.sum()), 1)
        out = np.maximum(np.ceil(m[:, None] * shares[None, :]), 1)
        out = np.where(m[:, None] >= INT32_MAX, INT32_MAX, out)
        out = np.where(m[:, None] <= 0, 0, out)
        return np.clip(out, 0, INT32_MAX).astype(np.int32)

    # ------------------------------------------------------------- dispatch
    def dispatch(
        self, blk, rest, order, bounds, budgets, maxr,
        safe_stop: bool = True, prune_blocks: bool = True,
    ):
        """Run one (batch x shard) step; inputs are stacked numpy tables."""
        prof = self.obs.profiler if self.obs.enabled else None
        if prof is not None:
            t_plan0 = self.obs.clock()
        args = (
            self.dix,
            self.doc_base,
            jnp.asarray(blk),
            jnp.asarray(rest),
            jnp.asarray(order),
            jnp.asarray(bounds),
            jnp.asarray(budgets, jnp.int32),
            jnp.asarray(maxr, jnp.int32),
        )
        if self.mesh is not None:
            key = (safe_stop, prune_blocks)
            if key not in self._mesh_fns:
                self._mesh_fns[key] = make_mesh_dispatch(
                    self.mesh,
                    self._mesh_axis,
                    s_pad=self.s_pad,
                    k=self.k,
                    safe_stop=safe_stop,
                    prune_blocks=prune_blocks,
                    impl=self.impl,
                    docs_format=self.docs_format,
                )
            fn = self._mesh_fns[key]
            kwargs = {}
        else:
            fn = sharded_batched_traverse
            kwargs = dict(
                s_pad=self.s_pad,
                k=self.k,
                safe_stop=safe_stop,
                prune_blocks=prune_blocks,
                impl=self.impl,
                docs_format=self.docs_format,
            )
        if prof is None:
            return fn(*args, **kwargs)
        clk = self.obs.clock
        cache0 = jit_cache_size(fn)
        t_disp0 = clk()
        out = fn(*args, **kwargs)
        t_dev0 = clk()
        # Timing-only sync: results are fetched by the caller; untouched.
        jax.block_until_ready(out)  # analysis: allow[HOSTSYNC]
        t_dev1 = clk()
        prof.record_dispatch(
            "sharded",
            (int(np.asarray(blk).shape[0]), int(np.asarray(blk).shape[-1])),
            cache_before=cache0,
            cache_after=jit_cache_size(fn),
            plan_ms=(t_disp0 - t_plan0) * 1e3,
            dispatch_ms=(t_dev0 - t_disp0) * 1e3,
            device_ms=(t_dev1 - t_dev0) * 1e3,
        )
        prof.record_hbm_once("sharded", self.dix._asdict())
        return out

    # ------------------------------------------------------------ execution
    def traverse(
        self,
        plan: QueryPlan,
        budget_postings=INT32_MAX,
        max_ranges=INT32_MAX,
        safe_stop: bool = True,
        prune_blocks: bool = True,
        down_mask: np.ndarray | None = None,
    ) -> ShardedResult:
        """Single-query sharded traversal (a batch of one).

        Scalar budgets are split across shards proportionally; a length-S
        sequence assigns per-shard budgets directly. ``down_mask`` ([S]
        bool) marks dead shards: they are assigned zero work and the result
        degrades through the fidelity bound (DESIGN.md §9).
        """
        blk, rest, order, bounds = self.shard_plan(plan)
        bud = self._one_query_budget(budget_postings, self.split_postings_budget)
        mr = self._one_query_budget(max_ranges, self.split_range_budget)
        bud, mr = apply_down_mask(bud, mr, down_mask)
        out = self.dispatch(
            blk[None], rest[None], order[None], bounds[None], bud, mr,
            safe_stop=safe_stop, prune_blocks=prune_blocks,
        )
        return self._to_results(out, bounds[None], down_mask=down_mask)[0]

    def _one_query_budget(self, value, split_fn) -> np.ndarray:
        arr = np.asarray(value, np.int64)
        if arr.ndim == 0:
            return split_fn([int(arr)])
        if arr.shape != (self.n_shards,):
            raise ValueError(f"per-shard budget shape {arr.shape} != ({self.n_shards},)")
        return np.clip(arr, 0, INT32_MAX).astype(np.int32)[None]

    # --------------------------------------------------------------- unpack
    def _to_results(
        self, out, bounds: np.ndarray, down_mask: np.ndarray | None = None
    ) -> list[ShardedResult]:
        """Device outputs + host bounds tables [N, S, R_max] -> results."""
        vals, ids, post, blocks, ranges, safe, budget = (np.asarray(x) for x in out)
        down = (
            np.zeros(self.n_shards, bool)
            if down_mask is None
            else np.asarray(down_mask, bool)
        )
        results = []
        for n in range(vals.shape[0]):
            keep = ids[n] >= 0
            reasons = tuple(
                "down"
                if down[s]
                else shard_exit_reason(
                    bool(safe[n, s]), bool(budget[n, s]),
                    int(ranges[n, s]), int(self.r_loc[s]),
                )
                for s in range(self.n_shards)
            )
            # fb: fidelity loss attributable to the anytime knob or to a
            # dead shard (budget/down exits — the §4/§9 bound surfaced to
            # callers). resid: max BoundSum over ALL skipped ranges, safe
            # exits included, used for the exactness certificate below.
            # down_resid: the dead shards' share of resid — degraded
            # results are never certified exact while it is nonzero.
            fb = 0
            resid = 0
            down_resid = 0
            for s in range(self.n_shards):
                rp, rl = int(ranges[n, s]), int(self.r_loc[s])
                if rp < rl:
                    r_bound = int(bounds[n, s, rp:rl].max())
                    resid = max(resid, r_bound)
                    if reasons[s] in ("budget", "down"):
                        fb = max(fb, r_bound)
                    if reasons[s] == "down":
                        down_resid = max(down_resid, r_bound)
            # Exactness certificate, strict about tie-breaks: a doc in a
            # skipped range can score up to that range's BoundSum, and at
            # equal score a smaller docid displaces the k-th entry under the
            # heap's total order. The device's safe condition (bound < theta)
            # is judged per shard and per lane, so the certificate is
            # re-derived here against the merged list. Exact iff no skipped
            # range could hold a scoring doc (resid == 0; covers exhausted
            # shards and empty-for-query skipped ranges), or the list is
            # FULL and every skipped range is strictly below the k-th score.
            # With an under-filled list any unprocessed scoring doc belongs
            # in the top-k, so fullness is required. A down shard that could
            # have scored (down_resid > 0) always degrades to exact=False —
            # the deliberately conservative §9 contract, so operators can
            # alarm on inexact answers during an outage.
            n_found = int(keep.sum())
            exact = down_resid == 0 and (
                resid == 0
                or (n_found == self.k and resid < int(vals[n][keep][-1]))
            )
            results.append(
                ShardedResult(
                    doc_ids=ids[n][keep],
                    scores=vals[n][keep],
                    shard_postings=post[n].astype(np.int64),
                    shard_blocks=blocks[n].astype(np.int64),
                    shard_ranges=np.minimum(
                        ranges[n].astype(np.int64), self.r_loc
                    ),
                    shard_exit_reasons=reasons,
                    fidelity_bound=fb,
                    exact=exact,
                )
            )
            if self.obs.enabled:
                self.obs.count("sharded_queries")
                for s, r in enumerate(reasons):
                    self.obs.count("shard_exits", shard=s, reason=r)
                self.obs.count("sharded_exact", exact=exact)
                self.obs.observe("fidelity_bound", fb)
        return results


class ShardedBatchEngine:
    """Shape-bucketed (batch x shard) executor — the sharded ``BatchEngine``.

    Same static-shape discipline as ``BatchEngine``: plans snap to the
    ``BucketSpec`` width/batch ladder, so the XLA program cache stays
    bounded by #width_buckets x #batch_buckets per execution path. One
    dispatch covers every (lane, shard) pair.
    """

    def __init__(
        self, sengine: ShardedEngine, spec: BucketSpec | None = None, obs=None
    ):
        self.sengine = sengine
        self.engine = sengine.engine
        self.spec = spec or BucketSpec()
        # Default to the wrapped engine's handle so the whole sharded stack
        # shares one registry unless a caller deliberately splits them.
        self.obs = obs if obs is not None else sengine.obs
        self.compiled_shapes: set[tuple[int, int]] = set()
        self.batches_run = 0

    # ------------------------------------------------------------- planning
    def plan(self, q_terms: np.ndarray) -> QueryPlan:
        return self.engine.plan(q_terms)

    def plan_many(self, queries: Sequence[np.ndarray]) -> list[QueryPlan]:
        return [self.engine.plan(q) for q in queries]

    # ------------------------------------------------------------ execution
    def run_batch(
        self,
        plans: Sequence[QueryPlan],
        budget_postings=None,
        max_ranges=None,
        safe_stop: bool = True,
        prune_blocks: bool = True,
        down_mask: np.ndarray | None = None,
    ) -> list[ShardedResult]:
        """Traverse ``plans`` on all shards; results keep input order.

        Budgets may be None (unbounded), a scalar, an [n] per-query vector
        (split across shards proportionally), or an [n, S] matrix of
        explicit per-(query, shard) caps. ``down_mask`` ([S] bool) marks
        dead shards; their queries degrade through ``fidelity_bound`` and
        ``exact=False`` instead of failing (DESIGN.md §9).
        """
        n = len(plans)
        if n == 0:
            return []
        budgets = self._per_query_shard(
            budget_postings, n, self.sengine.split_postings_budget
        )
        maxr = self._per_query_shard(
            max_ranges, n, self.sengine.split_range_budget
        )
        budgets, maxr = apply_down_mask(budgets, maxr, down_mask)

        results: list[ShardedResult | None] = [None] * n
        for width, chunk in iter_bucket_chunks(plans, self.spec):
            self._run_chunk(
                [plans[i] for i in chunk], chunk, width, budgets, maxr,
                safe_stop, prune_blocks, results, down_mask,
            )
        return results  # type: ignore[return-value]

    def _per_query_shard(self, value, n: int, split_fn) -> np.ndarray:
        S = self.sengine.n_shards
        if value is None:
            return np.full((n, S), INT32_MAX, np.int32)
        arr = np.asarray(value, np.int64)
        if arr.ndim == 0:
            arr = np.full(n, int(arr), np.int64)
        if arr.shape == (n,):
            return split_fn(arr)
        if arr.shape == (n, S):
            return np.clip(arr, 0, INT32_MAX).astype(np.int32)
        raise ValueError(f"budget shape {arr.shape} not in {{({n},), ({n}, {S})}}")

    def _run_chunk(
        self, chunk_plans, chunk_idx, width, budgets, maxr,
        safe_stop, prune_blocks, results, down_mask=None,
    ) -> None:
        se = self.sengine
        batch = self.spec.batch_bucket(len(chunk_plans))
        S, Rm = se.n_shards, se.r_max
        blk = np.full((batch, S, Rm, width), -1, np.int32)
        rest = np.zeros((batch, S, Rm, width), np.int32)
        order = np.zeros((batch, S, Rm), np.int32)
        bounds = np.zeros((batch, S, Rm), np.int32)
        b = np.zeros((batch, S), np.int32)  # dummy lanes: zero budgets
        m = np.zeros((batch, S), np.int32)
        for lane, (qi, plan) in enumerate(zip(chunk_idx, chunk_plans)):
            blk[lane], rest[lane], order[lane], bounds[lane] = se.shard_plan(
                plan, width
            )
            b[lane] = budgets[qi]
            m[lane] = maxr[qi]

        out = se.dispatch(
            blk, rest, order, bounds, b, m,
            safe_stop=safe_stop, prune_blocks=prune_blocks,
        )
        self.compiled_shapes.add((batch, width))
        self.batches_run += 1
        unpacked = se._to_results(out, bounds, down_mask=down_mask)
        for lane, qi in enumerate(chunk_idx):
            results[qi] = unpacked[lane]

    # ---------------------------------------------------------------- misc
    def warmup(self, widths: Sequence[int] | None = None) -> None:
        """Pre-compile every (batch_bucket, width) program for given widths."""
        R = self.engine.index.n_ranges
        for w in widths or (self.spec.min_width,):
            dummy = dummy_plan(R, self.spec.width_bucket(w))
            for nb in batch_ladder(self.spec):
                self.run_batch([dummy] * nb)
