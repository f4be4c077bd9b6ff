"""Distributed anytime IR: ISN shards over the mesh + broker-merge collective.

The paper's deployment story (§1, §7): a collection too large for one node is
partitioned across index server nodes; every query runs on all partitions and
a broker merges per-node top-k lists. Here that maps onto one device mesh
(DESIGN.md §4-§5; serving/sharded.py is the range-partitioned sibling that
shards one index at range boundaries instead of building per-node
sub-indexes from a random document split):

  * the corpus is split into M = |model| shards, each a self-contained
    cluster-skipping sub-index (its own ranges, bounds, local docid space);
  * queries are sharded over (pod, data) — query parallelism/replication;
  * each model rank runs the *single-node* anytime traversal
    (core.range_daat.device_traverse, unchanged) over its shard with a
    per-shard work budget — the per-ISN SLA quantum;
  * the broker merge is one all_gather over ``model`` of [Q_loc, k]
    (vals, ids) + a top-k — the collective the roofline table shows for
    the anytime-ir cells.

Array convention: shard-major layouts [M, ...] sharded P("model", ...), so
the same code lowers for the production mesh and runs on 1 device (M=1).
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core import range_daat
from repro.core.clustered_index import build_index
from repro.core.range_daat import DeviceIndex
from repro.data.synth import Corpus
from repro.distributed.sharding import ShardCtx

__all__ = ["ShardedIndexArrays", "build_sharded_index", "sharded_anytime_query", "sharded_query_specs"]


@functools.partial(
    jax.tree_util.register_dataclass,
    data_fields=(
        "docs", "impacts", "blk_start", "blk_len", "blk_maximp",
        "range_starts", "doc_base",
    ),
    meta_fields=("s_pad", "k"),
)
@dataclasses.dataclass(frozen=True)
class ShardedIndexArrays:
    """Shard-major device arrays (all leading dim = M shards)."""

    docs: jnp.ndarray  # [M, NNZ] int32 local docids
    impacts: jnp.ndarray  # [M, NNZ] int32
    blk_start: jnp.ndarray  # [M, NB] int32
    blk_len: jnp.ndarray  # [M, NB] int32
    blk_maximp: jnp.ndarray  # [M, NB] int32
    range_starts: jnp.ndarray  # [M, R_loc] int32 (local docid space)
    doc_base: jnp.ndarray  # [M] int32 — global docid offset per shard
    s_pad: int
    k: int


def build_sharded_index(
    corpus: Corpus, n_shards: int, n_ranges_per_shard: int = 8, bits: int = 8,
    strategy: str = "clustered_bp", seed: int = 0,
):
    """Round-robin partition the corpus, build one sub-index per shard.

    Returns (arrays, engines) — engines are the per-shard host Engine
    objects (for planning query block tables per shard).
    """
    from repro.core.bm25 import invert
    from repro.core.quantize import fit_quantizer
    from repro.core.range_daat import Engine
    from repro.data.synth import Corpus as C

    # One GLOBAL quantizer so per-shard integer scores are merge-compatible.
    global_quant = fit_quantizer(invert(corpus).scores, bits=bits)

    # Split docs round-robin (the random-partition policy of §7.2).
    shard_of = np.arange(corpus.n_docs) % n_shards
    engines = []
    sub_indexes = []
    for m in range(n_shards):
        docs_m = np.nonzero(shard_of == m)[0]
        remap = {int(d): i for i, d in enumerate(docs_m)}
        ptr = [0]
        terms = []
        tfs = []
        for d in docs_m:
            t, f = corpus.doc_slice(int(d))
            terms.append(t)
            tfs.append(f)
            ptr.append(ptr[-1] + len(t))
        sub = C(
            n_docs=len(docs_m),
            n_terms=corpus.n_terms,
            doc_ptr=np.asarray(ptr, np.int64),
            doc_terms=np.concatenate(terms) if terms else np.empty(0, np.int32),
            doc_tfs=np.concatenate(tfs) if tfs else np.empty(0, np.int32),
            doc_topic=corpus.doc_topic[docs_m],
            n_topics=corpus.n_topics,
        )
        idx = build_index(
            sub, n_ranges=n_ranges_per_shard, strategy=strategy, bits=bits,
            seed=seed + m, quantizer=global_quant,
        )
        sub_indexes.append(idx)
        engines.append(Engine(idx, k=10))
        del remap

    # Pad per-shard arrays to common sizes and stack shard-major.
    def stack(get, pad_val=0, dtype=np.int32):
        arrs = [np.asarray(get(e.index), dtype=dtype) for e in engines]
        width = max(a.shape[0] for a in arrs)
        out = np.full((n_shards, width), pad_val, dtype=dtype)
        for m, a in enumerate(arrs):
            out[m, : a.shape[0]] = a
        return jnp.asarray(out)

    s_pad = max(e.s_pad for e in engines)
    doc_base = np.zeros(n_shards, np.int32)
    # global id = base + local id; bases spaced by padded shard size
    sizes = [e.index.n_docs for e in engines]
    doc_base[1:] = np.cumsum(sizes)[:-1]

    arrays = ShardedIndexArrays(
        docs=stack(lambda i: i.docs),
        impacts=stack(lambda i: i.impacts),
        blk_start=stack(lambda i: i.blk_start),
        blk_len=stack(lambda i: i.blk_len),
        blk_maximp=stack(lambda i: i.blk_maximp),
        range_starts=stack(lambda i: i.range_starts),
        doc_base=jnp.asarray(doc_base),
        s_pad=s_pad,
        k=10,
    )
    return arrays, engines


def plan_queries(engines, q_terms_batch: np.ndarray):
    """Host-side per-shard plans -> stacked [Q, M, R, B] device tables."""
    M = len(engines)
    Q = q_terms_batch.shape[0]
    plans = [[e.plan(q_terms_batch[qi]) for e in engines] for qi in range(Q)]
    R = max(p.order_host.shape[0] for row in plans for p in row)
    B = max(p.blk_tab.shape[1] for row in plans for p in row)

    blk = np.full((Q, M, R, B), -1, np.int32)
    rest = np.zeros((Q, M, R, B), np.int32)
    order = np.zeros((Q, M, R), np.int32)
    bounds = np.zeros((Q, M, R), np.int32)
    for qi in range(Q):
        for m in range(M):
            p = plans[qi][m]
            r, b = p.blk_tab.shape
            blk[qi, m, :r, :b] = np.asarray(p.blk_tab)
            rest[qi, m, :r, :b] = np.asarray(p.rest_tab)
            order[qi, m, :r] = np.asarray(p.order)
            bounds[qi, m, :r] = np.asarray(p.ordered_bounds)
    return (
        jnp.asarray(blk), jnp.asarray(rest), jnp.asarray(order), jnp.asarray(bounds)
    )


def sharded_query_specs(
    *, n_queries: int, n_shards: int, r_loc: int, b_width: int, nnz_loc: int,
    nb_loc: int, s_pad: int, k: int, impact_dtype=jnp.int32,
):
    """ShapeDtypeStructs for the dry-run (web-scale sharded index).

    ``impact_dtype=jnp.int8`` stores quantized impacts at their native
    8-bit width (the paper's own quantization level) — §Perf cell C.
    """
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    arrays = ShardedIndexArrays(
        docs=i32(n_shards, nnz_loc),
        impacts=jax.ShapeDtypeStruct((n_shards, nnz_loc), impact_dtype),
        blk_start=i32(n_shards, nb_loc),
        blk_len=i32(n_shards, nb_loc),
        blk_maximp=i32(n_shards, nb_loc),
        range_starts=i32(n_shards, r_loc),
        doc_base=i32(n_shards),
        s_pad=s_pad,
        k=k,
    )
    tables = (
        i32(n_queries, n_shards, r_loc, b_width),
        i32(n_queries, n_shards, r_loc, b_width),
        i32(n_queries, n_shards, r_loc),
        i32(n_queries, n_shards, r_loc),
    )
    return arrays, tables


def _local_traverse(arrays_local, blk, rest, order, bounds, *, s_pad, k,
                    budget, prune_blocks=True):
    """Run the single-node traversal on this shard for one query."""
    dix = DeviceIndex(
        docs=arrays_local[0], impacts=arrays_local[1],
        blk_start=arrays_local[2], blk_len=arrays_local[3],
        blk_maximp=arrays_local[4],
        bounds_dense=jnp.zeros((1, 1), jnp.int32),  # bounds arrive via tables
        range_starts=arrays_local[5],
        range_sizes=jnp.zeros_like(arrays_local[5]),
    )
    res = range_daat.device_traverse(
        dix, blk, rest, order, bounds,
        s_pad=s_pad, k=k, budget_postings=budget, safe_stop=True,
        prune_blocks=prune_blocks, impl="xla",
    )
    return res.state.vals, res.state.ids, res.ranges_processed


def make_sharded_query_fn(ctx: ShardCtx, *, s_pad: int, k: int, budget: int):
    """Build the jittable sharded query step (the anytime-ir serve step)."""
    m_axis = ctx.model_axis
    da = ctx.data_axes

    def body(arr_tuple, doc_base, blk, rest, order, bounds):
        # Shapes here are per-shard local: arr [1, ...]; tables [Q_loc, 1, R, B].
        arr_local = tuple(a[0] for a in arr_tuple)
        base = doc_base[0]
        Q = blk.shape[0]

        def one(args):
            b, r_, o, bd = args
            vals, ids, nr = _local_traverse(
                arr_local, b[0], r_[0], o[0], bd[0],
                s_pad=s_pad, k=k, budget=budget,
            )
            gids = jnp.where(ids >= 0, ids + base, -1)
            return vals, gids, nr

        vals, gids, nr = jax.lax.map(one, (blk, rest, order, bounds))
        # Broker merge: gather per-shard top-k and take the global top-k.
        all_vals = jax.lax.all_gather(vals, m_axis)  # [M, Q_loc, k]
        all_ids = jax.lax.all_gather(gids, m_axis)
        mv = jnp.moveaxis(all_vals, 0, 1).reshape(Q, -1)
        mi = jnp.moveaxis(all_ids, 0, 1).reshape(Q, -1)
        sel = jnp.argsort(-mv, axis=1)[:, :k]
        out_v = jnp.take_along_axis(mv, sel, axis=1)
        out_i = jnp.take_along_axis(mi, sel, axis=1)
        return out_v, out_i, jax.lax.psum(jnp.sum(nr), m_axis)

    arr_specs = tuple([P(m_axis, None)] * 6)
    fn = jax.shard_map(
        body,
        mesh=ctx.mesh,
        in_specs=(
            arr_specs,
            P(m_axis),
            P(da, m_axis, None, None),
            P(da, m_axis, None, None),
            P(da, m_axis, None),
            P(da, m_axis, None),
        ),
        out_specs=(P(da, None), P(da, None), P()),
        check_vma=False,
    )

    def step(arrays: ShardedIndexArrays, tables):
        blk, rest, order, bounds = tables
        return fn(
            (arrays.docs, arrays.impacts, arrays.blk_start, arrays.blk_len,
             arrays.blk_maximp, arrays.range_starts),
            arrays.doc_base, blk, rest, order, bounds,
        )

    return step


def sharded_anytime_query(arrays, tables, ctx, budget: int = 2**31 - 1):
    step = make_sharded_query_fn(
        ctx, s_pad=arrays.s_pad, k=arrays.k, budget=budget
    )
    return step(arrays, tables)
