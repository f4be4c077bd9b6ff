"""Embedding lookups for recsys: EmbeddingBag and row-sharded tables.

JAX has no native nn.EmbeddingBag or CSR sparse — per kernel_taxonomy §RecSys
the bag is built from ``jnp.take`` + masked reduction (ragged bags via
``jax.ops.segment_sum``). The fused weighted-reduce has a Pallas kernel in
kernels/embedding_bag; the gather itself stays in XLA (TPU-native path —
SparseCore/dynamic-gather on real hardware).

Sharded tables: rows are mod-placed over the mesh ``model`` axis; each rank
gathers its local hits and the combine is a psum — the DLRM all-to-all
analogue (DESIGN.md §5). The reduce-scatter variant is a §Perf candidate.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

__all__ = ["embedding_bag", "embedding_bag_ragged", "sharded_field_lookup"]


def embedding_bag(
    table: jnp.ndarray,  # [V, D]
    ids: jnp.ndarray,  # [B, L] int32, -1 = padding
    weights: jnp.ndarray | None = None,  # [B, L]
    combine: str = "sum",
    impl: str = "xla",
) -> jnp.ndarray:
    """Fixed-width multi-hot bag: out[b] = reduce_l table[ids[b, l]]."""
    mask = (ids >= 0).astype(table.dtype)
    w = mask if weights is None else weights.astype(table.dtype) * mask
    if impl == "pallas":
        from repro.kernels.embedding_bag.ops import bag_reduce

        rows = table[jnp.clip(ids, 0)]  # [B, L, D]
        out = bag_reduce(rows, w)
    else:
        rows = table[jnp.clip(ids, 0)]
        out = jnp.einsum("bld,bl->bd", rows, w)
    if combine == "mean":
        out = out / jnp.maximum(w.sum(-1, keepdims=True), 1.0)
    return out


def embedding_bag_ragged(
    table: jnp.ndarray,
    flat_ids: jnp.ndarray,  # [N] int32
    segment_ids: jnp.ndarray,  # [N] int32 bag index per id
    n_bags: int,
    combine: str = "sum",
) -> jnp.ndarray:
    """Ragged bags via segment_sum (true EmbeddingBag semantics)."""
    rows = table[jnp.clip(flat_ids, 0)]
    rows = jnp.where((flat_ids >= 0)[:, None], rows, 0)
    out = jax.ops.segment_sum(rows, segment_ids, num_segments=n_bags)
    if combine == "mean":
        cnt = jax.ops.segment_sum(
            (flat_ids >= 0).astype(table.dtype), segment_ids, num_segments=n_bags
        )
        out = out / jnp.maximum(cnt, 1.0)[:, None]
    return out


def sharded_field_lookup(table, ids, shard_ctx):
    """Row-sharded (mod-placement) embedding lookup.

    table [V, D] sharded P(model, None) (contiguous row blocks); ids [...]
    global row ids. Each model rank resolves the ids that fall inside its
    block and the psum over ``model`` assembles full rows — the collective
    the roofline table attributes to recsys lookups.
    """
    if shard_ctx is None:
        return table[jnp.clip(ids, 0)] * (ids >= 0)[..., None].astype(table.dtype)

    m_axis = shard_ctx.model_axis

    def body(tbl_local, ids_local):
        m = jax.lax.axis_index(m_axis)
        rows_per = tbl_local.shape[0]  # V / n_model (contiguous blocks)
        owner = jnp.where(ids_local >= 0, ids_local // rows_per, -1)
        local_row = jnp.clip(ids_local - m * rows_per, 0, rows_per - 1)
        rows = tbl_local[local_row]
        rows = jnp.where((owner == m)[..., None], rows, 0)
        return jax.lax.psum(rows, m_axis)

    B = ids.shape
    flat = ids.reshape(-1)
    # Shard the id stream over data only when it divides; tiny id sets
    # (e.g. batch-1 retrieval user features) are replicated instead.
    n_data = 1
    for a in shard_ctx.data_axes:
        n_data *= shard_ctx.mesh.shape[a]
    ids_spec = P(shard_ctx.data_axes) if flat.shape[0] % n_data == 0 else P()
    fn = jax.shard_map(
        body,
        mesh=shard_ctx.mesh,
        in_specs=(P(m_axis, None), ids_spec),
        out_specs=P(*ids_spec, None),
        check_vma=False,
    )
    out = fn(table, flat)
    return out.reshape(*B, table.shape[1])
