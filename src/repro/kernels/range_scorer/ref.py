"""Pure-jnp oracle for the range scorer.

Scores one document range: gathers the postings of the query's surviving
blocks and scatter-adds quantized impacts into a range-local accumulator.
Postings are stored block-major (``core.clustered_index.block_rows``): one
``[BLOCK]`` row per block, so a block's postings are one row gather by
block id, the form the TPU reads at HBM speed.
This is the semantic reference the Pallas kernel must match exactly
(integer impacts; float32 accumulation is exact below 2^24). The posting
gathers run under the named scope ``gather`` and the scatter-add under
``accumulate``, so a profiler trace times the two apart.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.clustered_index import PACK_DIR_BITS, PACK_WIDTHS

BLOCK = 128  # postings per block; matches core.clustered_index.BLOCK

# Zero-point for native int8 impact storage (DESIGN.md §8): quantized
# impacts live in [1, 2^b - 1] ⊆ [1, 255] for b <= 8, which overflows
# signed int8, so the stored code is ``impact - IMPACT_BIAS`` ∈ [-127, 127]
# and the gather widens with ``+ IMPACT_BIAS`` back into exact int32.
IMPACT_BIAS = 128

__all__ = [
    "BLOCK",
    "IMPACT_BIAS",
    "gather_block_impacts",
    "gather_block_postings",
    "gather_block_rows",
    "gather_block_postings_packed",
    "score_blocks_packed_ref",
    "score_blocks_ref",
    "unpack_dir",
]


def _lane_valid(
    ids: jnp.ndarray, lens: jnp.ndarray, keep: jnp.ndarray
) -> jnp.ndarray:
    """[B, BLOCK] lane validity shared by both docid formats."""
    lane = jnp.arange(BLOCK, dtype=jnp.int32)
    return (lane[None, :] < lens[:, None]) & keep[:, None] & (ids >= 0)[:, None]


def gather_block_rows(rows: jnp.ndarray, ids: jnp.ndarray) -> jnp.ndarray:
    """[B, BLOCK] rows of a block-major array by block id (-1 pad reads row 0)."""
    return rows[jnp.clip(ids, 0).astype(jnp.int32)]


def gather_block_impacts(
    blk_imps: jnp.ndarray,  # [NB, BLOCK] int32 or biased int8 impacts
    ids: jnp.ndarray,  # [B] block ids (-1 pad ok)
) -> jnp.ndarray:
    """Gather [B, BLOCK] widened impact values by block id.

    Impacts are block rows in every docs format — packed blocks replace
    only the docid stream.
    """
    v = gather_block_rows(blk_imps, ids)
    if blk_imps.dtype == jnp.int8:
        # Native int8 impact storage: codes are biased by IMPACT_BIAS so the
        # widen is the only place the true impact is reconstructed — impacts
        # stay 1 B per block lane in HBM (DESIGN.md §8).
        v = v.astype(jnp.int32) + IMPACT_BIAS
    return v


def gather_block_postings(
    blk_docs: jnp.ndarray,  # [NB, BLOCK] int32 docids (new ids)
    blk_imps: jnp.ndarray,  # [NB, BLOCK] int32 or biased int8 impacts
    ids: jnp.ndarray,  # [B] int32/int64 block ids (-1 pad ok)
    lens: jnp.ndarray,  # [B] int32 block lengths
    keep: jnp.ndarray,  # [B] bool survives pruning
    range_start: jnp.ndarray,  # scalar int32 first new-docid of the range
):
    """Gather block postings into dense [B*BLOCK] (local_id, value) pairs.

    Invalid lanes get local_id = -1 and value = 0 so any downstream
    accumulator drops them.
    """
    B = ids.shape[0]
    valid = _lane_valid(ids, lens, keep)
    d = gather_block_rows(blk_docs, ids)
    v = gather_block_impacts(blk_imps, ids)
    local = jnp.where(valid, d - range_start, -1).astype(jnp.int32)
    vals = jnp.where(valid, v, 0).astype(jnp.int32)
    return local.reshape(B * BLOCK), vals.reshape(B * BLOCK)


def unpack_dir(pack_dir: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Split merged directory entries into (word_start, width) columns.

    Entries come from ``core.clustered_index.pack_dir_entries``: the word
    offset in the low ``PACK_DIR_BITS`` bits, the ``PACK_WIDTHS`` code
    above it. Entries are non-negative, so the arithmetic shift is exact.
    """
    entry = pack_dir.astype(jnp.int32)
    ws = entry & ((1 << PACK_DIR_BITS) - 1)
    w = jnp.asarray(PACK_WIDTHS, jnp.int32)[entry >> PACK_DIR_BITS]
    return ws, w


def gather_block_postings_packed(
    pack_words: jnp.ndarray,  # [n_words] uint32 packed delta stream
    blk_imps: jnp.ndarray,  # [NB, BLOCK] int32 or biased int8 impacts
    ids: jnp.ndarray,  # [B] block ids (-1 pad ok)
    lens: jnp.ndarray,  # [B] int32 block lengths
    pack_dir: jnp.ndarray,  # [B] int32 merged (word_start | width code)
    pack_firsts: jnp.ndarray,  # [B] int32 absolute first docid per block
    keep: jnp.ndarray,  # [B] bool survives pruning
    range_start: jnp.ndarray,  # scalar int32 first new-docid of the range
):
    """Packed-format twin of :func:`gather_block_postings` — the oracle.

    Lane ``j`` of a width-``w`` block reads bits ``[j*w, (j+1)*w)`` of its
    word run (``delta_0 = 0`` is stored, so the layout is uniform), masks
    out the delta, and an inclusive prefix sum from the out-of-band first
    docid rebuilds absolute ids. Deltas of lanes past ``lens`` are zeroed
    *before* the cumsum so tail garbage can never leak into valid lanes.
    Returns the identical (local_id, value) contract, bitwise.
    """
    B = ids.shape[0]
    lane = jnp.arange(BLOCK, dtype=jnp.int32)
    valid = _lane_valid(ids, lens, keep)
    pack_starts, widths = unpack_dir(pack_dir)
    w = widths[:, None]  # [B, 1]
    bit = lane[None, :] * w  # [B, BLOCK]
    widx = pack_starts[:, None] + bit // 32
    n_words = pack_words.shape[0]
    word = pack_words[jnp.clip(widx, 0, max(n_words - 1, 0))]
    # Width mask in uint32 without ever shifting by >= 32 (w == 32 takes the
    # all-ones branch; the other branch still evaluates, so clamp to 31).
    wu = jnp.minimum(w, 31).astype(jnp.uint32)
    mask = jnp.where(
        w >= 32,
        jnp.uint32(0xFFFFFFFF),
        (jnp.uint32(1) << wu) - jnp.uint32(1),
    )
    shift = (bit % 32).astype(jnp.uint32)  # < 32 for every legal width
    delta = (word >> shift) & mask
    in_len = lane[None, :] < lens[:, None]
    delta = jnp.where(in_len, delta, jnp.uint32(0)).astype(jnp.int32)
    d = pack_firsts.astype(jnp.int32)[:, None] + jnp.cumsum(delta, axis=1)
    v = gather_block_impacts(blk_imps, ids)
    local = jnp.where(valid, d - range_start, -1).astype(jnp.int32)
    vals = jnp.where(valid, v, 0).astype(jnp.int32)
    return local.reshape(B * BLOCK), vals.reshape(B * BLOCK)


def score_blocks_ref(
    blk_docs: jnp.ndarray,
    blk_imps: jnp.ndarray,
    ids: jnp.ndarray,
    lens: jnp.ndarray,
    keep: jnp.ndarray,
    range_start: jnp.ndarray,
    s_pad: int,
) -> jnp.ndarray:
    """Accumulate surviving blocks into an int32 accumulator of size s_pad."""
    with jax.named_scope("gather"):
        local, vals = gather_block_postings(
            blk_docs, blk_imps, ids, lens, keep, range_start
        )
    with jax.named_scope("accumulate"):
        # local == -1 -> clamp to s_pad and drop via mode="drop".
        tgt = jnp.where(local < 0, s_pad, local)
        acc = jnp.zeros((s_pad,), jnp.int32)
        return acc.at[tgt].add(vals, mode="drop")


def score_blocks_packed_ref(
    pack_words: jnp.ndarray,
    blk_imps: jnp.ndarray,
    ids: jnp.ndarray,
    lens: jnp.ndarray,
    pack_dir: jnp.ndarray,
    pack_firsts: jnp.ndarray,
    keep: jnp.ndarray,
    range_start: jnp.ndarray,
    s_pad: int,
) -> jnp.ndarray:
    """Packed-format twin of :func:`score_blocks_ref` (same accumulator)."""
    with jax.named_scope("gather"):
        local, vals = gather_block_postings_packed(
            pack_words, blk_imps, ids, lens,
            pack_dir, pack_firsts, keep, range_start,
        )
    with jax.named_scope("accumulate"):
        tgt = jnp.where(local < 0, s_pad, local)
        acc = jnp.zeros((s_pad,), jnp.int32)
        return acc.at[tgt].add(vals, mode="drop")
