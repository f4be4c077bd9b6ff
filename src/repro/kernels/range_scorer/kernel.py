"""Pallas TPU kernel: range-local impact accumulation via one-hot MXU matmul.

TPU adaptation of the paper's inner scoring loop (DESIGN.md §2). The CPU
algorithm scatter-adds each posting's impact into an accumulator; TPUs have
no efficient per-element scatter, but the accumulator *tile* for one
topically-coherent range fits in VMEM — that locality is exactly what the
paper's reordering buys (its §5.2 "fewer cache misses" observation, moved up
one level of the memory hierarchy: HBM→VMEM instead of DRAM→L2).

The scatter is recast as a matmul: for an accumulator tile ``acc[s0:s0+S_TILE]``
and a tile of P gathered postings ``(local_id, val)``,

    acc[s] += sum_p val[p] * [local_id[p] == s]

i.e. ``vals[1, P] @ onehot[S_TILE, P]^T`` — an MXU contraction with both
dims multiples of 128. Grid = (n_s_tiles, n_p_tiles), postings innermost so
each accumulator tile is revisited while resident in VMEM; the one-hot is
built on the fly from an iota compare (never materialized in HBM). Every
block is 2-D with a leading dim of 1 (the whole array's) and a lane dim
that is a multiple of 128, so the blocks satisfy the TPU (8, 128) rule
alone and under ``vmap``.

Whether a kernel is compiled or interpreted is decided by the platform in
one place, ``pallas_interpret``: interpreted on CPU (the test suite),
compiled on TPU (``chip_smoke.py`` checks both kernels bitwise against
``ref`` there). Results are exact: integer impacts summed in fp32 at
``Precision.HIGHEST`` stay below 2^24.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.clustered_index import PACK_DIR_BITS

DEFAULT_S_TILE = 512
DEFAULT_P_TILE = 1024

BLOCK = 128  # postings per block; matches core.clustered_index.BLOCK
# A width-32 block needs BLOCK*32/32 = BLOCK words; every narrower block
# needs fewer, so a block's run never spans more than two 128-word rows.
WORDS_PER_BLOCK = BLOCK
DEFAULT_B_TILE = 8  # blocks decoded per grid step: one (8, 128) int32 tile
# The word stream is laid out in HBM as [rows, 128] int32, tiled (8, 128):
# DMA windows start on a tile boundary, _TILE_WORDS words apart.
_TILE_ROWS = 8
_TILE_WORDS = _TILE_ROWS * BLOCK
_TILE_WORDS_LOG2 = 10
_WS_MASK = (1 << PACK_DIR_BITS) - 1
# Rows of the kernel's [5, B] SMEM directory table. 2-D with the whole
# array as its block, so the table also passes the (8, 128) block rule
# under vmap; the range start rides along as a fifth row.
_DIR_ENTRY, _DIR_FIRST, _DIR_LEN, _DIR_VALID, _DIR_RANGE_START = range(5)

__all__ = ["pallas_interpret", "scatter_accumulate_pallas", "unpack_locals_pallas"]


def pallas_interpret() -> bool:
    """The one interpret-or-compile decision for this package's kernels.

    CPU has no Pallas compiler, so kernels run in interpret mode there; on
    TPU they always compile. Any other platform is refused rather than
    silently interpreted. Read at trace time, so a process decides once
    per compiled program.
    """
    platform = jax.default_backend()
    if platform == "cpu":
        return True
    if platform == "tpu":
        return False
    raise RuntimeError(
        f"range_scorer Pallas kernels support cpu (interpreted) and tpu "
        f"(compiled), not {platform!r}; use impl='xla'"
    )


def _scatter_kernel(ids_ref, vals_ref, acc_ref, *, s_tile: int, p_tile: int):
    s_idx = pl.program_id(0)
    p_idx = pl.program_id(1)

    @pl.when(p_idx == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    ids = ids_ref[...]  # [1, p_tile] int32 (-1 or OOB = dropped)
    vals = vals_ref[...].astype(jnp.float32)  # [1, p_tile]
    # Transposed one-hot [s_tile, p_tile]: row s is set where the posting's
    # local id is s. Ids outside the tile (padding -1 included) match no
    # row and contribute nothing.
    rows = jax.lax.broadcasted_iota(jnp.int32, (s_tile, p_tile), 0) + s_idx * s_tile
    onehot = (rows == ids).astype(jnp.float32)
    acc_ref[...] += jax.lax.dot_general(
        vals,
        onehot,
        (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )  # [1, s_tile]


@functools.partial(jax.jit, static_argnames=("s_pad", "s_tile", "p_tile"))
def scatter_accumulate_pallas(
    ids: jnp.ndarray,  # [P] int32 local docids, -1/OOB dropped
    vals: jnp.ndarray,  # [P] int32 impacts
    *,
    s_pad: int,
    s_tile: int = DEFAULT_S_TILE,
    p_tile: int = DEFAULT_P_TILE,
) -> jnp.ndarray:
    """acc[s] = sum of vals at ids==s, for s in [0, s_pad). Returns int32."""
    P = ids.shape[0]
    # Lane-dim tiles are multiples of 128 (the TPU tiling rule).
    s_tile = min(s_tile, -(-s_pad // 128) * 128)
    p_tile = min(p_tile, -(-P // 128) * 128)
    # Pad to tile multiples (padding ids = -1 → dropped).
    sp = (s_pad + s_tile - 1) // s_tile * s_tile
    pp = (P + p_tile - 1) // p_tile * p_tile
    if pp != P:
        ids = jnp.concatenate([ids, jnp.full((pp - P,), -1, jnp.int32)])
        vals = jnp.concatenate([vals, jnp.zeros((pp - P,), vals.dtype)])

    grid = (sp // s_tile, pp // p_tile)
    acc = pl.pallas_call(
        functools.partial(_scatter_kernel, s_tile=s_tile, p_tile=p_tile),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, p_tile), lambda s, p: (0, p)),
            pl.BlockSpec((1, p_tile), lambda s, p: (0, p)),
        ],
        out_specs=pl.BlockSpec((1, s_tile), lambda s, p: (0, s)),
        out_shape=jax.ShapeDtypeStruct((1, sp), jnp.float32),
        interpret=pallas_interpret(),
    )(ids.reshape(1, pp), vals.reshape(1, pp))
    return acc[0, :s_pad].astype(jnp.int32)


def _prefix_sum_lanes(x: jnp.ndarray, lane: jnp.ndarray) -> jnp.ndarray:
    """Inclusive prefix sum along the 128 lanes (log-step, exact int32)."""
    s = 1
    while s < BLOCK:
        x = x + jnp.where(lane >= s, pltpu.roll(x, s, 1), 0)
        s *= 2
    return x


def _unpack_kernel(dir_ref, words_hbm, out_ref, win_ref, sem, *, b_tile: int):
    """Decode one (b_tile, 128) tile of blocks: packed deltas -> local ids.

    The per-block directory scalars live in SMEM, one row of ``dir_ref``
    per column (_DIR_* below); the word stream stays in
    HBM as rows of 128 words. A block's WORDS_PER_BLOCK-word run starting
    at word ``ws`` lies inside the two (8, 128) tiles from row
    ``(ws // 1024) * 8``, so one tile-aligned DMA per block brings that
    16-row window into VMEM, and the run is rows ``q`` and ``q + 1`` of it
    (``q = (ws % 1024) // 128``). All rows then decode at once: for width
    w, lane j's delta sits at bit j*w of the run, i.e. in run word
    (j*w) // 32, a per-lane gather, shifted right by (j*w) % 32 and masked
    to w bits. Deltas past the block length are zeroed before the 128-lane
    inclusive prefix sum from the block's first docid, exactly like the
    oracle.
    """
    base = pl.program_id(0) * b_tile
    copies = []
    for r in range(b_tile):
        row0 = pl.multiple_of(
            ((dir_ref[_DIR_ENTRY, base + r] & _WS_MASK) >> _TILE_WORDS_LOG2) * _TILE_ROWS,
            _TILE_ROWS,
        )
        cp = pltpu.make_async_copy(
            words_hbm.at[pl.ds(row0, 2 * _TILE_ROWS)], win_ref.at[r], sem.at[r]
        )
        cp.start()
        copies.append(cp)

    shape = (b_tile, BLOCK)
    row = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, shape, 1)

    def per_row(col, f=lambda v: v):
        """Broadcast each block's SMEM scalar across its row."""
        out = jnp.zeros(shape, jnp.int32)
        for r in range(b_tile):
            out = jnp.where(row == r, f(dir_ref[col, base + r]), out)
        return out

    def width(entry):
        # PACK_WIDTHS = (0, 4, 8, 16, 32): code c > 0 maps to 2 << c, and a
        # table lookup would capture a constant array (illegal in Pallas).
        code = entry >> PACK_DIR_BITS
        return jnp.where(code == 0, 0, 2 << code)

    w = per_row(_DIR_ENTRY, width)
    off = per_row(_DIR_ENTRY, lambda e: e & (_TILE_WORDS - 1))  # run start in window
    ln = per_row(_DIR_LEN)
    fd = per_row(_DIR_FIRST)
    vb = per_row(_DIR_VALID)

    for cp in copies:
        cp.wait()
    run_a = jnp.zeros(shape, jnp.int32)
    run_b = jnp.zeros(shape, jnp.int32)
    for r in range(b_tile):
        q = (dir_ref[_DIR_ENTRY, base + r] & (_TILE_WORDS - 1)) >> 7
        run_a = jnp.where(row == r, win_ref[r, pl.ds(q, 1), :], run_a)
        run_b = jnp.where(row == r, win_ref[r, pl.ds(q + 1, 1), :], run_b)

    bit = lane * w
    idx = (off & (BLOCK - 1)) + (bit >> 5)  # 0 .. 254 within rows q, q + 1
    src = idx & (BLOCK - 1)
    word = jnp.where(
        idx < BLOCK,
        jnp.take_along_axis(run_a, src, axis=1, mode="promise_in_bounds"),
        jnp.take_along_axis(run_b, src, axis=1, mode="promise_in_bounds"),
    )
    # Width mask without ever shifting by 32 (w == 32 takes the all-ones
    # branch; the other branch still evaluates, so clamp to 31).
    mask = jnp.where(w >= 32, -1, (1 << jnp.minimum(w, 31)) - 1)
    delta = jax.lax.shift_right_logical(word, bit & 31) & mask
    in_len = lane < ln
    delta = jnp.where(in_len, delta, 0)
    docs = fd + _prefix_sum_lanes(delta, lane)
    out_ref[...] = jnp.where(in_len & (vb != 0), docs - dir_ref[_DIR_RANGE_START, 0], -1)


@functools.partial(jax.jit, static_argnames=("b_tile",))
def unpack_locals_pallas(
    pack_words: jnp.ndarray,  # [n_words] uint32 packed delta stream
    starts: jnp.ndarray,  # [B] block start offsets (-1 pad ok; validity only)
    lens: jnp.ndarray,  # [B] int32 block lengths
    pack_dir: jnp.ndarray,  # [B] int32 merged (word_start | width code)
    pack_firsts: jnp.ndarray,  # [B] absolute first docid per block
    keep: jnp.ndarray,  # [B] bool survives pruning
    range_start: jnp.ndarray,  # scalar int32
    *,
    b_tile: int = DEFAULT_B_TILE,
) -> jnp.ndarray:
    """Pallas decode of packed blocks to [B*BLOCK] local ids (-1 invalid).

    Matches ``ref.gather_block_postings_packed``'s id lanes bitwise. The
    word stream stays in HBM (``pl.ANY``), padded with zero words to whole
    (8, 128) tiles plus a spare tile so each block's two-tile DMA window
    never overruns. The directory columns go to SMEM as one [5, B] table:
    a whole-array SMEM block, not scalar prefetch, because ``vmap`` of a
    batched scalar-prefetch operand falls back to one kernel call per lane.
    Pruned / padding rows decode to all -1 via the validity row (their
    directory entries are clamped to a zero entry — word 0 width 0 — which
    is always in range).
    """
    B = starts.shape[0]
    # Pad to whole tiles plus one spare tile, so every 16-row window is in
    # bounds, and view the stream as [rows, 128] int32 rows.
    n_words = pack_words.shape[0]
    n_pad = (n_words // _TILE_WORDS + 2) * _TILE_WORDS
    words = jax.lax.bitcast_convert_type(
        jnp.concatenate(
            [pack_words.astype(jnp.uint32), jnp.zeros((n_pad - n_words,), jnp.uint32)]
        ),
        jnp.int32,
    ).reshape(n_pad // BLOCK, BLOCK)
    bp = (B + b_tile - 1) // b_tile * b_tile
    table = jnp.stack([
        jnp.maximum(pack_dir.astype(jnp.int32), 0),
        pack_firsts.astype(jnp.int32),
        lens.astype(jnp.int32),
        (keep & (starts >= 0)).astype(jnp.int32),
        jnp.broadcast_to(range_start.astype(jnp.int32), (B,)),
    ])
    table = jnp.pad(table, ((0, 0), (0, bp - B)))  # pad blocks: zero entries

    out = pl.pallas_call(
        functools.partial(_unpack_kernel, b_tile=b_tile),
        grid=(bp // b_tile,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((b_tile, BLOCK), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((bp, BLOCK), jnp.int32),
        scratch_shapes=[
            pltpu.VMEM((b_tile, 2 * _TILE_ROWS, BLOCK), jnp.int32),
            pltpu.SemaphoreType.DMA((b_tile,)),
        ],
        interpret=pallas_interpret(),
    )(table, words)
    return out[:B].reshape(B * BLOCK)
