"""Pallas range-scorer kernel vs pure-jnp oracle: shape/dtype sweeps.

On CPU the kernels run in interpret mode (``kernel.pallas_interpret``);
``tests/test_tpu_compile.py`` compiles them for a described v5e, and
``chip_smoke.py`` checks them compiled on the chip. All comparisons are
exact — integer impacts accumulated in fp32 stay below 2^24.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.clustered_index import pack_dir_entries, pack_docs
from repro.kernels.range_scorer import ref
from repro.kernels.range_scorer.kernel import (
    scatter_accumulate_pallas,
    unpack_locals_pallas,
)
from repro.kernels.range_scorer.ops import score_blocks


def _random_case(rng, nnz, n_blocks, s_range):
    docs = np.sort(rng.integers(0, s_range, size=nnz)).astype(np.int32)
    imps = rng.integers(1, 256, size=nnz).astype(np.int32)
    starts = rng.integers(0, max(nnz - ref.BLOCK, 1), size=n_blocks).astype(np.int64)
    lens = rng.integers(1, ref.BLOCK + 1, size=n_blocks).astype(np.int32)
    lens = np.minimum(lens, nnz - starts).astype(np.int32)
    keep = rng.random(n_blocks) < 0.8
    return docs, imps, starts, lens, keep


@pytest.mark.parametrize("s_pad", [128, 384, 1024])
@pytest.mark.parametrize("n_blocks", [1, 7, 32])
def test_pallas_matches_ref(s_pad, n_blocks):
    rng = np.random.default_rng(s_pad * 1000 + n_blocks)
    docs, imps, starts, lens, keep = _random_case(rng, 5000, n_blocks, s_pad)
    r0 = jnp.int32(0)
    expect = score_blocks(
        jnp.asarray(docs), jnp.asarray(imps), jnp.asarray(starts),
        jnp.asarray(lens), jnp.asarray(keep), r0, s_pad=s_pad, impl="xla",
    )
    got = score_blocks(
        jnp.asarray(docs), jnp.asarray(imps), jnp.asarray(starts),
        jnp.asarray(lens), jnp.asarray(keep), r0, s_pad=s_pad, impl="pallas",
    )
    np.testing.assert_array_equal(np.asarray(expect), np.asarray(got))


@pytest.mark.parametrize("s_tile,p_tile", [(128, 128), (256, 512), (512, 1024)])
def test_pallas_tile_sweep(s_tile, p_tile):
    rng = np.random.default_rng(s_tile + p_tile)
    P, S = 3000, 900
    ids = rng.integers(-1, S, size=P).astype(np.int32)
    vals = rng.integers(0, 256, size=P).astype(np.int32)
    vals[ids < 0] = 0
    got = scatter_accumulate_pallas(
        jnp.asarray(ids), jnp.asarray(vals), s_pad=S, s_tile=s_tile, p_tile=p_tile
    )
    expect = np.zeros(S, np.int64)
    np.add.at(expect, ids[ids >= 0], vals[ids >= 0])
    np.testing.assert_array_equal(np.asarray(got, np.int64), expect)


def test_all_pruned_gives_zero():
    rng = np.random.default_rng(0)
    docs, imps, starts, lens, _ = _random_case(rng, 1000, 4, 256)
    out = score_blocks(
        jnp.asarray(docs), jnp.asarray(imps), jnp.asarray(starts),
        jnp.asarray(lens), jnp.zeros(4, bool), jnp.int32(0),
        s_pad=256, impl="pallas",
    )
    assert int(jnp.sum(out)) == 0


def test_padding_blocks_ignored():
    rng = np.random.default_rng(1)
    docs, imps, starts, lens, keep = _random_case(rng, 1000, 4, 256)
    starts2 = np.concatenate([starts, [-1, -1]])
    lens2 = np.concatenate([lens, [128, 128]]).astype(np.int32)
    keep2 = np.concatenate([keep, [True, True]])
    a = score_blocks(
        jnp.asarray(docs), jnp.asarray(imps), jnp.asarray(starts),
        jnp.asarray(lens), jnp.asarray(keep), jnp.int32(0), s_pad=256,
    )
    b = score_blocks(
        jnp.asarray(docs), jnp.asarray(imps), jnp.asarray(starts2),
        jnp.asarray(lens2), jnp.asarray(keep2), jnp.int32(0), s_pad=256,
    )
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# --------------------------------------------------- packed docid decoding


def _packed_pool(rng, n_pool, max_deltas=(0, 1, 200, 255, 300, 70_000)):
    """Pool of contiguous blocks spanning every pack width, pre-packed."""
    blk_len = rng.integers(1, ref.BLOCK + 1, size=n_pool).astype(np.int64)
    blk_start = np.cumsum(blk_len) - blk_len
    chunks = []
    for length in blk_len:
        md = int(rng.choice(max_deltas))
        d = np.zeros(int(length), np.int64)
        if md:
            d[1:] = rng.integers(0, md + 1, size=int(length) - 1)
        chunks.append(int(rng.integers(0, 500)) + np.cumsum(d))
    docs = np.concatenate(chunks).astype(np.int64)
    packed = pack_docs(docs, blk_start, blk_len)
    imps = rng.integers(1, 256, size=docs.shape[0]).astype(np.int32)
    return docs, imps, blk_start, blk_len, packed


def _select(packed, blk_start, blk_len, sel):
    """Per-query directory columns for the selected blocks (engine layout)."""
    return dict(
        starts=jnp.asarray(blk_start[sel], jnp.int32),
        lens=jnp.asarray(blk_len[sel], jnp.int32),
        pack_dir=jnp.asarray(pack_dir_entries(packed)[sel], jnp.int32),
        pack_firsts=jnp.asarray(packed.blk_first[sel], jnp.int32),
    )


@pytest.mark.parametrize(
    "seed,n_pool,n_sel", [(0, 24, 17), (3, 24, 17), (9, 24, 17), (4, 400, 64)]
)
def test_pallas_packed_decode_matches_oracle_across_widths(seed, n_pool, n_sel):
    """Kernel decode == pure-jnp oracle for every width in one dispatch.

    The 400-block pool spans many 1024-word tiles, so block runs start at
    every offset and cross 128-word row and tile boundaries.
    """
    rng = np.random.default_rng(seed)
    _, imps, blk_start, blk_len, packed = _packed_pool(rng, n_pool)
    assert {0, 4, 8, 16, 32} <= set(packed.blk_width.tolist())
    sel = rng.integers(0, n_pool, size=n_sel)  # duplicates allowed, like a query
    cols = _select(packed, blk_start, blk_len, sel)
    keep = jnp.asarray(rng.random(n_sel) < 0.8)
    words = jnp.asarray(packed.words)
    r0 = jnp.int32(int(rng.integers(0, 100)))
    oracle_local, _ = ref.gather_block_postings_packed(
        words, jnp.asarray(imps), cols["starts"], cols["lens"],
        cols["pack_dir"], cols["pack_firsts"], keep, r0,
    )
    got = unpack_locals_pallas(
        words, cols["starts"], cols["lens"],
        cols["pack_dir"], cols["pack_firsts"], keep, r0,
    )
    np.testing.assert_array_equal(np.asarray(got), np.asarray(oracle_local))


def test_pallas_packed_decode_pruned_and_padding_rows():
    """keep=False rows and starts==-1 padding rows decode to all -1."""
    rng = np.random.default_rng(5)
    _, imps, blk_start, blk_len, packed = _packed_pool(rng, 8)
    sel = np.arange(8)
    cols = _select(packed, blk_start, blk_len, sel)
    # Engine-style padding rows: starts == -1, directory columns carry the
    # clamped gather of a real block (index 0), exactly what safe_ids does.
    starts = jnp.concatenate([cols["starts"], jnp.asarray([-1, -1], jnp.int32)])
    pad = lambda c: jnp.concatenate([c, c[:1], c[:1]])
    lens = pad(cols["lens"])
    pd, pf = pad(cols["pack_dir"]), pad(cols["pack_firsts"])
    words = jnp.asarray(packed.words)
    r0 = jnp.int32(0)

    all_pruned = jnp.zeros(10, bool)
    got = unpack_locals_pallas(words, starts, lens, pd, pf, all_pruned, r0)
    assert np.all(np.asarray(got) == -1)

    keep = jnp.ones(10, bool)
    got = unpack_locals_pallas(words, starts, lens, pd, pf, keep, r0)
    oracle_local, _ = ref.gather_block_postings_packed(
        words, jnp.asarray(imps), starts, lens, pd, pf, keep, r0
    )
    np.testing.assert_array_equal(np.asarray(got), np.asarray(oracle_local))
    assert np.all(np.asarray(got).reshape(10, ref.BLOCK)[8:] == -1)


def test_score_blocks_packed_parity_straddling_width_change():
    """One scored range spanning a width change: all three paths agree."""
    rng = np.random.default_rng(11)
    # Narrow deltas only so every docid stays inside a modest accumulator.
    docs, imps, blk_start, blk_len, packed = _packed_pool(
        rng, 12, max_deltas=(0, 1, 7)
    )
    assert len(set(packed.blk_width.tolist())) >= 2  # widths change mid-range
    s_pad = int(docs.max()) + 1
    sel = np.arange(12)
    cols = _select(packed, blk_start, blk_len, sel)
    keep = jnp.asarray(rng.random(12) < 0.9)
    words = jnp.asarray(packed.words)
    pk = dict(
        pack_words=words, pack_dir=cols["pack_dir"],
        pack_firsts=cols["pack_firsts"],
    )
    for r0 in (0, 3):
        base = score_blocks(
            jnp.asarray(docs, jnp.int32), jnp.asarray(imps), cols["starts"],
            cols["lens"], keep, jnp.int32(r0), s_pad=s_pad, impl="xla",
        )
        for impl in ("xla", "pallas"):
            got = score_blocks(
                jnp.zeros((1,), jnp.int32), jnp.asarray(imps), cols["starts"],
                cols["lens"], keep, jnp.int32(r0), s_pad=s_pad, impl=impl,
                docs_format="packed", **pk,
            )
            np.testing.assert_array_equal(
                np.asarray(got), np.asarray(base), err_msg=f"{impl} r0={r0}"
            )


def test_score_blocks_packed_requires_directory():
    with pytest.raises(ValueError, match="pack_"):
        score_blocks(
            jnp.zeros((1,), jnp.int32), jnp.zeros((1,), jnp.int32),
            jnp.zeros((1,), jnp.int32), jnp.ones((1,), jnp.int32),
            jnp.ones((1,), bool), jnp.int32(0), s_pad=128,
            docs_format="packed",
        )


@settings(max_examples=25, deadline=None)
@given(
    seed=st.integers(0, 2**31 - 1),
    s_pad=st.sampled_from([128, 256, 640]),
    n_blocks=st.integers(1, 24),
    range_start=st.integers(0, 100),
)
def test_property_pallas_equals_scatter(seed, s_pad, n_blocks, range_start):
    """Property: kernel == oracle for arbitrary block geometry + offsets."""
    rng = np.random.default_rng(seed)
    nnz = int(rng.integers(200, 4000))
    docs = np.sort(rng.integers(range_start, range_start + s_pad, size=nnz)).astype(
        np.int32
    )
    imps = rng.integers(1, 256, size=nnz).astype(np.int32)
    starts = rng.integers(0, nnz, size=n_blocks).astype(np.int64)
    lens = np.minimum(
        rng.integers(1, ref.BLOCK + 1, size=n_blocks), nnz - starts
    ).astype(np.int32)
    keep = rng.random(n_blocks) < 0.7
    args = (
        jnp.asarray(docs), jnp.asarray(imps), jnp.asarray(starts),
        jnp.asarray(lens), jnp.asarray(keep), jnp.int32(range_start),
    )
    a = score_blocks(*args, s_pad=s_pad, impl="xla")
    b = score_blocks(*args, s_pad=s_pad, impl="pallas")
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
