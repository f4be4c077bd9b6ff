"""Bit-packed docid delta codec: round-trip properties + space accounting.

The codec contract (DESIGN.md §12): ``unpack_docs(pack_docs(x, s, l), s, l)
== x`` bitwise for *any* valid block geometry, with the width directory
always choosing the smallest of ``PACK_WIDTHS`` that covers a block's max
delta. Property tests sweep randomized geometries; targeted cases pin the
edges the sweep can miss — 0-bit constant runs, single-posting blocks,
short tails, and full 32-bit deltas. Space assertions tie the accounting
formula to the actual uploaded device buffers.
"""

from __future__ import annotations

import jax
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.clustered_index import (
    BLOCK,
    PACK_DIR_BITS,
    PACK_WIDTHS,
    build_index,
    device_bytes_report,
    pack_dir_entries,
    pack_docs,
    unpack_docs,
)
from repro.core.range_daat import Engine
from repro.data.synth import make_corpus


def _random_geometry(rng, n_blocks, max_delta, max_len=BLOCK):
    """Random block-contiguous docid stream with bounded deltas."""
    blk_len = rng.integers(1, max_len + 1, size=n_blocks).astype(np.int64)
    blk_start = np.cumsum(blk_len) - blk_len
    chunks = []
    for length in blk_len:
        deltas = rng.integers(0, max_delta + 1, size=int(length))
        deltas[0] = 0  # block head carries the absolute docid
        chunks.append(int(rng.integers(0, 10_000)) + np.cumsum(deltas))
    return np.concatenate(chunks).astype(np.int64), blk_start, blk_len


# ------------------------------------------------------------ property sweep


@settings(max_examples=12, deadline=None)
@given(
    seed=st.integers(0, 2**30),
    max_delta=st.sampled_from([0, 1, 200, 255, 256, 65_535, 65_536, 2**20]),
    n_blocks=st.sampled_from([1, 3, 17]),
)
def test_pack_unpack_round_trip(seed, max_delta, n_blocks):
    rng = np.random.default_rng(seed)
    docs, blk_start, blk_len = _random_geometry(rng, n_blocks, max_delta)
    packed = pack_docs(docs, blk_start, blk_len)
    assert packed.n_postings == docs.shape[0]
    assert set(np.unique(packed.blk_width)) <= set(PACK_WIDTHS)
    # Width minimality: the directory picks the smallest covering width.
    for b in range(n_blocks):
        s, length = int(blk_start[b]), int(blk_len[b])
        d = np.diff(docs[s : s + length], prepend=docs[s]).max(initial=0)
        expect = next(w for w in PACK_WIDTHS if d < (1 << w) or w == 32)
        assert int(packed.blk_width[b]) == expect, (b, d)
    # Exact word budget: ceil(len * width / 32) per block, densely laid out.
    wpb = (blk_len * packed.blk_width + 31) // 32
    assert packed.n_words == int(wpb.sum())
    np.testing.assert_array_equal(
        packed.blk_word_start, np.cumsum(wpb) - wpb
    )
    np.testing.assert_array_equal(
        unpack_docs(packed, blk_start, blk_len), docs
    )


@settings(max_examples=6, deadline=None)
@given(seed=st.integers(0, 2**30), n_blocks=st.sampled_from([1, 9]))
def test_constant_and_singleton_blocks_cost_zero_words(seed, n_blocks):
    """0-bit runs: constant blocks and 1-posting blocks store no words."""
    rng = np.random.default_rng(seed)
    docs, blk_start, blk_len = _random_geometry(rng, n_blocks, max_delta=0)
    packed = pack_docs(docs, blk_start, blk_len)
    assert packed.n_words == 0
    assert np.all(packed.blk_width == 0)
    np.testing.assert_array_equal(packed.blk_first, docs[blk_start])
    np.testing.assert_array_equal(unpack_docs(packed, blk_start, blk_len), docs)

    ones = np.ones(n_blocks, np.int64)  # every block a single posting
    singles = np.arange(n_blocks, dtype=np.int64) * 37
    p1 = pack_docs(singles, np.arange(n_blocks, dtype=np.int64), ones)
    assert p1.n_words == 0 and np.all(p1.blk_width == 0)
    np.testing.assert_array_equal(
        unpack_docs(p1, np.arange(n_blocks, dtype=np.int64), ones), singles
    )


def test_short_tails_and_full_width_edges():
    """Tail blocks (< BLOCK lanes) and the 32-bit max-delta extreme."""
    # Mixed lengths incl. length-1 and length-BLOCK, forced width ladder.
    blk_len = np.asarray([1, 5, BLOCK, 3, 2], np.int64)
    blk_start = np.cumsum(blk_len) - blk_len
    rng = np.random.default_rng(0)
    docs = np.concatenate(
        [
            [7],
            5 + np.cumsum([0, 1, 1, 0, 1]),  # width 4
            np.cumsum(np.r_[0, rng.integers(0, 300, BLOCK - 1)]),  # width 16
            10 + np.cumsum([0, 70_000, 70_000]),  # width 32
            [4, 4],  # width 0
        ]
    ).astype(np.int64)
    packed = pack_docs(docs, blk_start, blk_len)
    assert packed.blk_width.tolist() == [0, 4, 16, 32, 0]
    np.testing.assert_array_equal(unpack_docs(packed, blk_start, blk_len), docs)

    # Max int32-representable delta: the 32-bit lane mask must not
    # truncate or sign-extend (docids themselves stay int32).
    big = np.asarray([1, 1 + (2**31 - 2)], np.int64)
    pb = pack_docs(big, np.asarray([0], np.int64), np.asarray([2], np.int64))
    assert pb.blk_width.tolist() == [32]
    np.testing.assert_array_equal(
        unpack_docs(pb, np.asarray([0], np.int64), np.asarray([2], np.int64)),
        big,
    )


def test_merged_directory_entries_round_trip():
    """pack_dir_entries ⊕ unpack_dir recovers (word_start, width) exactly."""
    import dataclasses

    import jax.numpy as jnp

    from repro.kernels.range_scorer.ref import unpack_dir

    rng = np.random.default_rng(7)
    docs, blk_start, blk_len = _random_geometry(rng, 17, max_delta=2**20)
    packed = pack_docs(docs, blk_start, blk_len)
    entries = pack_dir_entries(packed)
    assert entries.dtype == np.int32 and np.all(entries >= 0)
    ws, w = unpack_dir(jnp.asarray(entries))
    np.testing.assert_array_equal(np.asarray(ws), packed.blk_word_start)
    np.testing.assert_array_equal(np.asarray(w), packed.blk_width)

    # Word offsets beyond the 2^PACK_DIR_BITS cap must refuse to merge, not
    # silently corrupt the width bits (zero-strided view: no allocation).
    huge = dataclasses.replace(
        packed,
        words=np.broadcast_to(np.zeros(1, np.uint32), (1 << PACK_DIR_BITS,)),
    )
    with pytest.raises(ValueError, match="shard the index"):
        pack_dir_entries(huge)


def test_pack_rejects_invalid_input():
    s1 = np.asarray([0], np.int64)
    with pytest.raises(ValueError, match="BLOCK"):
        pack_docs(
            np.arange(BLOCK + 1), s1, np.asarray([BLOCK + 1], np.int64)
        )
    with pytest.raises(ValueError, match="non-negative"):
        pack_docs(np.asarray([-1, 2]), s1, np.asarray([2], np.int64))
    with pytest.raises(ValueError, match="non-decreasing"):
        pack_docs(np.asarray([5, 3]), s1, np.asarray([2], np.int64))


# ------------------------------------------------------- built-index mirror


def test_built_index_round_trip_and_cache():
    corpus = make_corpus(
        n_docs=400, n_terms=300, n_topics=4, mean_doc_len=40, seed=2
    )
    idx = build_index(corpus, n_ranges=4, strategy="clustered")
    packed = idx.packed_postings()
    assert packed is idx.packed_postings()  # cached per index object
    np.testing.assert_array_equal(
        unpack_docs(packed, idx.blk_start, idx.blk_len), idx.docs
    )
    # The packed mirror is strictly smaller than raw int32 docids here.
    assert packed.device_nbytes() < idx.nnz * 4


def test_space_report_matches_uploaded_buffers():
    """The accounting formula equals the actual device buffer nbytes."""
    corpus = make_corpus(
        n_docs=400, n_terms=300, n_topics=4, mean_doc_len=40, seed=3
    )
    idx = build_index(corpus, n_ranges=4, strategy="clustered")
    for docs_format, impact_dtype in [
        ("int32", "int32"), ("packed", "int8"), ("packed", "int32")
    ]:
        eng = Engine(
            idx, k=5, impact_dtype=impact_dtype, docs_format=docs_format
        )
        dev = idx.device_bytes(impact_dtype, docs_format)
        if docs_format == "packed":
            uploaded = (
                eng.dix.pack_words.nbytes
                + eng.dix.pack_dir.nbytes
                + eng.dix.pack_first.nbytes
            )
            # Packed docids come from the word stream: no docid rows.
            assert eng.dix.blk_docs is None
        else:
            uploaded = eng.dix.blk_docs.nbytes
            assert eng.dix.pack_words is None
        assert dev["docs"] == uploaded, (docs_format, impact_dtype)
        assert dev["impacts"] == eng.dix.blk_imps.nbytes
        assert dev["postings"] == dev["docs"] + dev["impacts"]
        # Formula-only path (manifest metadata) agrees with the index path.
        meta = device_bytes_report(
            nnz=idx.nnz,
            n_blocks=idx.n_blocks,
            n_terms=idx.n_terms,
            n_ranges=idx.n_ranges,
            impact_dtype=impact_dtype,
            docs_format=docs_format,
            n_pack_words=idx.packed_postings().n_words,
        )
        assert meta == dev
    assert jax.device_count() >= 1  # sanity: buffers actually uploaded


def test_space_report_surfaces_packed_device_bytes():
    corpus = make_corpus(
        n_docs=300, n_terms=200, n_topics=3, mean_doc_len=30, seed=5
    )
    idx = build_index(corpus, n_ranges=3, strategy="clustered")
    rep_raw = idx.space_report("int8", "int32")
    rep_pk = idx.space_report("int8", "packed")
    assert rep_pk["device_bytes"]["docs"] < rep_raw["device_bytes"]["docs"]
    # Logical paper-width accounting is format-independent.
    assert rep_pk["postings_gib"] == rep_raw["postings_gib"]
