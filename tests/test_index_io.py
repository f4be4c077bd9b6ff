"""Index lifecycle subsystem (DESIGN.md §8): artifacts, ingestion, int8.

Parity contracts are bitwise, matching the repo-wide convention: a loaded
artifact must produce `device_traverse` results identical to the
in-memory build — docids, scores, and tie-breaks — at either impact
storage dtype, single-device and sharded.
"""

from __future__ import annotations

import json
import os

import numpy as np
import pytest

from repro import index_io
from repro.core.clustered_index import build_index_cached, shard_device_index
from repro.core.range_daat import IMPACT_BIAS, Engine, pack_impacts
from repro.index_io import corpus_io
from repro.index_io.__main__ import main as index_io_cli
from repro.serving.sharded import ShardedEngine

DTYPES = ("int32", "int8")
SHARD_FIELDS = (
    "docs", "impacts", "blk_start", "blk_len", "blk_maxdoc", "blk_maximp",
    "blk_map", "range_starts", "range_sizes", "bounds_dense",
)


def _topk(engine, q):
    res = engine.traverse(engine.plan(q))
    return (
        np.asarray(res.state.ids).tolist(),
        np.asarray(res.state.vals).tolist(),
    )


# --------------------------------------------------------------------------
# Artifact round-trip — single device
# --------------------------------------------------------------------------


@pytest.mark.parametrize("impact_dtype", DTYPES)
@pytest.mark.parametrize("mmap", [False, True])
def test_round_trip_bitwise(index, queries, tmp_path, impact_dtype, mmap):
    path = str(tmp_path / f"art_{impact_dtype}")
    index_io.save_index(index, path, impact_dtype=impact_dtype)
    loaded = index_io.load_index(path, mmap=mmap)

    # Fingerprint stability across save/load (impacts widen back to exact
    # int32, so the int8 artifact hashes identically).
    assert loaded.fingerprint() == index.fingerprint()
    assert index_io.read_manifest(path)["fingerprint"] == index.fingerprint()

    ref = Engine(index, k=10)
    eng = Engine(loaded, k=10, impact_dtype=impact_dtype)
    for q in queries[:6]:
        assert _topk(eng, q) == _topk(ref, q)


def test_int8_engine_matches_int32_results(index, queries):
    """Native int8 HBM storage must not change any retrieval result."""
    e32 = Engine(index, k=10)
    e8 = Engine(index, k=10, impact_dtype="int8")
    for q in queries:
        assert _topk(e8, q) == _topk(e32, q)
    # Budgeted (anytime) traversals take the same early exits too.
    for q in queries[:4]:
        r32 = e32.traverse(e32.plan(q), budget_postings=512)
        r8 = e8.traverse(e8.plan(q), budget_postings=512)
        assert np.array_equal(np.asarray(r32.state.ids), np.asarray(r8.state.ids))
        assert np.array_equal(np.asarray(r32.state.vals), np.asarray(r8.state.vals))
        assert bool(r32.exit_budget) == bool(r8.exit_budget)


def test_pack_impacts_bias_roundtrip(index):
    packed = pack_impacts(index.impacts, "int8")
    assert packed.dtype == np.int8
    assert np.array_equal(
        packed.astype(np.int64) + IMPACT_BIAS, index.impacts.astype(np.int64)
    )
    with pytest.raises(ValueError):
        pack_impacts(index.impacts, "int16")


def test_int8_rejected_above_8_bits(corpus, clustered_arrangement, tmp_path):
    from repro.core.clustered_index import build_index

    idx9 = build_index(corpus, arrangement=clustered_arrangement, bits=9)
    with pytest.raises(ValueError, match="bits <= 8"):
        Engine(idx9, impact_dtype="int8")
    # Disk path rejects too, and a failed save leaves no staging dir behind.
    with pytest.raises(ValueError, match="bits <= 8"):
        index_io.save_index(idx9, str(tmp_path / "idx9"), impact_dtype="int8")
    assert [d for d in os.listdir(tmp_path) if ".tmp-" in d] == []


# --------------------------------------------------------------------------
# Artifact round-trip — shards
# --------------------------------------------------------------------------


@pytest.mark.parametrize("impact_dtype", DTYPES)
def test_shards_round_trip(index, queries, tmp_path, impact_dtype):
    shards = shard_device_index(index, 2)
    path = str(tmp_path / "shards")
    index_io.save_shards(
        shards, path, impact_dtype=impact_dtype, quantizer=index.quantizer
    )
    loaded = index_io.load_shards(path)

    assert len(loaded) == len(shards)
    for a, b in zip(shards, loaded):
        for f in SHARD_FIELDS:
            assert np.array_equal(getattr(a, f), getattr(b, f)), f
        assert (a.shard_id, a.range_lo, a.range_hi, a.doc_base, a.n_docs,
                a.postings) == (b.shard_id, b.range_lo, b.range_hi,
                                b.doc_base, b.n_docs, b.postings)

    # 2-shard device parity: loaded shards drive the same merged top-k.
    ref = ShardedEngine(Engine(index, k=10), 2, use_mesh=False)
    eng = ShardedEngine(
        Engine(index, k=10, impact_dtype=impact_dtype), 2,
        use_mesh=False, shards=loaded,
    )
    for q in queries[:6]:
        r0 = ref.traverse(ref.plan(q))
        r1 = eng.traverse(eng.plan(q))
        assert r0.doc_ids.tolist() == r1.doc_ids.tolist()
        assert r0.scores.tolist() == r1.scores.tolist()


def test_shards_preloaded_count_checked(index):
    shards = shard_device_index(index, 2)
    with pytest.raises(ValueError, match="shard count"):
        ShardedEngine(Engine(index, k=10), 3, use_mesh=False, shards=shards)


def test_shards_int8_requires_quantizer(index, tmp_path):
    shards = shard_device_index(index, 2)
    with pytest.raises(ValueError, match="quantizer"):
        index_io.save_shards(shards, str(tmp_path / "s"), impact_dtype="int8")


def test_from_artifact_end_to_end(index, queries, tmp_path):
    """The full loading surface: index artifact + shard artifact + engines."""
    path = str(tmp_path / "idx")
    spath = str(tmp_path / "idx.shards2")
    index_io.save_index(index, path, impact_dtype="int8")
    index_io.save_shards(
        shard_device_index(index, 2), spath, impact_dtype="int8",
        quantizer=index.quantizer, source_fingerprint=index.fingerprint(),
    )

    eng = Engine.from_artifact(path, k=10)
    assert eng.impact_dtype == "int8"  # defaults to the artifact's dtype
    seng = ShardedEngine.from_artifact(
        path, 2, shards_path=spath, use_mesh=False, k=10
    )
    ref = ShardedEngine(Engine(index, k=10), 2, use_mesh=False)
    for q in queries[:3]:
        r0 = ref.traverse(ref.plan(q))
        r1 = seng.traverse(seng.plan(q))
        assert r0.doc_ids.tolist() == r1.doc_ids.tolist()
        assert r0.scores.tolist() == r1.scores.tolist()


def test_from_artifact_rejects_stale_shards(index, tmp_path):
    """A shard set carved from a different index must not silently serve."""
    from repro.core.clustered_index import build_index
    from repro.data.synth import make_corpus

    other = build_index(
        make_corpus(n_docs=400, n_terms=300, n_topics=4, seed=9), n_ranges=4,
        strategy="clustered",
    )
    opath = str(tmp_path / "other")
    index_io.save_index(other, opath)
    spath = str(tmp_path / "stale.shards")
    index_io.save_shards(
        shard_device_index(index, 2), spath,
        quantizer=index.quantizer, source_fingerprint=index.fingerprint(),
    )
    with pytest.raises(index_io.ArtifactError, match="carved from"):
        ShardedEngine.from_artifact(opath, 2, shards_path=spath, use_mesh=False)

    # A shard set with no recorded source fingerprint is unverifiable and
    # equally refused (load_shards + ShardedEngine(shards=...) bypasses).
    upath = str(tmp_path / "unverifiable.shards")
    index_io.save_shards(shard_device_index(index, 2), upath,
                         quantizer=index.quantizer)
    with pytest.raises(index_io.ArtifactError, match="source_fingerprint"):
        ShardedEngine.from_artifact(opath, 2, shards_path=upath, use_mesh=False)


# --------------------------------------------------------------------------
# device_bytes accounting
# --------------------------------------------------------------------------


@pytest.mark.parametrize("impact_dtype", DTYPES)
def test_device_bytes_match_uploaded_buffers(index, impact_dtype):
    eng = Engine(index, impact_dtype=impact_dtype)
    dev = index.space_report(impact_dtype)["device_bytes"]
    # pack_* leaves are None in the raw-int32 docid layout and accounted
    # as a single "docs" line in the packed one (tests/test_packed_postings
    # covers that path), so only materialized non-pack leaves line up 1:1.
    # The postings' block rows are reported as "docs" and "impacts".
    key = {"blk_docs": "docs", "blk_imps": "impacts"}
    fields = [
        n for n in eng.dix._fields
        if not n.startswith("pack_") and getattr(eng.dix, n) is not None
    ]
    for name in fields:
        got = np.asarray(getattr(eng.dix, name)).nbytes
        assert dev[key.get(name, name)] == got, name
    assert dev["postings"] == dev["docs"] + dev["impacts"]
    assert dev["total"] == sum(dev[key.get(n, n)] for n in fields)


def test_int8_halves_postings_hbm(index):
    d32 = index.space_report("int32")["device_bytes"]
    d8 = index.space_report("int8")["device_bytes"]
    assert d32["impacts"] == 4 * d8["impacts"]  # 4 B -> 1 B per block lane
    assert d32["postings"] / d8["postings"] >= 1.5  # docs stay int32
    assert d8["total"] < d32["total"]
    with pytest.raises(ValueError):
        index.device_bytes("float16")


# --------------------------------------------------------------------------
# Error paths: corruption, versioning, overwrite
# --------------------------------------------------------------------------


@pytest.fixture()
def artifact_path(index, tmp_path):
    path = str(tmp_path / "art")
    index_io.save_index(index, path, impact_dtype="int8")
    return path


def test_corrupt_manifest_raises(artifact_path):
    with open(os.path.join(artifact_path, "manifest.json"), "w") as f:
        f.write("{ not json")
    with pytest.raises(index_io.CorruptArtifactError, match="unparseable"):
        index_io.load_index(artifact_path)
    assert index_io.validate_artifact(artifact_path) != []


def test_version_mismatch_raises(artifact_path):
    mpath = os.path.join(artifact_path, "manifest.json")
    with open(mpath) as f:
        manifest = json.load(f)
    manifest["format_version"] = index_io.FORMAT_VERSION + 1
    with open(mpath, "w") as f:
        json.dump(manifest, f)
    with pytest.raises(index_io.VersionMismatchError, match="format_version"):
        index_io.load_index(artifact_path)


def test_missing_array_raises(artifact_path):
    os.remove(os.path.join(artifact_path, "arrays", "docs.npy"))
    with pytest.raises(index_io.CorruptArtifactError, match="docs"):
        index_io.load_index(artifact_path)


def test_tampered_array_fails_fingerprint(artifact_path):
    fpath = os.path.join(artifact_path, "arrays", "docs.npy")
    docs = np.load(fpath)
    docs = docs.copy()
    docs[0] += 1
    np.save(fpath, docs)
    with pytest.raises(index_io.CorruptArtifactError, match="fingerprint"):
        index_io.load_index(artifact_path)
    assert any("sha256" in p for p in index_io.validate_artifact(artifact_path))


def test_wrong_kind_raises(index, artifact_path, tmp_path):
    shards = shard_device_index(index, 2)
    spath = str(tmp_path / "shards")
    index_io.save_shards(shards, spath, quantizer=index.quantizer)
    with pytest.raises(index_io.CorruptArtifactError, match="kind"):
        index_io.load_index(spath)
    with pytest.raises(index_io.CorruptArtifactError, match="kind"):
        index_io.load_shards(artifact_path)


def test_concurrent_overwrite_readers_survive_publish_race(index, tmp_path):
    """Bugfix regression: the rename-aside overwrite admits a briefly-absent
    path, so readers (read_manifest / load_index / validate) must retry once
    on ENOENT instead of crashing on a healthy artifact. Stress: one writer
    republishing in a loop against concurrent readers."""
    import threading

    path = str(tmp_path / "live")
    index_io.save_index(index, path)
    stop = threading.Event()
    errors: list = []

    def writer():
        try:
            for _ in range(12):
                index_io.save_index(index, path, overwrite=True)
        except Exception as e:  # noqa: BLE001 — surfaced via `errors`
            errors.append(("writer", repr(e)))
        finally:
            stop.set()

    def reader():
        try:
            while not stop.is_set():
                manifest = index_io.read_manifest(path)
                assert manifest["fingerprint"] == index.fingerprint()
                loaded = index_io.load_index(path, mmap=True)
                assert loaded.fingerprint() == index.fingerprint()
        except Exception as e:  # noqa: BLE001 — surfaced via `errors`
            errors.append(("reader", repr(e)))

    threads = [threading.Thread(target=writer)] + [
        threading.Thread(target=reader) for _ in range(3)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300)
    assert errors == []
    assert index_io.validate_artifact(path) == []


def test_overwrite_guard(index, artifact_path):
    with pytest.raises(index_io.ArtifactError, match="overwrite"):
        index_io.save_index(index, artifact_path)
    index_io.save_index(index, artifact_path, overwrite=True)  # replaces
    assert index_io.validate_artifact(artifact_path) == []
    # No staging directories left behind (unique per-save `<name>.tmp-*`).
    leftovers = [
        d for d in os.listdir(os.path.dirname(artifact_path)) if ".tmp-" in d
    ]
    assert leftovers == []


# --------------------------------------------------------------------------
# Cached build via the artifact format (pickle path deleted)
# --------------------------------------------------------------------------


def test_build_index_cached_uses_artifacts(tmp_path):
    from repro.data.synth import make_corpus

    c = make_corpus(n_docs=400, n_terms=300, n_topics=4, seed=3)
    cache = str(tmp_path / "cache")
    i1 = build_index_cached(c, cache_dir=cache, n_ranges=4, strategy="clustered")
    entries = os.listdir(cache)
    assert len(entries) == 1 and entries[0].startswith("index_")
    assert not entries[0].endswith(".pkl")  # the pickle path is gone
    assert index_io.validate_artifact(os.path.join(cache, entries[0])) == []
    i2 = build_index_cached(c, cache_dir=cache, n_ranges=4, strategy="clustered")
    assert i2.fingerprint() == i1.fingerprint()
    assert os.listdir(cache) == entries  # cache hit, no rebuild


def test_build_index_cached_self_heals_old_format(tmp_path):
    """A format-version bump is a cache miss, not a permanent crash."""
    from repro.data.synth import make_corpus

    c = make_corpus(n_docs=400, n_terms=300, n_topics=4, seed=3)
    cache = str(tmp_path / "cache")
    i1 = build_index_cached(c, cache_dir=cache, n_ranges=4, strategy="clustered")
    entry = os.path.join(cache, os.listdir(cache)[0])
    mpath = os.path.join(entry, "manifest.json")
    with open(mpath) as f:
        manifest = json.load(f)
    manifest["format_version"] = index_io.FORMAT_VERSION - 1  # "older" format
    with open(mpath, "w") as f:
        json.dump(manifest, f)

    i2 = build_index_cached(c, cache_dir=cache, n_ranges=4, strategy="clustered")
    assert i2.fingerprint() == i1.fingerprint()  # rebuilt, same build inputs
    assert index_io.validate_artifact(entry) == []  # entry rewritten current
    # Corruption still raises (the docstring's contract) — not silently healed.
    with open(mpath, "w") as f:
        f.write("broken")
    with pytest.raises(index_io.CorruptArtifactError):
        build_index_cached(c, cache_dir=cache, n_ranges=4, strategy="clustered")


# --------------------------------------------------------------------------
# Corpus reader registry
# --------------------------------------------------------------------------


def test_tsv_reader_round_trip(tmp_path):
    src = tmp_path / "coll.tsv"
    src.write_text(
        "d0\tthe quick brown fox\n"
        "d1\tquick quick fox jumps\n"
        "\n"
        "d2\tlazy dog sleeps\n"
    )
    c = corpus_io.read_tsv(str(src))
    assert c.n_docs == 3
    # Vocabulary in sorted token order: brown dog fox jumps lazy quick sleeps the
    assert c.n_terms == 8
    t, tf = c.doc_slice(1)
    vocab = {"brown": 0, "dog": 1, "fox": 2, "jumps": 3, "lazy": 4,
             "quick": 5, "sleeps": 6, "the": 7}
    assert dict(zip(t.tolist(), tf.tolist())) == {
        vocab["quick"]: 2, vocab["fox"]: 1, vocab["jumps"]: 1
    }
    c2 = corpus_io.read_corpus("tsv", str(src))
    assert c2.fingerprint() == c.fingerprint()  # deterministic
    assert corpus_io.read_tsv(str(src), max_docs=2).n_docs == 2


def test_jsonl_reader_text_and_terms(tmp_path):
    text_src = tmp_path / "text.jsonl"
    text_src.write_text(
        '{"id": "a", "text": "alpha beta"}\n{"id": "b", "text": "beta gamma"}\n'
    )
    c = corpus_io.read_jsonl(str(text_src))
    assert c.n_docs == 2 and c.n_terms == 3

    term_src = tmp_path / "terms.jsonl"
    term_src.write_text(
        '{"terms": [0, 2], "tfs": [3, 1]}\n{"terms": [1]}\n'
    )
    c = corpus_io.read_jsonl(str(term_src))
    assert c.n_docs == 2 and c.n_terms == 3
    t, tf = c.doc_slice(0)
    assert t.tolist() == [0, 2] and tf.tolist() == [3, 1]

    mixed = tmp_path / "mixed.jsonl"
    mixed.write_text('{"text": "a"}\n{"terms": [0]}\n')
    with pytest.raises(ValueError, match="mixes"):
        corpus_io.read_jsonl(str(mixed))


def test_tsv_reader_rejects_untabbed_line(tmp_path):
    src = tmp_path / "bad.tsv"
    src.write_text("d0\tfine text\nd1 missing tab separator\n")
    with pytest.raises(ValueError, match="no tab"):
        corpus_io.read_tsv(str(src))


def test_ingested_corpus_builds_and_serves(tmp_path):
    """A real-collection reader output drives the full pipeline."""
    lines = []
    rng = np.random.default_rng(0)
    words = [f"w{i}" for i in range(50)]
    for d in range(60):
        toks = rng.choice(words, size=rng.integers(5, 15))
        lines.append(f"doc{d}\t{' '.join(toks)}")
    src = tmp_path / "c.tsv"
    src.write_text("\n".join(lines) + "\n")

    from repro.core.clustered_index import build_index

    c = corpus_io.read_corpus("tsv", str(src))
    idx = build_index(c, n_ranges=2, strategy="clustered")
    eng = Engine(idx, k=5)
    res = eng.traverse(eng.plan(np.asarray([0, 1, 2], np.int32)))
    ids = np.asarray(res.state.ids)
    assert (ids >= 0).any()


def test_gated_readers_clean_without_optional_deps():
    avail = corpus_io.available_readers()
    assert {"synth", "tsv", "jsonl", "ciff", "ir_datasets"} <= set(avail)
    for name in ("ciff", "ir_datasets"):
        if avail[name]:  # pragma: no cover — extra installed in this env
            pytest.skip(f"optional dep for {name} installed")
        with pytest.raises(corpus_io.MissingDependencyError, match="repro\\[corpus\\]"):
            corpus_io.get_reader(name)
    with pytest.raises(KeyError, match="unknown corpus reader"):
        corpus_io.get_reader("nope")


# --------------------------------------------------------------------------
# CLI
# --------------------------------------------------------------------------


def test_cli_build_inspect_validate(tmp_path, capsys):
    out = str(tmp_path / "idx")
    rc = index_io_cli([
        "build", "--out", out, "--reader", "synth",
        "--n-docs", "400", "--n-terms", "300", "--n-topics", "4",
        "--n-ranges", "4", "--impact-dtype", "int8", "--shards", "2",
    ])
    assert rc == 0
    assert index_io_cli(["inspect", out]) == 0
    assert "int8" in capsys.readouterr().out
    assert index_io_cli(["validate", out]) == 0
    assert index_io_cli(["validate", out + ".shards2"]) == 0

    # Corruption is a nonzero exit, not a traceback.
    with open(os.path.join(out, "manifest.json"), "w") as f:
        f.write("broken")
    assert index_io_cli(["validate", out]) == 1
    assert index_io_cli(["inspect", out]) == 1


def test_cli_rejects_int8_above_8_bits(tmp_path, capsys):
    """Bad parameter combos exit 1 with a message — before any build work."""
    rc = index_io_cli([
        "build", "--out", str(tmp_path / "x"), "--bits", "9",
        "--impact-dtype", "int8", "--n-docs", "100", "--n-terms", "80",
    ])
    assert rc == 1
    assert "--bits <= 8" in capsys.readouterr().err
