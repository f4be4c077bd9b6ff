"""The stored index: keyed by configuration, seed and index-building sources."""

import numpy as np

from bench import gen, system
from bench.spec import load_cell
from bench.tests.conftest import REPO


def _tree(root, body="x = 1\n"):
    for sub in ("core", "data", "index_io", "serving"):
        d = root / "src/repro" / sub
        d.mkdir(parents=True, exist_ok=True)
        (d / "m.py").write_text(body)
    (root / "bench").mkdir(exist_ok=True)
    for name in ("gen.py", "system.py", "loop.py"):
        (root / "bench" / name).write_text(body)
    (root / "cfg.json").write_text("{}")


def test_key_follows_config_seed_and_index_sources(tmp_path):
    _tree(tmp_path)
    cfg = tmp_path / "cfg.json"
    key = system.index_key(cfg, 5, tmp_path)
    assert system.index_key(cfg, 5, tmp_path) == key
    assert system.index_key(cfg, 6, tmp_path) != key
    for other in ("src/repro/serving/m.py", "bench/loop.py"):  # not index sources
        (tmp_path / other).write_text("x = 2\n")
        assert system.index_key(cfg, 5, tmp_path) == key
    for sub in ("src/repro/core/m.py", "src/repro/data/m.py", "src/repro/index_io/m.py", "bench/gen.py", "bench/system.py"):
        f = tmp_path / sub
        f.write_text("x = 3\n")
        assert system.index_key(cfg, 5, tmp_path) != key, sub
        f.write_text("x = 1\n")
    cfg.write_text('{"n_docs": 1}')
    assert system.index_key(cfg, 5, tmp_path) != key


def test_key_follows_the_modules_a_configuration_names(tmp_path):
    _tree(tmp_path)
    cfg = tmp_path / "cfg.json"
    mods = [tmp_path / "inputs.py", tmp_path / "layout.py"]
    for m in mods:
        m.write_text("x = 1\n")
    key = system.index_key(cfg, 5, tmp_path, mods)
    assert key != system.index_key(cfg, 5, tmp_path)
    for m in mods:
        m.write_text("x = 2\n")
        assert system.index_key(cfg, 5, tmp_path, mods) != key, m.name
        m.write_text("x = 1\n")
    assert system.index_key(cfg, 5, tmp_path, mods) == key


def test_stored_index_loads_and_a_broken_one_is_rebuilt(tiny_root):
    cell = load_cell("tiny.safe", tiny_root / "BENCHMARK.json", tiny_root / "traffic")
    corpus = gen.make_corpus(cell.config, 9)
    order, ends = gen.arrange(corpus)
    lines = []
    index_dir = tiny_root / "index"
    args = (cell, corpus, order, ends, 9, REPO, index_dir, lines.append)
    built, how, _ = system.get_index(*args)
    assert how == "built" and not lines
    loaded, how, _ = system.get_index(*args)
    assert how == "loaded"
    assert np.array_equal(loaded.docs, built.docs) and np.array_equal(loaded.impacts, built.impacts)
    (stored,) = index_dir.iterdir()
    (stored / "manifest.json").write_text("not json")
    again, how, _ = system.get_index(*args)
    assert how == "built" and np.array_equal(again.impacts, built.impacts)
    assert len(lines) == 1 and "did not load" in lines[0] and "building" in lines[0]
