"""A configuration names its inputs, reference and layout; the harness finds them."""

import json
import re

import pytest

from bench import gen, reference, run
from bench.layouts import single
from bench.spec import BENCH, MODULES, load_cell
from bench.tests.conftest import OWN_MODULES, cpu_devices
from bench.tests.data import fixture_inputs, fixture_layout, fixture_reference

SEED = 2**31 + 91


@pytest.mark.parametrize("name", ["web-isn.safe", "passage-isn.safe"])
def test_a_configuration_that_names_nothing_gets_the_defaults(name):
    cell = load_cell(name)
    assert not set(MODULES) & set(cell.config)
    assert (cell.inputs, cell.reference, cell.layout) == (gen, reference, single)
    assert cell.module_files == tuple(str(BENCH / MODULES[k]) for k in ("inputs", "reference", "layout"))


def test_a_cell_is_served_through_the_modules_its_configuration_names(tiny_root):
    cell = load_cell("tinyown.safe", tiny_root / "BENCHMARK.json", tiny_root / "traffic")
    assert (cell.inputs, cell.reference, cell.layout) == (fixture_inputs, fixture_reference, fixture_layout)
    assert cell.module_files == tuple(str(BENCH / OWN_MODULES[k]) for k in ("inputs", "reference", "layout"))
    for mod in (fixture_inputs, fixture_reference, fixture_layout):
        mod.CALLS.clear()
    lines = []
    out = run.run_cell(cell, SEED, 2.0, True, cpu_devices, tiny_root / ".cache", 60.0, log=lines.append)
    assert out["correct"] and out["attempted"] > 0, out["checks"]
    assert set(fixture_inputs.CALLS) == {"make_corpus", "arrange", "make_queries", "build_index"}
    assert set(fixture_layout.CALLS) == {"make_engine", "warm", "make_server", "lead_in", "program_hlo"}
    assert fixture_reference.CALLS[0] == "make_reference"
    assert fixture_reference.CALLS.count("check") == out["attempted"]
    # The named arrangement cuts each topic's range in two: the program's
    # index was built from it, and the reference agreed with what it served.
    (index_line,) = [x for x in lines if x.startswith("index:")]
    n_topics = int(cell.config["n_topics"])
    assert int(re.search(r"(\d+) ranges", index_line).group(1)) == 2 * n_topics


@pytest.mark.parametrize("key", sorted(MODULES))
@pytest.mark.parametrize("path", ["layouts/absent.py", "../README.md", "../src/repro/__init__.py"])
def test_a_configuration_naming_no_module_of_the_benchmark_fails_at_load(tiny_root, key, path):
    cfg_file = tiny_root / "configs/tiny.json"
    cfg_file.write_text(json.dumps(dict(json.loads(cfg_file.read_text()), **{key: path})))
    with pytest.raises(FileNotFoundError, match=re.escape(str((BENCH / path).resolve()))):
        load_cell("tiny.safe", tiny_root / "BENCHMARK.json", tiny_root / "traffic")

