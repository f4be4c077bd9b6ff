"""Tiny cells for CPU tests: the harness at a size a test run holds.

The fixtures write a BENCHMARK.json, a configuration and traffic mixes into
a temporary root; the harness reads them by name as it reads the real ones.
"""

import json
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(REPO), str(REPO / "src")]

TINY = {
    "n_docs": 3000,
    "n_terms": 3000,
    "n_topics": 8,
    "mean_doc_len": 60,
    "nnz_loc": 150000,
}


def tiny_config(k=10):
    cfg = json.loads((REPO / "bench/configs/web-isn.json").read_text())
    cfg.update(TINY, k=k)
    return cfg


# A budgeted, bursty mix: 4 s at half the mean rate, 1 s at three times it,
# under an SLA with the paper's Reactive policy.
SLA = {"sla_ms": 254.9, "phases": [[4.0, 0.5], [1.0, 3.0]], "policy": {"alpha": 1.0, "beta": 1.2, "q": 0.01}}


def tiny_traffic(rate=20.0, sla=False):
    t = json.loads((REPO / "bench/traffic/web-safe.json").read_text())
    t.update(rate_qps=rate, df_min=5)
    if sla:
        t.update(SLA)
    return t


# The modules a configuration may name, as test fixtures that record their calls.
OWN_MODULES = {
    "inputs": "tests/data/fixture_inputs.py",
    "reference": "tests/data/fixture_reference.py",
    "layout": "tests/data/fixture_layout.py",
}


@pytest.fixture
def tiny_root(tmp_path):
    """A root with cells ``tiny.safe``, ``tiny.sla``, ``tinyk.safe`` (k=100) and
    ``tinyown.safe``, whose configuration names its own modules (``OWN_MODULES``)."""
    (tmp_path / "configs").mkdir()
    (tmp_path / "traffic").mkdir()
    (tmp_path / "configs/tiny.json").write_text(json.dumps(dict(tiny_config(), name="tiny")))
    (tmp_path / "configs/tinyk.json").write_text(json.dumps(dict(tiny_config(k=100), name="tinyk")))
    own = dict(tiny_config(), name="tinyown", **OWN_MODULES)
    (tmp_path / "configs/tinyown.json").write_text(json.dumps(own))
    (tmp_path / "traffic/safe.json").write_text(json.dumps(tiny_traffic()))
    (tmp_path / "traffic/sla.json").write_text(json.dumps(tiny_traffic(sla=True)))
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    spec["configs"] = [
        {"name": n, "source": "test", "file": f"configs/{n}.json", "reduced": [], "why": "test"}
        for n in ("tiny", "tinyk", "tinyown")
    ]
    spec["workloads"] = [
        {"name": "tiny.safe", "config": "tiny", "traffic": "safe", "chips": 1, "why": "test"},
        {"name": "tiny.sla", "config": "tiny", "traffic": "sla", "chips": 1, "why": "test"},
        {"name": "tinyk.safe", "config": "tinyk", "traffic": "safe", "chips": 1, "why": "test"},
        {"name": "tinyown.safe", "config": "tinyown", "traffic": "safe", "chips": 1, "why": "test"},
    ]
    as_tiny = {"web-isn.safe": "tiny.safe", "passage-isn.safe": "tinyk.safe"}
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [as_tiny[w] for w in m["workloads"]]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    return tmp_path


def cpu_devices(n):
    import jax

    return jax.devices()[:n]
