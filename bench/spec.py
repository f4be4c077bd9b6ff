"""What one cell is, found by name from ``BENCHMARK.json``.

A cell names a configuration (its file under ``bench/configs``) and a
traffic mix (``bench/traffic/<traffic>.json``). Every metric is read by
``bench/metrics/<metric>.py``, whose ``read(run)`` returns a number or None.
Adding a configuration, a mix, a metric or a cell means adding files and
entries, never editing one.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path

__all__ = ["BENCH", "ROOT", "Cell", "load_cell", "metric_reader"]

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    config_file: Path
    traffic: dict
    end_to_end: list  # metric entries of BENCHMARK.json this cell reports
    per_layer: list

    @property
    def guarantee(self) -> str:
        """``rank_safe`` without a budget, ``certified`` under an SLA."""
        g = self.config["guarantee"]
        return g["budgeted"] if self.traffic.get("sla_ms") is not None else g["unbudgeted"]


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, benchmark: Path | None = None, traffic_dir: Path | None = None) -> Cell:
    benchmark = Path(benchmark or ROOT / "BENCHMARK.json")
    root = benchmark.parent
    spec = json.loads(benchmark.read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in {benchmark}; have {sorted(cells)}")
    w = cells[name]
    cfg_entry = {c["name"]: c for c in spec["configs"]}[w["config"]]
    config_file = root / cfg_entry["file"]
    traffic_file = Path(traffic_dir or BENCH / "traffic") / f"{w['traffic']}.json"
    return Cell(
        name=name,
        chips=int(w["chips"]),
        config=json.loads(config_file.read_text()),
        config_file=config_file,
        traffic=json.loads(traffic_file.read_text()),
        end_to_end=[m for m in spec["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in spec["per_layer"] if _applies(m, name)],
    )


def metric_reader(name: str, metrics_dir: Path | None = None):
    """``read(run)`` of ``bench/metrics/<name>.py``."""
    path = Path(metrics_dir or BENCH / "metrics") / f"{name}.py"
    mod_spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    if mod_spec is None or mod_spec.loader is None:
        raise FileNotFoundError(f"no reader for metric {name!r} at {path}")
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read
