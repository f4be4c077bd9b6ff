"""What one cell is, found by name from ``BENCHMARK.json``.

A cell names a configuration (its file under ``bench/configs``) and a
traffic mix (``bench/traffic/<traffic>.json``). Every metric is read by
``bench/metrics/<metric>.py``, whose ``read(run)`` returns a number or None.
Adding a configuration, a mix, a metric or a cell means adding files and
entries, never editing one.

A configuration may name, by a path relative to ``bench/``, the modules that
decide what is served and how it is judged (``MODULES``, with their
defaults):

* ``inputs``: ``make_corpus(cfg, seed)``, ``arrange(corpus)`` giving
  ``(order, ends)``, ``make_queries(corpus, traffic, n, seed, lead_in=False)``
  and ``build_index(cfg, corpus, order, ends)``, the program's index;
* ``reference``: ``make_reference(corpus, order, ends, cfg)``, an object with
  ``check(q, answer, guarantee)``, ``recall(q, answer)`` and
  ``expected(q, answer, guarantee)`` (the answer the guarantee allows, which
  the precision control serves in the program's place);
* ``layout``: ``make_engine(index, cfg, devices)``, which returns once the
  index is on the cell's devices; ``warm(engine, cfg, queries, log)``;
  ``make_server(engine, cfg, traffic, spans, obs)``;
  ``lead_in(server, engine, queries, log)``; ``program_hlo(server)``, the
  compiled text of the served traversal; and ``PROGRAMS``, the jitted
  programs whose device time is the traversal's, the first of them
  dispatched once a server step.

Arrival schedules are the traffic's (``gen.make_schedule``) whatever the
configuration names.
"""

from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
from pathlib import Path
from types import ModuleType

__all__ = ["BENCH", "MODULES", "ROOT", "Cell", "load_cell", "metric_reader", "resolve_module"]

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
MODULES = {"inputs": "gen.py", "reference": "reference.py", "layout": "layouts/single.py"}


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    config_file: Path
    traffic: dict
    end_to_end: list  # metric entries of BENCHMARK.json this cell reports
    per_layer: list
    inputs: ModuleType
    reference: ModuleType
    layout: ModuleType

    @property
    def module_files(self) -> tuple[str, ...]:
        """Files of the cell's inputs, reference and layout modules."""
        return tuple(m.__file__ for m in (self.inputs, self.reference, self.layout))

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
    config = json.loads(config_file.read_text())
    return Cell(
        name=name,
        chips=int(w["chips"]),
        config=config,
        config_file=config_file,
        traffic=json.loads(traffic_file.read_text()),
        end_to_end=[m for m in spec["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in spec["per_layer"] if _applies(m, name)],
        **{key: resolve_module(config.get(key, default)) for key, default in MODULES.items()},
    )


def resolve_module(rel: str) -> ModuleType:
    """The module at ``bench/<rel>``, imported as part of the package."""
    path = (BENCH / rel).resolve()
    if path.suffix != ".py" or BENCH not in path.parents or not path.is_file():
        raise FileNotFoundError(f"no module {rel!r} of the benchmark: {path} is not a .py file under {BENCH}")
    parts = path.relative_to(BENCH).with_suffix("").parts
    return importlib.import_module(".".join(("bench", *parts)))


def metric_reader(name: str, metrics_dir: Path | None = None):
    """``read(run)`` of ``bench/metrics/<name>.py``."""
    path = Path(metrics_dir or BENCH / "metrics") / f"{name}.py"
    mod_spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    if mod_spec is None or mod_spec.loader is None:
        raise FileNotFoundError(f"no reader for metric {name!r} at {path}")
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read
