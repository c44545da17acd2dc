"""Find a cell's configuration, traffic mix and metric readers by name.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric sits in files of its own, so a new cell is added by
adding files and entries, never by editing one that is there:

* ``BENCHMARK.json`` at the root: the cells, configurations and metrics;
* a configuration: its ``file`` from ``BENCHMARK.json`` (sizes, runtime
  settings, source), with two modules beside it under the same stem:
  ``<stem>.py`` builds the deployment for the program (tables, programs),
  ``<stem>.reference.py`` holds its data generator and plain reference;
* a traffic mix: ``bench/traffic/<traffic>.json``, read by the one
  generator in :mod:`bench.loadgen`;
* a per-layer metric: ``bench/metrics/<metric name>.py``, whose
  ``read(run)`` returns a number, or None where it finds nothing to read;
  a reader of a program counter also defines ``snapshot(rt)``, which the
  harness calls on the ``ServingRuntime`` just before and just after the
  window (``run.before``, ``run.after``, ``run.delta(key)``).
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import sys
from pathlib import Path
from types import ModuleType
from typing import Dict, List, Optional

ROOT = Path(__file__).resolve().parents[1]


@dataclasses.dataclass(frozen=True)
class Metric:
    name: str
    unit: str
    better: str
    source: str
    layer: Optional[str] = None
    moves: Optional[str] = None
    workloads: Optional[tuple] = None

    def applies_to(self, cell: str) -> bool:
        return self.workloads is None or cell in self.workloads


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    config: str
    traffic: str
    chips: int


class Benchmark:
    """``BENCHMARK.json`` as read from ``root``."""

    def __init__(self, root: Path = ROOT):
        self.root = Path(root)
        spec = json.loads((self.root / "BENCHMARK.json").read_text())
        self.cells: Dict[str, Cell] = {
            w["name"]: Cell(w["name"], w["config"], w["traffic"],
                            int(w["chips"]))
            for w in spec["workloads"]}
        self.configs: Dict[str, dict] = {c["name"]: c
                                         for c in spec["configs"]}
        self.end_to_end = [_metric(m) for m in spec["end_to_end"]]
        self.per_layer = [_metric(m) for m in spec["per_layer"]]

    def cell(self, name: str) -> Cell:
        try:
            return self.cells[name]
        except KeyError:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                           f"known: {sorted(self.cells)}") from None

    def metrics_for(self, cell: str, trace: bool) -> List[Metric]:
        """The cell's end-to-end metrics (``trace`` off) or its per-layer
        metrics (``trace`` on)."""
        pool = self.per_layer if trace else self.end_to_end
        return [m for m in pool if m.applies_to(cell)]

    def config_path(self, name: str) -> Path:
        return self.root / self.configs[name]["file"]

    def load_config(self, name: str) -> "Config":
        path = self.config_path(name)
        return Config(name, json.loads(path.read_text()),
                      load_module(path.with_suffix(".py")),
                      load_module(path.with_name(path.stem + ".reference.py")))

    def load_traffic(self, name: str) -> dict:
        return json.loads(
            (self.root / "bench" / "traffic" / f"{name}.json").read_text())

    def load_reader(self, metric: str) -> ModuleType:
        return load_module(self.root / "bench" / "metrics" / f"{metric}.py")


@dataclasses.dataclass
class Config:
    """One deployment: ``data`` is its JSON file, ``module`` the code beside
    it that builds it for the program (``build_db``, ``programs``), and
    ``reference`` its data generator and plain reference (``generate``,
    ``reference``, ``control``, ``answer``), which import nothing of the
    program."""

    name: str
    data: dict
    module: ModuleType
    reference: ModuleType
    columns: Optional[dict] = None      # the run's data, from ``generate``

    @property
    def sizes(self) -> dict:
        return self.data["sizes"]


def _metric(m: dict) -> Metric:
    wl = m.get("workloads")
    return Metric(m["name"], m["unit"], m["better"], m["source"],
                  m.get("layer"), m.get("moves"),
                  tuple(wl) if wl is not None else None)


def load_module(path: Path) -> ModuleType:
    """Import a file by path (its name may hold ``-`` and ``.``). The module
    is registered in ``sys.modules`` so that ``inspect`` finds its source,
    which the program lifter reads."""
    path = Path(path).resolve()
    key = "bench_file_" + "".join(c if c.isalnum() else "_"
                                  for c in str(path))
    mod = sys.modules.get(key)
    if mod is not None:
        return mod
    spec = importlib.util.spec_from_file_location(key, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[key] = mod
    try:
        spec.loader.exec_module(mod)
    except BaseException:
        del sys.modules[key]
        raise
    return mod
