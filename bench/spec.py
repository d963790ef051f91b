"""The benchmark's data: ``BENCHMARK.json`` and the files it names.

Every configuration, traffic mix and metric is found by its name, so a
new cell needs only new files under ``bench/`` and new entries in
``BENCHMARK.json``:

* ``bench/configs/<config>.json``: the model as run (``model``, the
  ``ModelConfig`` fields), the engine (``serve``, ``ServeConfig``
  fields), the published keys it came from, ``source``, ``reduced``,
  ``assumed``, ``departures``, ``deployment``, ``reference`` (a module
  under ``bench/reference/``) and the correctness limits (``check``);
* ``bench/traffic/<traffic>.json``: the parameters that
  :mod:`bench.traffic` turns into requests;
* ``bench/metrics/<metric>.py``: a reader ``read(record)`` from the run's
  record to one number, or None where it finds nothing to read.

A configuration of a model family the benchmark has not run before
brings, besides its configuration file, two more files, and needs no
edit to any file here:

* ``bench/families/<family>.py``, found by ``model.family``:
  ``program_params(seed, m)``, the program's parameter tree in bf16,
  made on the device from the seed; ``decode_call(m, live)``, the
  (flops, bytes) one decode call needs, ``live`` listing each live
  slot's context; ``prefill_call(m, pos0, n)``, the flops of ``n``
  prompt tokens after ``pos0`` cached ones;
* ``bench/reference/<reference>.py``, named by the configuration's
  ``reference``: ``gaps(seed, m, seqs, precision)`` as
  :mod:`bench.reference.dense` has it, computed in float32 from leaves
  remade through the family's module, with ``precision="fp8"`` the
  control.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
from typing import Callable, Dict, List, Optional

ROOT = pathlib.Path(__file__).resolve().parents[1]


@dataclasses.dataclass(frozen=True)
class Metric:
    """One metric entry of ``BENCHMARK.json``."""

    name: str
    unit: str
    better: str
    source: str
    kind: str                        # "end_to_end" | "per_layer"
    workloads: Optional[tuple]       # None: every cell that reports `moves`
    moves: Optional[str] = None

    def applies(self, cell: str, reported: List[str]) -> bool:
        """True where ``cell`` reports this metric; ``reported`` lists
        the cell's end-to-end metrics (for a per-layer metric with no
        ``workloads`` key)."""
        if self.workloads is not None:
            return cell in self.workloads
        if self.kind == "end_to_end":
            return True
        return self.moves in reported


@dataclasses.dataclass(frozen=True)
class Cell:
    """One workload of ``BENCHMARK.json`` with its files loaded."""

    name: str
    chips: int
    config_name: str
    traffic_name: str
    config: Dict
    traffic: Dict
    metrics: List[Metric]            # end_to_end then per_layer, in order
    root: pathlib.Path

    def reported(self, kind: str) -> List[Metric]:
        """This cell's metrics of one kind ("end_to_end"/"per_layer")."""
        e2e = [m.name for m in self.metrics
               if m.kind == "end_to_end" and m.applies(self.name, [])]
        return [m for m in self.metrics
                if m.kind == kind and m.applies(self.name, e2e)]


def _load_json(path: pathlib.Path) -> Dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, root: pathlib.Path = ROOT) -> Cell:
    """The cell ``name`` of ``<root>/BENCHMARK.json`` and its files."""
    bench = _load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[name]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    metrics = []
    for kind in ("end_to_end", "per_layer"):
        for m in bench[kind]:
            wl = m.get("workloads")
            metrics.append(Metric(m["name"], m["unit"], m["better"],
                                  m["source"], kind,
                                  tuple(wl) if wl is not None else None,
                                  m.get("moves")))
    return Cell(name=name, chips=int(w["chips"]), config_name=w["config"],
                traffic_name=w["traffic"],
                config=_load_json(root / cfg_entry["file"]),
                traffic=_load_json(root / "bench" / "traffic"
                                   / f"{w['traffic']}.json"),
                metrics=metrics, root=root)


def _load_module(path: pathlib.Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(root: pathlib.Path, name: str) -> Callable:
    """``read(record)`` of ``<root>/bench/metrics/<name>.py``."""
    path = root / "bench" / "metrics" / f"{name}.py"
    return _load_module(path, "bench_metric_" + name.replace(".", "_")
                        .replace("-", "_")).read


def family_module(root: pathlib.Path, family: str):
    """The weights and costs of a model family,
    ``<root>/bench/families/<family>.py``. A family without one stops
    here, naming the missing file: no other family's tree stands in."""
    path = root / "bench" / "families" / f"{family}.py"
    if not path.is_file():
        raise FileNotFoundError(
            f"no module for the model family {family!r}: {path} is missing "
            f"(it gives program_params, decode_call and prefill_call)")
    return _load_module(path, "bench_family_" + family.replace("-", "_"))


def reference_module(root: pathlib.Path, name: str):
    """The plain reference ``<root>/bench/reference/<name>.py``."""
    return _load_module(root / "bench" / "reference" / f"{name}.py",
                        "bench_reference_" + name)


def model_config(config: Dict):
    """The ``ModelConfig`` a configuration file runs."""
    from repro.configs.base import ModelConfig
    m = dict(config["model"])
    return ModelConfig(arch_id=m.pop("arch_id"), **m)


def serve_config(config: Dict, seed: int):
    """The ``ServeConfig`` a configuration file runs (seeded)."""
    from repro.serving.config import ServeConfig
    return ServeConfig(seed=seed % (2 ** 31), **config["serve"])
