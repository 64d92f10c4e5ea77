"""Finds everything a cell needs by the names in ``BENCHMARK.json``:

* configuration ``<name>``: the JSON file its entry names;
* traffic mix ``<name>``: ``traffic/<name>.json``;
* per-layer metric ``<name>``: the reader ``metrics/<name>.py``, whose
  ``read(run)`` returns a number or None (nothing to read);
* reference ``<name>`` (named by the configuration file):
  ``reference/<name>.py``.

A later configuration, mix or metric is a new file and a new entry:
nothing here needs an edit.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from typing import Dict, List, Optional

HERE = Path(__file__).resolve().parent.parent          # benchmarks/chip


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict                  # the configuration file's contents
    config_name: str
    traffic: dict                 # the mix file's contents
    traffic_name: str
    end_to_end: List[dict]        # the metrics this cell reports
    per_layer: List[dict]
    base: Path                    # benchmark directory (configs/, ...)


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not path.is_file():
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def cell(bench: dict, workload: str, root: Path,
         base: Optional[Path] = None) -> Cell:
    """The cell ``workload`` of a parsed ``BENCHMARK.json`` whose paths
    are relative to ``root``."""
    base = base or HERE
    w = next((w for w in bench["workloads"] if w["name"] == workload),
             None)
    if w is None:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    c = next(c for c in bench["configs"] if c["name"] == w["config"])
    config = json.loads((root / c["file"]).read_text())
    traffic = json.loads((base / "traffic" / f"{w['traffic']}.json")
                         .read_text())
    return Cell(
        name=workload, chips=int(w["chips"]), config=config,
        config_name=c["name"], traffic=traffic,
        traffic_name=w["traffic"],
        end_to_end=[m for m in bench["end_to_end"]
                    if _applies(m, workload)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, workload)],
        base=base)


def readers_for(c: Cell, metrics: List[dict]) -> Dict[str, object]:
    """metric name -> the ``read`` function of ``metrics/<name>.py``."""
    return {m["name"]: load_module(c.base / "metrics" / f"{m['name']}.py",
                                   f"chipbench_metric_{m['name']}").read
            for m in metrics}


def reference(c: Cell):
    name = c.config["reference"]
    return load_module(c.base / "reference" / f"{name}.py",
                       f"chipbench_reference_{name}")
