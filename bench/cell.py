"""What one cell of ``BENCHMARK.json`` runs, gathered from its data files.

A cell names a configuration and a traffic mix. The configuration's file
is the one ``BENCHMARK.json`` gives; the traffic mix is
``bench/traffic/<traffic>.json``; the limits of the comparison that
decides ``correct`` are ``bench/limits/<cell>.json``. The model type named
in the configuration (``model_type``) picks ``program/<type>.py``,
``reference/<type>.py`` and ``flops/<type>.py``; a per-layer metric is
read by ``metrics/<metric>.py``. Adding a cell, a configuration or a
metric therefore adds files and entries, and edits none.
"""

from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import pathlib
from typing import Any, Dict, List

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent


class BenchError(RuntimeError):
    """The run cannot measure what the cell asks for."""


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]          # the configuration file, as run
    traffic: Dict[str, Any]
    limits: Dict[str, float]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]

    @property
    def model_type(self) -> str:
        return self.config["model_type"]

    def module(self, kind: str):
        """``program``, ``reference`` or ``flops`` module of the model type."""
        return importlib.import_module(f"{kind}.{self.model_type}")


#: the keys of a traffic file, each read by ``run.py``: a key it does not
#: know would be ignored, so it is refused
TRAFFIC_KEYS = {"about", "batch", "seq_len", "mesh", "moe_mode",
                "expert_axes", "capacity_factor", "engine", "pipeline",
                "opt", "warm_steps", "ahead_steps", "compared_steps",
                "trace_steps"}


def _read_json(path: pathlib.Path):
    try:
        return json.loads(path.read_text())
    except FileNotFoundError as e:
        raise BenchError(f"missing {path}") from e


def load_cell(name: str, root: pathlib.Path = ROOT,
              limits_dir: pathlib.Path = BENCH / "limits") -> Cell:
    bench = _read_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = _read_json(root / configs[w["config"]]["file"])
    traffic = _read_json(BENCH / "traffic" / f"{w['traffic']}.json")
    if set(traffic) != TRAFFIC_KEYS:
        raise BenchError(f"traffic {w['traffic']!r}: keys "
                         f"{sorted(set(traffic) ^ TRAFFIC_KEYS)} are unknown "
                         "or missing")
    limits = _read_json(limits_dir / f"{name}.json")

    def applies(m) -> bool:
        return "workloads" not in m or name in m["workloads"]
    e2e = [m for m in bench["end_to_end"] if applies(m)]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if applies(m) and m["moves"] in reported]
    return Cell(name, int(w["chips"]), config, traffic, limits, e2e,
                per_layer)


def metric_reader(name: str):
    """``read(ctx)`` of ``metrics/<name>.py``."""
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "metric_" + name.replace(".", "_").replace("-", "_"), path)
    if spec is None:
        raise BenchError(f"no reader {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def peaks_of(device_kind: str) -> Dict[str, float]:
    """Peak rates of one chip of this kind, from ``peaks.json``. A kind that
    is not in the table is an error, not a default."""
    table = _read_json(BENCH / "peaks.json")
    if device_kind not in table["devices"]:
        raise BenchError(f"device kind {device_kind!r} is not in "
                         "bench/peaks.json")
    return table["devices"][device_kind]
