"""Every cell of BENCHMARK.json resolves to its data files and readers,
and each configuration's reference and program agree on the parameters."""

import json
import pathlib

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())


@pytest.mark.parametrize("name", [w["name"] for w in BENCH["workloads"]])
def test_cell_resolves(name):
    import run
    from cell import load_cell, metric_reader

    cell = load_cell(name)
    for kind in ("program", "reference", "flops"):
        assert cell.module(kind) is not None
    for m in cell.per_layer:
        assert callable(metric_reader(m["name"]))
    assert set(run.GAPS) | {"still_leaf_share"} == set(cell.limits)
    assert cell.end_to_end and cell.per_layer


@pytest.mark.parametrize("config", [c["name"] for c in BENCH["configs"]])
def test_reference_layout_is_the_programs(config):
    import jax

    from cell import load_cell
    from reference.common import is_leaf
    from repro.models import lm
    from repro.models.common import is_spec

    cell = next(load_cell(w["name"]) for w in BENCH["workloads"]
                if w["config"] == config)
    defs = lm.param_defs(cell.module("program").model_config(
        cell.config, cell.traffic["capacity_factor"]))
    layout = cell.module("reference").layout(cell.config)
    prog, _ = jax.tree_util.tree_flatten_with_path(defs, is_leaf=is_spec)
    ref, _ = jax.tree_util.tree_flatten_with_path(layout, is_leaf=is_leaf)
    assert [(jax.tree_util.keystr(p), tuple(s.shape)) for p, s in prog] == \
        [(jax.tree_util.keystr(p), tuple(s.shape)) for p, s in ref]
    assert sum(s.size for _, s in prog) == cell.config["parameters"]


def test_cpu_run_prints_no_result():
    import os
    import subprocess
    import sys

    out = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload",
         BENCH["workloads"][0]["name"], "--seed", "2147483901",
         "--seconds", "1", "--trace", "0"],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=300, cwd=ROOT)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
