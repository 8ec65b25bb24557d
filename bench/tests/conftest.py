"""Puts ``bench/`` and the program's ``src/`` on the import path, and gives
the tests a test-sized cell built from ``tests/data``."""

import json
import pathlib
import sys

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
DATA = BENCH / "tests" / "data"
for p in (str(BENCH.parent / "src"), str(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)


#: what the tests' runs on the CPU take for a chip's peak rates: it sizes
#: the stream (``run.stream_steps``) and skips the look for a TPU
CPU_PEAKS = {"bf16_flops": 4e10, "int8_ops": 8e10, "hbm_bytes_per_s": 1e10}


def tiny_cell(config: str, traffic: str, chips: int = 1):
    from cell import Cell

    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    return Cell(f"{config}.{traffic}", chips,
                json.loads((DATA / f"{config}.json").read_text()),
                json.loads((DATA / f"{traffic}.json").read_text()),
                json.loads((DATA / "limits.json").read_text()),
                [m for m in bench["end_to_end"] if "workloads" not in m],
                [])


@pytest.fixture
def deepseek_tiny():
    return tiny_cell("deepseek-v2-tiny", "tiny-shuffle-fed")
