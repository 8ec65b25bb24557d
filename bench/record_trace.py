"""Record a traced run of a cell with what ``program_trace.py`` reads.

    python bench/record_trace.py --workload <cell> --seed <n> --seconds <s> \
        --out <dir>

Runs the cell as ``run.py --trace 1`` does, prints the same result line,
and keeps in ``<dir>``:

- ``<cell>.xplane.pb.gz``: the profile of the traced steps, gzipped;
- ``<cell>.layers.json``: {instruction: layer} of the compiled step
  (``program_trace.layer_of_ops``) for the operations the traced
  window ran;
- ``<cell>.json``: the result line, the traced steps' dropped units, the
  units one step routes, and the numbers of ``program_trace.metrics``.

What it keeps is what ``run.py --trace 1`` keeps for its readers
(``run.read_trace``): the step's HLO text is taken after the window, and
the traced steps' ``dropped_units`` are kept as device arrays and read
once the run has ended, so nothing is read back inside the window.
"""

from __future__ import annotations

import argparse
import gzip
import json
import pathlib
import sys

import run
from cell import BenchError, load_cell


def record(cell, seed: int, seconds: float, out: pathlib.Path, *,
           peaks=None) -> dict:
    """One traced run of ``cell``; writes the three files and returns what
    ``<cell>.json`` holds."""
    out.mkdir(parents=True, exist_ok=True)
    kept = {}
    result = run.run_once(cell, seed, seconds, True, peaks=peaks,
                          keep=kept)[0]
    ctx = kept["ctx"]
    (out / f"{cell.name}.xplane.pb.gz").write_bytes(
        gzip.compress(kept["profile"]))
    (out / f"{cell.name}.layers.json").write_text(
        json.dumps(ctx["layer_of"], indent=0, sort_keys=True) + "\n")
    info = {"result": result, "dropped_units": ctx["dropped"],
            "routed_units_per_step": ctx["routed_units"],
            "program": ctx["program"]}
    (out / f"{cell.name}.json").write_text(json.dumps(info, indent=1) + "\n")
    return info


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", type=pathlib.Path, required=True)
    args = ap.parse_args(argv)
    try:
        info = record(load_cell(args.workload), args.seed, args.seconds,
                      args.out)
    except BenchError as e:
        run.log(f"bench: {e}")
        return 2
    run.log(json.dumps(info["program"]))
    print(json.dumps(info["result"]), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
