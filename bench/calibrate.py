"""Readings that the limits of ``correct`` are set from (not part of a run).

    python bench/calibrate.py --workload <cell> --seeds 11,12,13 \
        --planted 11,12,13 --seconds 3

In one process, for each seed of ``--seeds``, a run of the cell with a
short window (``run.run_once``): the program's readings of every number
compared, against the plain float32 reference. For each seed of
``--planted`` also, against the same reference steps:

- ``control``: the reference with every matmul operand rounded to float8
  e4m3 (cotangents to e5m2), the precision below the configuration's
  bfloat16 compute, put in the program's place;
- ``half_batch``: the reference on the first half of each batch, the mean
  taken over it (half of the batch left out).

A state left unchanged reads 1 on ``grad_diff`` and ``change_diff`` (and
on the norm gaps) by their definition and needs no run. Prints one JSON
line per seed. Needs the chips the cell asks for; the reference runs on
the first.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

BENCH = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH.parent / "src"), str(BENCH)]

import run  # noqa: E402
from cell import load_cell  # noqa: E402


def planted(cell, seed: int, ref, device) -> dict:
    """The control's and the half-batch fault's numbers against ``ref``."""
    t = cell.traffic
    groups = run.capacity_groups(t)
    out = {}
    for name, kw in (("control", {"fp8": True}),
                     ("half_batch", {"drop_rows": t["batch"] // 2})):
        t0 = time.perf_counter()
        x = run.reference_steps(cell, seed, groups, device, **kw)
        values, worst = run.gaps(x, ref, cell.limits["still_leaf_share"])
        out[name] = {**values, "seconds": time.perf_counter() - t0,
                     "worst_leaves": worst}
        del x
    return out


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--planted", default="")
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args()
    import jax

    cell = load_cell(args.workload)
    with_planted = {int(s) for s in args.planted.split(",") if s}
    for seed in (int(s) for s in args.seeds.split(",")):
        result, ref = run.run_once(cell, seed, args.seconds, False)
        line = {"workload": args.workload, "seed": seed,
                "correct": result["correct"],
                "program": {k: c["value"] for k, c in
                            result["checks"].items()},
                "notes": result["notes"]}
        if seed in with_planted:
            line.update(planted(cell, seed, ref, jax.devices()[0]))
        del ref, result
        print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
