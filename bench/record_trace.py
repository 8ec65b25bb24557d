"""Record a traced run of a cell with what ``program_trace.py`` reads.

    python bench/record_trace.py --workload <cell> --seed <n> --seconds <s> \
        --out <dir>

Runs the cell as ``run.py --trace 1`` does, prints the same result line,
and keeps in ``<dir>``:

- ``<cell>.xplane.pb.gz``: the profile of the traced steps, gzipped;
- ``<cell>.layers.json``: {instruction: layer} of the compiled step
  (``repro.obs.scopes.layer_of_ops``) for the operations the traced
  window ran;
- ``<cell>.json``: the result line, the traced steps' dropped units, the
  units one step routes, and the numbers of ``program_trace.metrics``.

The step's HLO text is taken when it compiles, in the warm-up; the traced
steps' ``dropped_units`` are kept as device arrays and read once the run
has ended, so nothing is read back inside the window.
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
    import jax

    import program_trace
    import trace_reduce
    from repro.obs.scopes import layer_of_ops

    out.mkdir(parents=True, exist_ok=True)
    kept = {"dropped": []}
    profile = out / f"{cell.name}.xplane.pb.gz"

    class RecordingStep(run.TimedStep):
        def __call__(self, params, opt, batch):
            res = super().__call__(params, opt, batch)
            if "hlo" not in kept:
                kept["hlo"] = self.compiled.as_text()
            if self.traced and self.traced[0] <= self.n - 1 <= self.traced[1]:
                kept["dropped"].append(res[2]["dropped_units"])
            return res

    def read_trace(cell, step, peaks, chips):
        found = trace_reduce.find_profile(run.TRACE_DIR)
        profile.write_bytes(gzip.compress(pathlib.Path(found).read_bytes()))
        kept["steps"] = step.traced[1] - step.traced[0] + 1
        return plain_read_trace(cell, step, peaks, chips)

    plain_step, plain_read_trace = run.TimedStep, run.read_trace
    run.TimedStep, run.read_trace = RecordingStep, read_trace
    try:
        result = run.run_cell(cell, seed, seconds, True, peaks=peaks)
    finally:
        run.TimedStep, run.read_trace = plain_step, plain_read_trace

    data = jax.profiler.ProfileData.from_serialized_xspace(
        gzip.decompress(profile.read_bytes()))
    red = trace_reduce.reduce_profile(data)
    ran = {program_trace.instruction(n) for d in red.devices
           for n, _, _ in red.ops[d]}
    layer_of = {k: v for k, v in layer_of_ops(kept["hlo"]).items()
                if k in ran}
    (out / f"{cell.name}.layers.json").write_text(
        json.dumps(layer_of, indent=0, sort_keys=True) + "\n")
    model_cfg = run.program_config(cell)[0]
    t = cell.traffic
    routed = (t["batch"] * t["seq_len"] * model_cfg.moe.top_k
              * (model_cfg.num_layers - model_cfg.moe.first_dense_layers))
    dropped = [int(v) for v in jax.device_get(kept["dropped"])]
    info = {"result": result, "dropped_units": dropped,
            "routed_units_per_step": routed,
            "program": program_trace.metrics(
                red, program_trace.program_spans(data), layer_of,
                kept["steps"], dropped, routed)}
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
