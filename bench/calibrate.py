#!/usr/bin/env python3
"""Read a cell's compared numbers over many seeds, with the control.

    python bench/calibrate.py --workload <cell> --seeds 1,2,3 \\
        [--control-seeds 1,2,3] [--witness-seeds 1] [--seconds 8]
        [--set plan_stream.period_s=2] [--set correct.sample_batches=8]
        [--point "plan_stream.period_s=4;plan_stream.clients=8" ...]
        [--out chiprun_out/calibrate.jsonl]

Runs the cell in one process once per seed (the programs compile once),
and prints one JSON line per seed: the program's numbers (``checks``,
and every number a cell may compare under ``info.gaps``); for control
seeds the control's (the reference in float8-e4m3 in the program's
place, the daemon answering each family's previous plan) judged against
the cell's limits (``info.control_correct``); for witness seeds the
reference's own in bfloat16, the served precision.  ``--set`` overrides
a traffic parameter, with ``correct.`` one of the cell's correctness
entries, or with ``config.`` an entry of its configuration (such as
``config.model.compute_dtype="float32"``, served beside
``JAX_DEFAULT_MATMUL_PRECISION=highest``: the program itself as a
witness that sides with the reference); each ``--point`` is a further set of overrides under which
every seed runs again (a sweep, for example of the plan stream's period
to find the rate the daemon sustains).  The limits in
``bench/workloads/<cell>.json`` are set from these readings: above the
largest the program gives, below the smallest the control gives.  The
benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def _set(cell, assignment: str) -> None:
    key, value = assignment.split("=", 1)
    *path, last = key.split(".")
    node = cell.traffic
    if path and path[0] in ("correct", "config"):
        node, path = getattr(cell, path[0]), path[1:]
    for part in path:
        node = node[part]
    node[last] = json.loads(value)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--witness-seeds", default="")
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--set", action="append", default=[])
    ap.add_argument("--point", action="append", default=[])
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    import jax

    from bench import cells, harness
    from bench.compile_log import CompileLog
    from bench.reference.check import CONTROL, WITNESS

    devices = jax.devices()
    chips = cells.load_cell(args.workload).chips
    if devices[0].platform != "tpu" or len(devices) < chips:
        print("calibrate: needs the cell's TPU chips", file=sys.stderr)
        return 2
    compiles = CompileLog()
    control = {int(s) for s in args.control_seeds.split(",") if s}
    witness = {int(s) for s in args.witness_seeds.split(",") if s}
    points = [p.split(";") for p in args.point] or [[]]
    for point in points:
        for seed in (int(s) for s in args.seeds.split(",")):
            cell = cells.load_cell(args.workload)
            for assignment in args.set + point:
                _set(cell, assignment)
            lows = (CONTROL,) * (seed in control) + (WITNESS,) * (
                seed in witness)
            out = harness.run(cell, seed, args.seconds, False, devices,
                              lows=lows, compiles=compiles)
            _report(args.out, seed, seed in control, point, out)
    return 0


def _report(path, seed, control, point, out) -> None:
    line = out["line"]
    text = json.dumps({
        "seed": seed, "control": control, "point": point,
        "checks": {k: v["value"] for k, v in line["checks"].items()},
        "metrics": {k: v["value"] for k, v in line["metrics"].items()},
        "attempted": line["attempted"], "failed": line["failed"],
        "memory_peak_bytes": line["device"]["memory_peak_bytes"],
        "info": out["info"]})
    print(text, flush=True)
    if path:
        with open(path, "a") as f:
            f.write(text + "\n")


if __name__ == "__main__":
    sys.exit(main())
