#!/usr/bin/env python3
"""Run one benchmark cell once and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell's configuration, traffic and limits come from ``BENCHMARK.json``
and the files under ``bench/`` that it names.  With ``--trace 0`` the
result line holds the cell's end-to-end metrics, with ``--trace 1`` its
per-layer metrics read from a profiler trace of the window.  The last line
of standard output is the result as one JSON object; the numbers compared
for ``correct`` are also the last lines of standard error.  Without a TPU,
or with fewer chips than the cell needs, it exits non-zero and prints no
result.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="copy the raw trace to this directory")
    args = ap.parse_args(argv)

    from bench import cells

    cell = cells.load_cell(args.workload)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"bench: needs a TPU; JAX found {devices[0].platform}",
              file=sys.stderr)
        return 2
    if len(devices) < cell.chips:
        print(f"bench: {cell.name} needs {cell.chips} chips, JAX sees "
              f"{len(devices)}", file=sys.stderr)
        return 2

    from bench import harness

    out = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                      devices, keep_trace=args.keep_trace)
    line = out["line"]
    print(json.dumps({"info": out["info"]}), file=sys.stderr)
    for name, c in line["checks"].items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
