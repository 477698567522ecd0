"""Benchmark harness: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV and writes a machine-readable
``BENCH_*.json`` snapshot (``--json PATH``, default ``BENCH_latest.json``)
so successive runs accumulate a perf trajectory.  Modules:
  fig12  AlgoBW vs transfer size (balanced/random/skewed) vs 4 baselines
  fig13  skew sweep + FLASH phase breakdown
  fig14  MoE end-to-end training speedup (EP degree, top-k)
  fig15  scale sweep (servers, GPUs/server)
  fig16  intra-server topology + bandwidth-ratio sweep
  fig17  scheduler synthesis time + memory overhead slope
  hetero heterogeneous fabrics: degraded/failed/mixed NICs, oversubscription
  dynamic  drifting-MoE serving loop: cache + warm start + compiled executor
  serving  closed-loop concurrent load on the plan-serving daemon
  fault    mid-run NIC failure: fabric events, re-repair, bounded slowdown
  roofline  per-(arch x shape x mesh) terms from the dry-run sweep
"""

from __future__ import annotations

import argparse

from repro.launch.compile_cache import enable_compile_cache

from . import (
    fig12_algbw,
    fig13_skew,
    fig14_moe_e2e,
    fig15_scale,
    fig16_topo,
    fig17_overhead,
    fig_dynamic,
    fig_fault,
    fig_hetero,
    fig_serving,
    roofline_table,
)
from .common import Csv


MODULES = (fig12_algbw, fig13_skew, fig14_moe_e2e, fig15_scale,
           fig16_topo, fig17_overhead, fig_hetero, fig_dynamic,
           fig_serving, fig_fault, roofline_table)


def main(argv=None) -> None:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument(
        "--json", default="BENCH_latest.json", metavar="PATH",
        help="write a machine-readable snapshot here ('' to disable)")
    parser.add_argument(
        "--only", default="", metavar="SUBSTR",
        help="run only modules whose name contains SUBSTR "
             "(e.g. 'fig17' for the synthesis/overhead rows)")
    args = parser.parse_args(argv)
    enable_compile_cache()

    mods = [m for m in MODULES if args.only in m.__name__]
    if not mods:
        names = ", ".join(m.__name__.rsplit(".", 1)[-1] for m in MODULES)
        parser.error(f"--only {args.only!r} matches none of: {names}")
    csv = Csv()
    print("name,us_per_call,derived")
    for mod in mods:
        mod.run(csv)
    if args.json:
        csv.write_json(args.json)
        print(f"# wrote {len(csv.records)} rows to {args.json}")


if __name__ == "__main__":
    main()
