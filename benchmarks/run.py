"""Benchmark entry point: one reproduction per paper table/figure plus the
roofline/kernel deliverables.

  PYTHONPATH=src python -m benchmarks.run [--only paper_figures ...]
"""

from __future__ import annotations

import argparse
import sys

import repro.telemetry as telemetry
from repro.compile_cache import enable_compile_cache

from . import blended_workloads, container_sizing, dnn_annealing, \
    fleet_arbitration, kernel_bench, paper_figures, pipeline_overlap, \
    roofline_table, surrogate_scale, trace_fleet
from .common import OUT_DIR, write_json

SUITES = {
    "paper_figures": paper_figures.run_all,
    "blended_workloads": blended_workloads.run_all,
    "fleet_arbitration": fleet_arbitration.run_all,
    "dnn_annealing": dnn_annealing.run_all,
    "roofline_table": roofline_table.run_all,
    "kernel_bench": kernel_bench.run_all,
    "surrogate_scale": surrogate_scale.run_all,
    "container_sizing": container_sizing.run_all,
    "pipeline_overlap": pipeline_overlap.run_all,
    "trace_fleet": trace_fleet.run_all,
}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", nargs="*", default=None,
                    help="suite names to run (default: all)")
    args = ap.parse_args(argv)
    enable_compile_cache()

    results = []
    for name, fn in SUITES.items():
        if args.only and name not in args.only:
            continue
        print(f"=== {name} ===", flush=True)
        # each suite runs under its own telemetry window and leaves a
        # TELEMETRY_<suite>.json + .perfetto.json next to its BENCH_*
        # artifact (sessions nest, so suites arming their own are fine)
        with telemetry.session(meta={"suite": name}) as tel:
            try:
                results.extend(fn())
            except Exception as e:  # a crashed suite is a failed suite
                import traceback
                traceback.print_exc()
                results.append({"bench": name, "ok": False,
                                "error": repr(e), "checks": []})
            tel.write_artifacts(f"TELEMETRY_{name}", out_dir=OUT_DIR)

    write_json("results.json", results)
    n_ok = sum(1 for r in results if r.get("ok"))
    n_checks = sum(len(r.get("checks", [])) for r in results)
    n_checks_ok = sum(sum(1 for c in r.get("checks", []) if c["ok"])
                      for r in results)
    print(f"\n{n_ok}/{len(results)} benches passed "
          f"({n_checks_ok}/{n_checks} claim checks)")
    return 0 if n_ok == len(results) else 1


if __name__ == "__main__":
    sys.exit(main())
