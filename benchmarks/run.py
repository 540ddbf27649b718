"""Benchmark harness entry point: ``python -m benchmarks.run``.

Runs one benchmark per paper table/figure (see DESIGN.md §7) plus the
roofline report when analyzed dry-run records exist. ``--quick`` trims
the density sweep. Individual benches run via
``python -m benchmarks.<name>``.
"""
from __future__ import annotations

import argparse
import os
import time
import traceback

from benchmarks.common import RESULTS_DIR


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default=None,
                    help="comma-separated subset of benchmark names")
    args = ap.parse_args()

    from repro.models import compile_cache
    compile_cache.enable()

    from benchmarks import (cache, cluster, cold_start, cpu_cycles,
                            density, faasm_gap, fault_tolerance,
                            hlo_analysis, memory_footprint, ml_serving,
                            model_flops, overload, sim_throughput,
                            warm_path)

    benches = [
        ("cpu_cycles (Fig 2)", cpu_cycles.run, {}),
        ("memory_footprint (Fig 3/10/11)", memory_footprint.run, {}),
        ("model_flops (analytic reference)", model_flops.run, {}),
        ("hlo_analysis (loop-aware HLO scan)", hlo_analysis.run, {}),
        ("warm_path (Fig 7/8/9)", warm_path.run, {}),
        ("cold_start (Fig 12/13)", cold_start.run, {}),
        ("sim_throughput (DES engine)", sim_throughput.run,
         {"quick": args.quick}),
        ("density (Fig 6 + full matrix)", density.run,
         {"quick": args.quick}),
        ("ml_serving (MLServe: calibrated ML suite)", ml_serving.run,
         {"quick": args.quick}),
        ("fault_tolerance (§5, FaultPlane)", fault_tolerance.run,
         {"quick": args.quick}),
        ("overload (GuardRails degradation curves)", overload.run,
         {"quick": args.quick}),
        ("cluster (ClusterSim fleet dispatch sweep)", cluster.run,
         {"quick": args.quick}),
        ("cache (SharedCache reuse + density delta)", cache.run,
         {"quick": args.quick}),
        ("faasm_gap (Fig 14)", faasm_gap.run, {}),
    ]
    roofline_path = os.path.join(RESULTS_DIR, "roofline.jsonl")
    if os.path.exists(roofline_path):
        from benchmarks import roofline
        benches.append(("roofline (SRoofline)", roofline.run, {}))

    wanted = set(args.only.split(",")) if args.only else None
    failures = []
    for name, fn, kw in benches:
        if wanted and not any(w in name for w in wanted):
            continue
        print(f"\n{'=' * 72}\n{name}\n{'=' * 72}")
        t0 = time.time()
        try:
            fn(**kw)
            print(f"[{name}] done in {time.time() - t0:.1f}s")
        except Exception:                          # noqa: BLE001
            failures.append(name)
            traceback.print_exc()

    print(f"\n{'=' * 72}")
    if failures:
        print(f"FAILED: {failures}")
        raise SystemExit(1)
    print("all benchmarks completed; JSON results in results/")


if __name__ == "__main__":
    main()
