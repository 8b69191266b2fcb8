"""Benchmark harness: one module per paper table/figure + the roofline.

Prints ``name,us_per_call,derived`` CSV at the end (harness contract).
Run: PYTHONPATH=src python -m benchmarks.run [--quick]

``--quick`` shrinks the fused-topk A/B shapes for CI smoke runs; the paper
tables are analytic and always run in full.
"""
from __future__ import annotations

import argparse
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="CI smoke mode: small fused-topk A/B shapes")
    args = ap.parse_args()
    csv_rows: list = []

    from benchmarks import ann_sweep, cortex_m4, estimator_sweep
    from benchmarks import fault_sweep, fp_backends, kernel_blocks
    from benchmarks import parallel_speedup, quant_ab, report, roofline
    from benchmarks import serving_load, sorting, tenant_sweep

    fitted = fp_backends.run(csv_rows)          # Fig. 9 / Table 2
    parallel_speedup.run(csv_rows, fitted)      # Fig. 10 / Table 3
    cortex_m4.run(csv_rows)                     # Fig. 11
    sorting.run(csv_rows)                       # Eq. 14
    kernel_blocks.run(csv_rows)                 # Pallas BlockSpec analysis
    fused = parallel_speedup.run_fused_ab(csv_rows, quick=args.quick)
    report.write_fused_entry(fused)             # accumulate BENCH json
    est = estimator_sweep.run(csv_rows, quick=args.quick)
    report.write_estimators_entry(est)          # algorithm x backend x bucket
    sharded = parallel_speedup.run_sharded(csv_rows, quick=args.quick)
    report.write_sharded_entry(sharded)         # 1-vs-8-shard vs Amdahl
    serving = serving_load.run(csv_rows, quick=args.quick)
    report.write_serving_entry(serving)         # rate x algo x bucket policy
    quant = quant_ab.run(csv_rows, quick=args.quick)
    report.write_quant_entry(quant)             # representation A/B (§5.2)
    ann = ann_sweep.run(csv_rows, quick=args.quick)
    report.write_ann_entry(ann)                 # recall@k vs latency (§10)
    tenants = tenant_sweep.run(csv_rows, quick=args.quick)
    report.write_tenants_entry(tenants)         # grouped-vs-loop (§11)
    faults = fault_sweep.run(csv_rows, quick=args.quick)
    report.write_faults_entry(faults)           # chaos degrade A/B (§13)
    roofline.run(csv_rows)                      # deliverable (g)

    # close the loop (DESIGN.md §12): refit the cost model against the
    # sweep entries this run just appended and persist CALIBRATION.json
    from repro.core import calibrate
    fit = calibrate.calibrate(write=True)
    print("\n== Calibration refit (CALIBRATION.json) ==")
    for tier, ts in fit["summary"]["tiers"].items():
        print(f"   {tier:9s} median |rel err| "
              f"{ts['median_abs_rel_err']:.0%} over {ts['n']} rows")
        csv_rows.append((f"calibration/{tier}", 0.0,
                         f"median_abs_rel_err="
                         f"{ts['median_abs_rel_err']:.3f};n={ts['n']}"))

    print("\nname,us_per_call,derived")
    for name, us, derived in csv_rows:
        print(f"{name},{us:.2f},{derived}")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
