"""Generate the EXPERIMENTS.md §Dry-run and §Roofline tables from
experiments/dryrun/*.json (markdown emitters; the narrative lives in
EXPERIMENTS.md itself).

  PYTHONPATH=src python -m benchmarks.report [--refresh]
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from benchmarks.roofline import analyze_record, load_records, model_flops

BENCH_FUSED_TOPK = Path(__file__).resolve().parents[1] / \
    "BENCH_fused_topk.json"
BENCH_ESTIMATORS = Path(__file__).resolve().parents[1] / \
    "BENCH_estimators.json"
BENCH_SHARDED = Path(__file__).resolve().parents[1] / \
    "BENCH_sharded.json"
BENCH_SERVING = Path(__file__).resolve().parents[1] / \
    "BENCH_serving.json"
BENCH_QUANT = Path(__file__).resolve().parents[1] / \
    "BENCH_quant.json"
BENCH_ANN = Path(__file__).resolve().parents[1] / \
    "BENCH_ann.json"
BENCH_TENANTS = Path(__file__).resolve().parents[1] / \
    "BENCH_tenants.json"
BENCH_FAULTS = Path(__file__).resolve().parents[1] / \
    "BENCH_faults.json"
CALIBRATION = Path(__file__).resolve().parents[1] / \
    "CALIBRATION.json"

# Required keys per BENCH accumulator: every entry must carry the
# envelope, every result record the per-kind keys.  The trajectory files
# are append-only across many CI runs — a malformed entry must fail
# LOUDLY at load instead of silently skewing the tables built from them.
# (Keys added later — e.g. "shards" on estimator records — are asserted
# for NEW entries by CI, not retroactively required of old ones.)
_ENTRY_KEYS = ("timestamp", "backend", "results")
_RESULT_KEYS = {
    "estimators": ("algorithm", "policy", "bucket", "path", "us_per_query"),
    "fused_topk": ("shape", "fused", "two_pass", "speedup"),
    "sharded": ("algorithm", "shards", "strategy", "us_per_query_1shard",
                "us_per_query_8shard", "measured_speedup", "amdahl_bound"),
    "serving": ("algorithm", "rate", "max_wait", "p50", "p95", "p99",
                "throughput", "occupancy", "hit_rate",
                "deadline_miss_rate"),
    "quant": ("algorithm", "arm", "bucket", "path", "us_per_query",
              "label_agreement"),
    "ann": ("algorithm", "arm", "bucket", "N", "nprobe", "us_per_query",
            "recall_at_k", "k"),
    "tenants": ("algorithm", "n_tenants", "resident_frac", "bucket",
                "us_per_query_grouped", "us_per_query_loop"),
    "calibration": ("tier", "algorithm", "op", "bucket", "path",
                    "measured_us", "predicted_us", "rel_err"),
    "faults": ("algorithm", "mode", "plan", "degrade", "completed",
               "shed", "shed_rate", "miss_rate", "miss_plus_shed_rate",
               "label_agreement"),
}


def load_bench(path: Path, kind: str) -> dict:
    """Load + schema-check a BENCH_*.json accumulator.

    Raises ValueError naming the offending entry/record on corrupt JSON,
    a missing ``entries`` list, or records missing required keys.
    """
    required = _RESULT_KEYS[kind]
    try:
        data = json.loads(path.read_text())
    except json.JSONDecodeError as e:
        raise ValueError(f"{path.name}: corrupt JSON ({e})") from None
    entries = data.get("entries")
    if not isinstance(entries, list):
        raise ValueError(f"{path.name}: no 'entries' list")
    for i, entry in enumerate(entries):
        missing = [k for k in _ENTRY_KEYS if k not in entry]
        if missing:
            raise ValueError(f"{path.name}: entry {i} missing {missing}")
        if not isinstance(entry["results"], list):
            raise ValueError(f"{path.name}: entry {i} 'results' not a list")
        for j, rec in enumerate(entry["results"]):
            missing = [k for k in required if k not in rec]
            if missing:
                raise ValueError(f"{path.name}: entry {i} result {j} "
                                 f"missing {missing}")
    return data


def fmt_bytes(b: float) -> str:
    for unit in ("B", "KB", "MB", "GB", "TB"):
        if abs(b) < 1024:
            return f"{b:.1f}{unit}"
        b /= 1024
    return f"{b:.1f}PB"


def dryrun_table(mesh: str, tag: str = "baseline") -> str:
    lines = [
        f"| arch | shape | status | compile_s | HLO FLOPs/chip | "
        f"HBM bytes/chip | collective/chip | param bytes/chip |",
        "|---|---|---|---|---|---|---|---|",
    ]
    for rec in load_records(mesh, tag):
        if rec.get("status") == "skipped":
            lines.append(f"| {rec['arch']} | {rec['shape']} | SKIP "
                         f"(sub-quadratic-only) | — | — | — | — | — |")
            continue
        if rec.get("status") != "ok":
            lines.append(f"| {rec['arch']} | {rec['shape']} | "
                         f"ERROR | — | — | — | — | — |")
            continue
        hs = rec["hlo_stats"]
        pbytes = rec["params"] * 2 / rec["n_devices"]
        lines.append(
            f"| {rec['arch']} | {rec['shape']} | ok | {rec['compile_s']} | "
            f"{hs['flops_dot']:.2e} | {fmt_bytes(hs['bytes'])} | "
            f"{fmt_bytes(hs['collective_bytes'])} | {fmt_bytes(pbytes)} |")
    return "\n".join(lines)


def roofline_table(mesh: str = "single", tag: str = "baseline") -> str:
    lines = [
        "| arch | shape | compute_s | memory_s | collective_s | dominant | "
        "MODEL_FLOPS | useful | MFU_bound | next lever |",
        "|---|---|---|---|---|---|---|---|---|---|",
    ]
    for rec in load_records(mesh, tag):
        if rec.get("status") == "skipped":
            lines.append(f"| {rec['arch']} | {rec['shape']} | — | — | — | "
                         f"SKIP | — | — | — | sub-quadratic-only shape |")
            continue
        r = analyze_record(rec)
        if not r:
            continue
        lines.append(
            f"| {r['arch']} | {r['shape']} | {r['compute_s']:.3f} | "
            f"{r['memory_s']:.3f} | {r['collective_s']:.3f} | "
            f"**{r['dominant']}** | {r['model_flops']:.2e} | "
            f"{r['useful_ratio']:.2f} | {r['mfu_bound']:.1%} | "
            f"{r['advice'][:60]}... |")
    return "\n".join(lines)


def perf_compare_table(cells, tags) -> str:
    """Before/after table for the hillclimbed cells."""
    lines = ["| cell | tag | compute_s | memory_s | collective_s | dominant | "
             "step_lb_s | MFU_bound |",
             "|---|---|---|---|---|---|---|---|"]
    for arch, shape in cells:
        for tag in tags:
            recs = [r for r in load_records("single", tag)
                    if r["arch"] == arch and r["shape"] == shape]
            if not recs or recs[0].get("status") != "ok":
                continue
            r = analyze_record(recs[0])
            lines.append(
                f"| {arch}/{shape} | {tag} | {r['compute_s']:.3f} | "
                f"{r['memory_s']:.3f} | {r['collective_s']:.3f} | "
                f"{r['dominant']} | {r['step_time_lb_s']:.3f} | "
                f"{r['mfu_bound']:.1%} |")
    return "\n".join(lines)


def _append_entry(results, path: Path, kind: str, **extra) -> dict:
    """Append one timestamped measurement entry to a BENCH_*.json
    accumulator.  An existing file is schema-checked first — silently
    resetting a corrupt trajectory would drop history and skew every
    report built on it.  ``extra`` keys land on the entry envelope
    (the calibration artifact carries its refit vectors there)."""
    import time as _time
    entry = {
        "timestamp": _time.strftime("%Y-%m-%dT%H:%M:%S"),
        "backend": _backend_name(),
        "results": results,
        **extra,
    }
    data = load_bench(path, kind) if path.exists() else {"entries": []}
    data["entries"].append(entry)
    path.write_text(json.dumps(data, indent=2) + "\n")
    return entry


def write_fused_entry(results, path: Path = BENCH_FUSED_TOPK) -> dict:
    """Append one fused-vs-two-pass A/B measurement (latency + HLO
    bytes-accessed per shape) to BENCH_fused_topk.json so the perf
    trajectory accumulates across runs."""
    return _append_entry(results, path, "fused_topk")


def write_estimators_entry(results, path: Path = BENCH_ESTIMATORS) -> dict:
    """Append one algorithm x backend x bucket serving sweep (unified
    Estimator API through NonNeuralServeEngine) to BENCH_estimators.json."""
    return _append_entry(results, path, "estimators")


def write_sharded_entry(results, path: Path = BENCH_SHARDED) -> dict:
    """Append one 1-vs-8-shard serving speedup measurement (next to the
    Amdahl bound) to BENCH_sharded.json."""
    return _append_entry(results, path, "sharded")


def write_serving_entry(results, path: Path = BENCH_SERVING) -> dict:
    """Append one request-stream scheduler load sweep (rate x algorithm x
    bucket policy, SLO accounting from ServingStats) to
    BENCH_serving.json."""
    return _append_entry(results, path, "serving")


def write_quant_entry(results, path: Path = BENCH_QUANT) -> dict:
    """Append one representation A/B sweep (fp32-ref / fp32-fused / bf16 /
    int8 per algorithm x bucket, latency + label agreement — the Fig. 9-11
    analogue) to BENCH_quant.json."""
    return _append_entry(results, path, "quant")


def write_ann_entry(results, path: Path = BENCH_ANN) -> dict:
    """Append one recall@k-vs-latency sweep (IVF-PQ ANN against the exact
    fused kNN oracle, nprobe as the knob, per reference size N) to
    BENCH_ann.json."""
    return _append_entry(results, path, "ann")


def write_tenants_entry(results, path: Path = BENCH_TENANTS) -> dict:
    """Append one multi-tenant grouped-vs-loop sweep (G same-shape fits
    served through ONE vmapped launch per (group x bucket) cell vs G
    separate per-model launches, per residency fraction) to
    BENCH_tenants.json."""
    return _append_entry(results, path, "tenants")


def write_faults_entry(results, path: Path = BENCH_FAULTS) -> dict:
    """Append one chaos A/B sweep (the committed ChaosPlan replayed with
    graceful degradation off vs on, per algorithm and serving mode:
    miss+shed rate, brownout-tier label agreement vs the exact fp32
    oracle, downshift counts) to BENCH_faults.json."""
    return _append_entry(results, path, "faults")


def write_calibration_entry(results, *, vectors, summary,
                            path: Path = CALIBRATION) -> dict:
    """Append one calibration fit (per-(tier, algorithm, bucket)
    predicted-vs-measured rows + the refit us-per-op vectors and fit
    summary on the envelope) to CALIBRATION.json — the artifact
    ``CostModel.from_calibration`` and ``REPRO_CALIBRATION`` consume."""
    return _append_entry(results, path, "calibration",
                         vectors=vectors, summary=summary)


def calibration_table(path: Path = CALIBRATION) -> str:
    if not path.exists():
        return "(no CALIBRATION.json yet — run python -m repro.core.calibrate)"
    data = load_bench(path, "calibration")
    lines = ["| when | tier | algo | bucket | path | measured us/q | "
             "predicted us/q | rel err |",
             "|---|---|---|---|---|---|---|---|"]
    for e in data["entries"]:
        for r in e["results"]:
            lines.append(
                f"| {e['timestamp']} | {r['tier']} | {r['algorithm']} | "
                f"{r['bucket']} | {r['path']} | {r['measured_us']:.1f} | "
                f"{r['predicted_us']:.1f} | {r['rel_err']:+.0%} |")
    return "\n".join(lines)


def tenants_table(path: Path = BENCH_TENANTS) -> str:
    if not path.exists():
        return "(no BENCH_tenants.json yet — run benchmarks/run.py)"
    data = load_bench(path, "tenants")
    lines = ["| when | algo | G | resident | bucket | grouped us/q | "
             "loop us/q | speedup |",
             "|---|---|---|---|---|---|---|---|"]
    for e in data["entries"]:
        for r in e["results"]:
            speed = r["us_per_query_loop"] / max(
                r["us_per_query_grouped"], 1e-9)
            lines.append(
                f"| {e['timestamp']} | {r['algorithm']} | "
                f"{r['n_tenants']} | {r['resident_frac']:.2f} | "
                f"{r['bucket']} | {r['us_per_query_grouped']:.1f} | "
                f"{r['us_per_query_loop']:.1f} | {speed:.2f}x |")
    return "\n".join(lines)


def faults_table(path: Path = BENCH_FAULTS) -> str:
    if not path.exists():
        return "(no BENCH_faults.json yet — run benchmarks/fault_sweep.py)"
    data = load_bench(path, "faults")
    lines = ["| when | algo | mode | plan | degrade | completed | shed | "
             "miss+shed | agreement | downshifts | tiers |",
             "|---|---|---|---|---|---|---|---|---|---|---|"]
    for e in data["entries"]:
        for r in e["results"]:
            tiers = ", ".join(f"{k}:{v}" for k, v in sorted(
                r.get("tier_served", {}).items())) or "—"
            lines.append(
                f"| {e['timestamp']} | {r['algorithm']} | {r['mode']} | "
                f"{r['plan']} | {'on' if r['degrade'] else 'off'} | "
                f"{r['completed']} | {r['shed']} | "
                f"{r['miss_plus_shed_rate']:.3f} | "
                f"{r['label_agreement']:.3f} | {r.get('downshifts', 0)} | "
                f"{tiers} |")
    return "\n".join(lines)


def ann_table(path: Path = BENCH_ANN) -> str:
    if not path.exists():
        return "(no BENCH_ann.json yet — run benchmarks/run.py)"
    data = load_bench(path, "ann")
    lines = ["| when | arm | N | bucket | nprobe | refine | us/query | "
             "recall@k | vs exact |",
             "|---|---|---|---|---|---|---|---|---|"]
    for e in data["entries"]:
        exact = {(r["N"], r["bucket"]): r["us_per_query"]
                 for r in e["results"] if r["arm"] == "exact"}
        for r in e["results"]:
            base = exact.get((r["N"], r["bucket"]))
            speed = (f"{base / r['us_per_query']:.1f}x"
                     if base and r["arm"] != "exact" else "—")
            lines.append(
                f"| {e['timestamp']} | {r['arm']} | {r['N']} | "
                f"{r['bucket']} | {r['nprobe']} | {r.get('refine', 0)} | "
                f"{r['us_per_query']:.1f} | {r['recall_at_k']:.3f} | "
                f"{speed} |")
    return "\n".join(lines)


def quant_table(path: Path = BENCH_QUANT) -> str:
    if not path.exists():
        return "(no BENCH_quant.json yet — run benchmarks/run.py)"
    data = load_bench(path, "quant")
    lines = ["| when | algo | arm | bucket | path | us/query | "
             "agreement vs fp32 |",
             "|---|---|---|---|---|---|---|"]
    for e in data["entries"]:
        for r in e["results"]:
            lines.append(
                f"| {e['timestamp']} | {r['algorithm']} | {r['arm']} | "
                f"{r['bucket']} | {r['path']} | {r['us_per_query']:.1f} | "
                f"{r['label_agreement']:.3f} |")
    return "\n".join(lines)


def serving_table(path: Path = BENCH_SERVING) -> str:
    if not path.exists():
        return "(no BENCH_serving.json yet — run benchmarks/serving_load.py)"
    data = load_bench(path, "serving")
    lines = ["| when | algo | rate | max_wait | p50 | p95 | p99 | "
             "req/tick | occupancy | hit | miss |",
             "|---|---|---|---|---|---|---|---|---|---|---|"]
    for e in data["entries"]:
        for r in e["results"]:
            lines.append(
                f"| {e['timestamp']} | {r['algorithm']} | {r['rate']:g} | "
                f"{r['max_wait']} | {r['p50']:.0f} | {r['p95']:.0f} | "
                f"{r['p99']:.0f} | {r['throughput']:.2f} | "
                f"{r['occupancy']:.2f} | {r['hit_rate']:.2f} | "
                f"{r['deadline_miss_rate']:.2f} |")
    return "\n".join(lines)


def estimators_table(path: Path = BENCH_ESTIMATORS) -> str:
    if not path.exists():
        return "(no BENCH_estimators.json yet — run benchmarks/run.py)"
    data = load_bench(path, "estimators")
    lines = ["| when | algo | policy | bucket | shards | path | us/query | "
             "libgcc/fpu penalty |",
             "|---|---|---|---|---|---|---|---|"]
    for e in data["entries"]:
        for r in e["results"]:
            cyc = r.get("analytic_cycles", {})
            pen = (cyc.get("libgcc", 0.0) / cyc["fpu"]
                   if cyc.get("fpu") else float("nan"))
            lines.append(
                f"| {e['timestamp']} | {r['algorithm']} | {r['policy']} | "
                f"{r['bucket']} | {r.get('shards', 1)} | {r['path']} | "
                f"{r['us_per_query']:.1f} | {pen:.1f}x |")
    return "\n".join(lines)


def sharded_table(path: Path = BENCH_SHARDED) -> str:
    if not path.exists():
        return "(no BENCH_sharded.json yet — run benchmarks/run.py)"
    data = load_bench(path, "sharded")
    lines = ["| when | algo | strategy | us/q 1-shard | us/q 8-shard | "
             "us/q query | us/q reference | measured | amdahl bound |",
             "|---|---|---|---|---|---|---|---|---|"]

    def _us(r, key):
        return f"{r[key]:.1f}" if key in r else "—"

    for e in data["entries"]:
        for r in e["results"]:
            lines.append(
                f"| {e['timestamp']} | {r['algorithm']} | "
                f"{r['strategy']} | "
                f"{r['us_per_query_1shard']:.1f} | "
                f"{r['us_per_query_8shard']:.1f} | "
                f"{_us(r, 'us_per_query_query')} | "
                f"{_us(r, 'us_per_query_reference')} | "
                f"{r['measured_speedup']:.2f}x | "
                f"{r['amdahl_bound']:.2f}x |")
    return "\n".join(lines)


def _backend_name() -> str:
    import jax
    return jax.default_backend()


def fused_topk_table(path: Path = BENCH_FUSED_TOPK) -> str:
    if not path.exists():
        return "(no BENCH_fused_topk.json yet — run benchmarks/run.py)"
    data = load_bench(path, "fused_topk")
    lines = ["| when | (N,d,Q,k) | fused_us | two_pass_us | speedup | "
             "fused HLO bytes | two_pass HLO bytes |",
             "|---|---|---|---|---|---|---|"]
    for e in data["entries"]:
        for r in e["results"]:
            lines.append(
                f"| {e['timestamp']} | {tuple(r['shape'])} | "
                f"{r['fused']['us']:.0f} | {r['two_pass']['us']:.0f} | "
                f"{r['speedup']:.2f}x | "
                f"{fmt_bytes(r['fused']['hlo_bytes'])} | "
                f"{fmt_bytes(r['two_pass']['hlo_bytes'])} |")
    return "\n".join(lines)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--refresh", action="store_true",
                    help="re-run the HLO analyzer on cached .hlo.zst files")
    ap.add_argument("--tag", default="baseline")
    ap.add_argument("--fused-topk", action="store_true",
                    help="measure the fused distance->top-k A/B and append "
                         "an entry to BENCH_fused_topk.json")
    ap.add_argument("--estimators", action="store_true",
                    help="run the estimator serving sweep (algorithm x "
                         "backend x bucket) and append an entry to "
                         "BENCH_estimators.json")
    ap.add_argument("--sharded", action="store_true",
                    help="measure the 1-vs-8-shard serving speedup "
                         "(forced-8-device subprocess) and append an "
                         "entry to BENCH_sharded.json")
    ap.add_argument("--serving", action="store_true",
                    help="run the request-stream scheduler load sweep "
                         "(rate x algorithm x bucket policy) and append "
                         "an entry to BENCH_serving.json")
    ap.add_argument("--quant", action="store_true",
                    help="run the representation A/B (fp32-ref / "
                         "fp32-fused / bf16 / int8 per algorithm x "
                         "bucket) and append an entry to BENCH_quant.json")
    ap.add_argument("--ann", action="store_true",
                    help="run the IVF-PQ recall@k-vs-latency sweep "
                         "(nprobe knob, exact fused kNN oracle) and "
                         "append an entry to BENCH_ann.json")
    ap.add_argument("--tenants", action="store_true",
                    help="run the multi-tenant grouped-vs-loop sweep "
                         "(ModelStore + vmapped group launch per tenant "
                         "count) and append an entry to BENCH_tenants.json")
    ap.add_argument("--faults", action="store_true",
                    help="replay the committed ChaosPlan with graceful "
                         "degradation off vs on (admission control, "
                         "deadline shedding, brownout ladder, breakers) "
                         "and append an entry to BENCH_faults.json")
    ap.add_argument("--paper-tables", action="store_true",
                    help="print the unified backend-rung table (analytic "
                         "Table-2 fits + measured CALIBRATION.json tiers, "
                         "latency + energy) and the calibration fit table "
                         "from the committed artifacts — no benchmarks run")
    args = ap.parse_args()
    if args.paper_tables:
        from benchmarks.fp_backends import (
            analytic_rung_rows, calibrate, measured_rung_rows)
        fitted, _ = calibrate()
        rows = analytic_rung_rows(fitted) + measured_rung_rows()
        print("### Backend rungs (analytic + measured, latency + energy)\n")
        print("| rung | kernel | kind | cycles | us | energy_uJ |")
        print("|---|---|---|---|---|---|")
        for r in rows:
            print(f"| {r['rung']} | {r['kernel']} | {r['kind']} | "
                  f"{r['cycles']:.3e} | {r['us']:.2f} | "
                  f"{r['energy_uj']:.3f} |")
        print("\n### Calibration (predicted vs measured)\n")
        print(calibration_table())
        return
    if args.faults:
        from benchmarks.fault_sweep import run as run_faults
        write_faults_entry(run_faults([], quick=True))
        print("\n### Fault-injection A/B (graceful degradation)\n")
        print(faults_table())
        return
    if args.tenants:
        from benchmarks.tenant_sweep import run as run_tenants
        write_tenants_entry(run_tenants([], quick=True))
        print("\n### Multi-tenant grouped serving\n")
        print(tenants_table())
        return
    if args.ann:
        from benchmarks.ann_sweep import run as run_ann
        write_ann_entry(run_ann([], quick=True))
        print("\n### ANN recall-vs-latency\n")
        print(ann_table())
        return
    if args.quant:
        from benchmarks.quant_ab import run as run_quant
        write_quant_entry(run_quant([], quick=True))
        print("\n### Quant A/B\n")
        print(quant_table())
        return
    if args.serving:
        from benchmarks.serving_load import run as run_serving
        write_serving_entry(run_serving([], quick=True))
        print("\n### Serving load\n")
        print(serving_table())
        return
    if args.sharded:
        from benchmarks.parallel_speedup import run_sharded
        write_sharded_entry(run_sharded([], quick=True))
        print("\n### Sharded serving speedup\n")
        print(sharded_table())
        return
    if args.fused_topk:
        from benchmarks.parallel_speedup import run_fused_ab
        write_fused_entry(run_fused_ab([], quick=True))
        print("\n### Fused distance->top-k A/B\n")
        print(fused_topk_table())
        return
    if args.estimators:
        from benchmarks.estimator_sweep import run as run_estimators
        write_estimators_entry(run_estimators([], quick=True))
        print("\n### Estimator serving sweep\n")
        print(estimators_table())
        return
    if args.refresh:
        from benchmarks.roofline import refresh_from_hlo
        for mesh in ("single", "multi"):
            n = refresh_from_hlo(mesh, args.tag)
            print(f"refreshed {n} {mesh} records", file=sys.stderr)
    print("### Dry-run (single-pod 16x16)\n")
    print(dryrun_table("single", args.tag))
    print("\n### Dry-run (multi-pod 2x16x16)\n")
    print(dryrun_table("multi", args.tag))
    print("\n### Roofline (single-pod)\n")
    print(roofline_table("single", args.tag))


if __name__ == "__main__":
    main()
