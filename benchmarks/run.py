"""Benchmark harness: one module per paper table/figure + the roofline
report.  ``python -m benchmarks.run [--quick] [--only NAME] [--json PATH]``

Besides the CSV tail, every run writes a machine-readable
``BENCH_throughput.json`` (all rows + metadata) so the perf trajectory is
tracked across PRs."""

import argparse
import csv
import json
import platform
import sys
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default=None,
                    choices=["cost_model", "batch_curve", "throughput",
                             "offload", "attn_schemes", "roofline"])
    ap.add_argument("--json", default=None,
                    help="machine-readable output path ('' disables; "
                         "defaults to BENCH_throughput.json on full runs — "
                         "partial --only runs don't clobber the tracked "
                         "snapshot unless asked to)")
    ap.add_argument("--workload", default="all",
                    choices=["all", "decode", "prefill_heavy", "online",
                             "latency_curve", "tracing", "roofline"],
                    help="throughput bench workload: 'decode' / "
                         "'prefill_heavy' run just that measured engine "
                         "workload (implies --only throughput, no "
                         "simulator pass); 'online' runs the Poisson "
                         "online-serving workload through OnlineLLM "
                         "with prefix caching (p50/p99 TTFT + ITL, "
                         "prefix-hit correctness); 'latency_curve' sweeps "
                         "simulated link latency on the real engine "
                         "(virtual clock, circular vs round-flush); "
                         "'tracing' runs the flight-recorder overhead "
                         "A/B (trace on vs off, gated >= 0.95x) and "
                         "exports bench_timeline.json; "
                         "'roofline' runs just the roofline report "
                         "incl. the measured per-kernel "
                         "achieved-vs-peak rows (implies --only "
                         "roofline)")
    args = ap.parse_args()
    if args.workload != "all" and args.only is None:
        args.only = "roofline" if args.workload == "roofline" \
            else "throughput"
    if args.json is None:
        args.json = "" if args.only else "BENCH_throughput.json"

    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    from benchmarks import (bench_attention_schemes, bench_batch_curve,
                            bench_cost_model, bench_offload, bench_roofline,
                            bench_throughput)
    benches = {
        "cost_model": bench_cost_model.run,       # paper Table 2
        "batch_curve": bench_batch_curve.run,     # paper Table 3
        "throughput": bench_throughput.run,       # paper Table 4 (headline)
        "offload": bench_offload.run,             # Formulas 1-2
        "attn_schemes": bench_attention_schemes.run,  # SPerf cell D
        "roofline": bench_roofline.run,           # deliverable (g)
    }
    rows = []
    timings = {}
    for name, fn in benches.items():
        if args.only and name != args.only:
            continue
        t0 = time.perf_counter()
        if name == "throughput":
            rows.extend(fn(quick=args.quick, workload=args.workload) or [])
        else:
            rows.extend(fn(quick=args.quick) or [])
        timings[name] = round(time.perf_counter() - t0, 1)
        print(f"   [{name}: {timings[name]:.1f}s]")

    if args.json:
        with open(args.json, "w") as f:
            json.dump({"generated_at": time.strftime("%Y-%m-%dT%H:%M:%S"),
                       "host": platform.node(),
                       "python": platform.python_version(),
                       "quick": args.quick,
                       "bench_seconds": timings,
                       "rows": rows}, f, indent=1, default=str)
        print(f"\nwrote {args.json} ({len(rows)} rows)")

    # machine-readable tail
    print("\n== CSV ==")
    w = csv.writer(sys.stdout)
    w.writerow(["bench", "key", "value"])
    for r in rows:
        bench = r.pop("bench")
        key = str(r.pop("name", "") or r.pop("arch", "") or r.pop(
            "policy", "") or r.pop("kernel", "") or "")
        shape = str(r.pop("shape", "") or r.pop("latency", "") or "")
        for k, v in r.items():
            if isinstance(v, (int, float)) and v is not None:
                tag = "/".join(x for x in (key, shape, k) if x)
                w.writerow([bench, tag, v])


if __name__ == "__main__":
    main()
