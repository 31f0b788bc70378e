"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.  ``--full`` restores the
paper's GA settings (P=100, N=10, G=500); the default uses fewer
generations for CPU wall-time (EXPERIMENTS.md records which setting
produced each number).  ``--json PATH`` additionally writes all rows plus
the structured metric records (GA throughput, cache hit rates, ...) as a
machine-readable report; save one as ``BENCH_<label>.json`` to serve as the
perf-regression baseline (see benchmarks/README.md).
"""
import argparse
import importlib
import sys
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper GA settings (slower)")
    ap.add_argument("--only", default="",
                    help="comma-separated benchmark names to run")
    ap.add_argument("--json", default="",
                    help="write rows + structured records to this path")
    args = ap.parse_args()

    # suite modules are imported only when they run: a suite that imports
    # jax must not do so in a parent that a later suite forks
    suites = {
        "fig7": "fig7_receptive_field",
        "fig9": "fig9_resnet50_groups",
        "fig10": "fig10_workloads",
        "fig11": "fig11_repartition",
        "ga": "ga_convergence",
        "island": "island_scaling",
        "kernels": "kernel_bench",
        "roofline": "roofline_table",
        "serve": "serve_load",
        "tpu_ga": "tpu_schedule_bench",
    }
    selected = [s.strip() for s in args.only.split(",") if s.strip()] \
        or list(suites)
    unknown = [s for s in selected if s not in suites]
    if unknown:
        ap.error(f"unknown --only name(s) {', '.join(sorted(unknown))}; "
                 f"valid: {', '.join(suites)}")
    print("name,us_per_call,derived")
    failures = 0
    for name in selected:
        try:
            importlib.import_module(f"benchmarks.{suites[name]}").run(
                full=args.full)
        except Exception:
            failures += 1
            print(f"{name}_FAILED,0,{traceback.format_exc(limit=1)!r}")
    if args.json:
        from benchmarks.common import dump_json
        dump_json(args.json)
    sys.exit(1 if failures else 0)


if __name__ == '__main__':
    main()
