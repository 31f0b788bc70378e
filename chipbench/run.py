"""Run one cell of the benchmark on the chip this process holds.

    python chipbench/run.py --workload <config>.<mix> --seed <n> \
        --seconds <s> --trace <0|1>

With ``--trace 0`` the result's metrics are the cell's end-to-end metrics;
with ``--trace 1`` its per-layer metrics, read from a profiler trace of a
sub-window and from the program's counters.  Earlier lines on standard
error name the device, the engine that scored the batches, the compiles and
the numbers compared; the last line of standard output is the result.  On a
machine whose first device is not a TPU the run exits non-zero and prints
no result.
"""
from __future__ import annotations

import time

PROCESS_START = time.monotonic()

import argparse  # noqa: E402 — the set-up clock starts before imports
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from chipbench import harness  # noqa: E402
from chipbench.harness import log  # noqa: E402
from chipbench.stats import beyond, nearest_rank  # noqa: E402


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def trace_span(seconds: float) -> tuple:
    """(start offset, length) of the traced sub-window."""
    return 0.25 * seconds, min(5.0, 0.25 * seconds)


def end_to_end(ctx, window, setup_s) -> dict:
    out = {"setup_s": setup_s}
    if "searches" in window:
        out["search_evals_per_s"] = window["offspring"] / window["window_s"]
    else:
        lat = [(j["seen"] - j["t"]) if j.get("seen") is not None
               else (window["window_s"] + ctx["mix"]["drain_s"] - j["t"])
               for j in window["jobs"]]
        out["job_p50_s"] = nearest_rank(lat, 0.50)
        out["job_p95_s"] = nearest_rank(lat, 0.95)
    units = {m["name"]: m["unit"] for m in ctx["end_to_end"]}
    return {k: {"value": v, "unit": units[k]} for k, v in out.items()
            if k in units}


def per_layer(ctx, record) -> dict:
    out = {}
    for m in ctx["per_layer"]:
        v = harness.metric_reader(m["name"])(record)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def describe(ctx, window) -> None:
    if "searches" in window:
        cut = sum(1 for s in window["searches"] if s["cut"])
        log(f"window: {window['attempted']} searches started, {cut} cut by "
            f"the window's end, {window['offspring']} offspring, "
            f"{window['generations']} generations")
        log(f"engine: batches_by_engine {json.dumps(window['engines'])}")
    else:
        late = window["lateness"]
        lat = [j["seen"] - j["t"] for j in window["jobs"]
               if j.get("seen") is not None]
        if lat:
            log(f"latency: {len(lat)} jobs seen done, p50 "
                f"{nearest_rank(lat, 0.50)} s, p95 {nearest_rank(lat, 0.95)} "
                f"s ({beyond(lat, 0.95)} beyond it)")
        log(f"window: {window['attempted']} jobs due, outcomes "
            f"{json.dumps(window['outcomes'], sort_keys=True)}, queue wait "
            f"{json.dumps(window['queue_wait'])}")
        log(f"generator: {late['sent']} sent, lateness p50 "
            f"{late['p50_s']} s, max {late['max_s']} s")
        log(f"engine: eval.batches_by_engine "
            f"{json.dumps(window['engines'], sort_keys=True)}")
    log(f"compiles in window: {window['compiles_in_window']}")


def failed_count(window) -> int:
    if "searches" in window:
        return sum(1 for s in window["searches"]
                   if s.get("error") or s.get("bad"))
    return sum(1 for j in window["jobs"] if j.get("state") != "done")


def run_cell(ctx, seed: int, seconds: float, trace: bool, device: dict,
             compiles) -> dict:
    """Set-up, window, drain and check of one run on ``device``; the
    result line as a dict."""
    driver = harness.DRIVERS[ctx["mix"]["driver"]](ctx)
    tracer = harness.Tracer(trace, *trace_span(seconds))
    try:
        driver.setup()
        log(f"set-up compiles: {json.dumps(compiles.summary())}")
        window = driver.window(seed, seconds, tracer, compiles)
    finally:
        driver.close()
    setup_s = window["t0"] - PROCESS_START
    describe(ctx, window)
    device = dict(device, memory_peak_bytes=harness.memory_peak())
    record = {"window": window, "config": ctx["config"], "mix": ctx["mix"],
              "device_kind": device["kind"]}
    red = None
    if trace:
        try:
            red = tracer.reduce()
        finally:
            tracer.close()
        if red is None:
            raise RuntimeError("trace: no device plane ran an operation")
        record["trace"] = red
        log(f"trace: window {red['window_s']} s, busy {red['busy_s']} s, "
            f"kernels {json.dumps(red['kernels'])}, engine counters "
            f"{json.dumps(red['counters_start'])} -> "
            f"{json.dumps(red['counters_stop'])}")
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
    from chipbench import check
    t_check = harness.now()
    tally = check.check(window, ctx)
    check_s = harness.now() - t_check
    nums = tally.numbers()
    correct = check.verdict(tally, ctx["limits"], window["attempted"])
    for f in tally.faults:
        log(f"fault: {f}")
    result = {"correct": correct, "attempted": window["attempted"],
              "failed": failed_count(window),
              "metrics": (per_layer(ctx, record) if trace
                          else end_to_end(ctx, window, setup_s)),
              "device": device}
    if red is not None:
        result["breakdown"] = {"device_ops": red["device_ops"],
                               "idle_gaps": red["idle_gaps"]}
    result["compared"] = {k: {"value": v, "limit": ctx["limits"][k]}
                          for k, v in nums.items()}
    log(f"setup_s: {setup_s}")
    log(f"compared: {tally.compared} numbers in {check_s} s")
    for k, v in nums.items():
        log(f"{k} {v} limit {ctx['limits'][k]}")
    return result


def main(argv=None) -> int:
    args = parse(argv)
    ctx = harness.load_cell(args.workload)
    harness.prepare_program()
    harness.enable_cache()
    compiles = harness.CompileLog()
    try:
        device = harness.check_device(ctx["cell"]["chips"])
    except harness.NoChip as e:
        log(f"no result: {e}")
        return 1
    result = run_cell(ctx, args.seed, args.seconds, bool(args.trace), device,
                      compiles)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
