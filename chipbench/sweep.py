"""Sweep the arrival rate of an open-loop cell to find the highest rate
the system sustains without a growing backlog.

    python chipbench/sweep.py --workload <cell> --seconds <s> \
        --seed <n> --rates 0.1 0.2 0.3 ...

Rates are in the mix's ``sweeps_per_s``: design sweeps, each one job per
machine of the configuration.

One process on the chip; for each rate a fresh daemon, the cell's traffic
at that rate, and one JSON line: the latency median and tail, and the
backlog trend (median latency of the window's last third over its first
third: about 1 when the queue is steady, growing past it when the rate is
beyond what the system sustains).  The cell keeps its rate in its mix
file; the benchmark's own runs never sweep.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from chipbench import harness  # noqa: E402
from chipbench.stats import nearest_rank  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    ctx = harness.load_cell(args.workload)
    harness.prepare_program()
    harness.enable_cache()
    compiles = harness.CompileLog()
    try:
        harness.check_device(ctx["cell"]["chips"])
    except harness.NoChip as e:
        harness.log(f"no sweep: {e}")
        return 1
    for rate in args.rates:
        ctx["mix"]["sweeps_per_s"] = rate
        driver = harness.DaemonDriver(ctx)
        try:
            driver.setup()
            w = driver.window(args.seed, args.seconds,
                              harness.Tracer(False, 0, 0), compiles)
        finally:
            driver.close()
        jobs = w["jobs"]
        done = [j for j in jobs if j.get("seen") is not None]
        lat = [j["seen"] - j["t"] for j in done]
        third = max(len(done) // 3, 1)
        first = [j["seen"] - j["t"] for j in done[:third]]
        last = [j["seen"] - j["t"] for j in done[-third:]]
        print(json.dumps({
            "sweeps_per_s": rate, "jobs": len(jobs), "done": len(done),
            "p50_s": nearest_rank(lat, 0.5) if lat else None,
            "p95_s": nearest_rank(lat, 0.95) if lat else None,
            "backlog_trend": (nearest_rank(last, 0.5)
                              / nearest_rank(first, 0.5)) if lat else None,
            "outcomes": w["outcomes"], "lateness": w["lateness"],
            "compiles_in_window": w["compiles_in_window"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
