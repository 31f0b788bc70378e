"""Readings of the numbers compared, for setting their limits.

    python chipbench/readings.py --workload <cell> --seconds <s> \
        --seeds 1 2 3 ...

In one process on the chip: one set-up of the cell, then for each seed a
window at the cell's own load, checked twice, once with the program's
answers and once with the control's (the plain reference computed in
float32 in the program's place).  Prints one JSON line per seed and a
summary: the largest reading of the program and the smallest of the
control, per number.  The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from chipbench import check, harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    ctx = harness.load_cell(args.workload)
    harness.prepare_program()
    harness.enable_cache()
    compiles = harness.CompileLog()
    try:
        harness.check_device(ctx["cell"]["chips"])
    except harness.NoChip as e:
        harness.log(f"no readings: {e}")
        return 1
    driver = harness.DRIVERS[ctx["mix"]["driver"]](ctx)
    rows = []
    try:
        driver.setup()
        for seed in args.seeds:
            if ctx["mix"]["driver"] == "daemon" and seed != args.seeds[0]:
                driver.close()
                driver = harness.DRIVERS["daemon"](ctx)
                driver.setup()
            w = driver.window(seed, args.seconds, harness.Tracer(False, 0, 0),
                              compiles)
            prog = check.check(w, ctx)
            ctl = check.check(check.control_answers(w, ctx["config"]), ctx)
            row = {"seed": seed, "attempted": w["attempted"],
                   "compared": prog.compared, "program": prog.numbers(),
                   "control": ctl.numbers(),
                   "control_correct": check.verdict(ctl, ctx["limits"],
                                                    w["attempted"]),
                   "program_correct": check.verdict(prog, ctx["limits"],
                                                    w["attempted"]),
                   "faults": prog.faults}
            rows.append(row)
            print(json.dumps(row), flush=True)
    finally:
        driver.close()
    keys = rows[0]["program"]
    print(json.dumps({
        "summary": args.workload, "seeds": len(rows),
        "program_max": {k: max(r["program"][k] for r in rows) for k in keys},
        "control_min": {k: min(r["control"][k] for r in rows) for k in keys},
        "control_all_fail": not any(r["control_correct"] for r in rows),
        "program_all_pass": all(r["program_correct"] for r in rows)}),
        flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
