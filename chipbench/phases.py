"""The program's phase spans, as the window's completed searches report
them: ``backend_stats["phases"]`` of each artifact, ``{name: {"calls",
"seconds"}}`` (``repro.obs.Phases``: the engine's ``pop.*``, the GA loop's
``ga.*`` and the session's ``session.*`` spans).

A search cut by the window's end, or one that failed, has no artifact and
is left out of sums and counts alike.  A program that reports no phases
gives nothing to read."""
from __future__ import annotations

from typing import Dict, Optional, Tuple


def window_phases(rec: Dict) -> Tuple[Optional[Dict], int]:
    """(phases summed over the completed searches, how many searches);
    (None, 0) when no completed search reports phases."""
    total: Dict[str, Dict] = {}
    n = 0
    for s in rec["window"].get("searches") or ():
        art = s.get("artifact")
        ph = (art or {}).get("backend_stats", {}).get("phases")
        if ph is None:
            continue
        n += 1
        for name, v in ph.items():
            t = total.setdefault(name, {"calls": 0, "seconds": 0.0})
            t["calls"] += v["calls"]
            t["seconds"] += v["seconds"]
    return (total, n) if n else (None, 0)


def calls(ph: Dict, name: str) -> int:
    return ph.get(name, {}).get("calls", 0)


def seconds(ph: Dict, *names: str) -> float:
    return sum(ph.get(n, {}).get("seconds", 0.0) for n in names)
