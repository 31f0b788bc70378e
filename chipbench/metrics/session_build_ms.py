"""Session: milliseconds per completed search outside the GA loop —
building the session (``session.build``: graph, cost model, evaluator),
the engine's tables and their placement on the device (``pop.build``,
at the first batch) and the work after the search (``session.finish``:
exact best cost, breakdowns, artifact)."""
from chipbench.phases import seconds, window_phases


def read(rec):
    ph, n = window_phases(rec)
    if not ph:
        return None
    return 1e3 * seconds(ph, "session.build", "pop.build",
                         "session.finish") / n
