"""Population scoring, host work: milliseconds per engine batch spent in
the engine outside the label kernel's launch and wait and outside group
costing (``pop.batch`` minus ``pop.labels.launch``, ``pop.labels.wait``
and ``pop.cost``, over ``pop.batch`` calls, in the window's completed
searches)."""
from chipbench.phases import calls, seconds, window_phases


def read(rec):
    ph, _ = window_phases(rec)
    if not ph or not calls(ph, "pop.batch"):
        return None
    host = seconds(ph, "pop.batch") - seconds(
        ph, "pop.labels.launch", "pop.labels.wait", "pop.cost")
    return 1e3 * host / calls(ph, "pop.batch")
