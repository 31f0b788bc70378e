"""Group costing: milliseconds per engine batch in the cost model, for the
groups no earlier batch of the search had costed (``pop.cost`` over
``pop.batch`` calls, in the window's completed searches)."""
from chipbench.phases import calls, seconds, window_phases


def read(rec):
    ph, _ = window_phases(rec)
    if not ph or not calls(ph, "pop.batch"):
        return None
    return 1e3 * seconds(ph, "pop.cost") / calls(ph, "pop.batch")
