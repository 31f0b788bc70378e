"""GA host loop: milliseconds per generation making offspring, the
mutation and the top-up loops (``ga.mutate`` over ``ga.generation``
calls, in the window's completed searches)."""
from chipbench.phases import calls, seconds, window_phases


def read(rec):
    ph, _ = window_phases(rec)
    if not ph or not calls(ph, "ga.generation"):
        return None
    return 1e3 * seconds(ph, "ga.mutate") / calls(ph, "ga.generation")
