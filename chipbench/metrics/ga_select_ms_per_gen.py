"""GA host loop: milliseconds per generation in survivor selection
(``ga.select`` over ``ga.generation`` calls, in the window's completed
searches)."""
from chipbench.phases import calls, seconds, window_phases


def read(rec):
    ph, _ = window_phases(rec)
    if not ph or not calls(ph, "ga.generation"):
        return None
    return 1e3 * seconds(ph, "ga.select") / calls(ph, "ga.generation")
