"""GA host loop: percent of generations whose top-up was scored in one
call with the next generation's offspring (``ga.ahead`` over
``ga.generation`` calls, in the window's completed searches)."""
from chipbench.phases import calls, window_phases


def read(rec):
    ph, _ = window_phases(rec)
    if not ph or not calls(ph, "ga.generation"):
        return None
    return 100.0 * calls(ph, "ga.ahead") / calls(ph, "ga.generation")
