"""Label kernel, seen from the host: milliseconds per engine batch from
the kernel's launch (padding, host-to-device copy, dispatch) to its labels
read back (``pop.labels.launch`` plus ``pop.labels.wait`` over
``pop.batch`` calls, in the window's completed searches).  Less
``label_kernel_us_per_call``, it leaves launch and transfer."""
from chipbench.phases import calls, seconds, window_phases


def read(rec):
    ph, _ = window_phases(rec)
    if not ph or not calls(ph, "pop.batch") \
            or not calls(ph, "pop.labels.wait"):
        return None
    wait = seconds(ph, "pop.labels.launch", "pop.labels.wait")
    return 1e3 * wait / calls(ph, "pop.batch")
