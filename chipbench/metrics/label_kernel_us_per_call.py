"""Label kernel: device microseconds per call in the traced sub-window,
from the profiler's module events."""

KERNEL = "jit__labels_jax"


def read(rec):
    tr = rec.get("trace")
    if not tr:
        return None
    k = tr["kernels"].get(KERNEL)
    if not k or not k["calls"]:
        return None
    return 1e6 * k["seconds"] / k["calls"]
