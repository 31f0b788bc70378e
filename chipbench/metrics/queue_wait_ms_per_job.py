"""Job queue: mean milliseconds a job of the window waited for a worker,
from the daemon's ``daemon.queue_wait_s`` and ``daemon.queue_waits``
counters."""


def read(rec):
    q = rec["window"].get("queue_wait")
    if not q or not q["waits"]:
        return None
    return 1e3 * q["seconds"] / q["waits"]
