"""GA host loop and session: milliseconds per generation of the window
spent outside population scoring (and outside starting and stopping the
profiler)."""


def read(rec):
    w = rec["window"]
    c = w.get("counters")
    if not c or not w.get("generations"):
        return None
    host_s = (w.get("elapsed_s", w["window_s"]) - c["batch_time_s"]
              - w.get("trace_overhead_s", 0.0))
    return 1e3 * host_s / w["generations"]
