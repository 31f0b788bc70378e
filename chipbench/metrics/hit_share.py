"""Queue and store dedup: share of the jobs due in the window answered
without a search of their own (store hits and jobs attached to an
in-flight twin), from the daemon's ``daemon.jobs{outcome}`` counters."""


def read(rec):
    w = rec["window"]
    o = w.get("outcomes")
    if not o or not w["attempted"]:
        return None
    return 100.0 * (o.get("cache_hit", 0) + o.get("deduped", 0)) \
        / w["attempted"]
