"""Population scoring: milliseconds of the engine's own clock per batch
over the window's searches (``Evaluator.population().stats()``: the batch
blocks on the labels it reads back, so device time is inside)."""


def read(rec):
    c = rec["window"].get("counters")
    if not c or not c["batches"]:
        return None
    return 1e3 * c["batch_time_s"] / c["batches"]
