"""Means over the program's ``Tracer`` spans of a traced run, for the
metric readers.  A run of a program without the span reads ``None``."""


def mean_ms(run, name: str):
    """Mean duration, in ms, of the program's spans called ``name`` over
    every batch the run served."""
    durs = [s.dur for s in run.spans if s.ph == "X" and s.name == name]
    return 1e3 * sum(durs) / len(durs) if durs else None


def batcher_wait_ms(run):
    """Mean wait of a request in the batcher, in ms: each ``batch``
    span's ``queue_wait_s`` (the mean over its requests, taken where the
    batch was formed) weighted by its ``size``."""
    waits = [(s.args["queue_wait_s"], s.args["size"]) for s in run.spans
             if s.ph == "X" and s.name == "batch" and "queue_wait_s" in s.args]
    n = sum(size for _, size in waits)
    return 1e3 * sum(w * size for w, size in waits) / n if n else None
