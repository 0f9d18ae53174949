"""Modelled-clock bookkeeping per served batch in the online cells: the
program's ``telemetry`` span (``record_batch`` and the hardware
mirror, inside ``epilogue``), mean in ms (moves ``p50_ms``)."""
import spans


def value(run):
    return spans.mean_ms(run, "telemetry")
