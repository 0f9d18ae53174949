"""Modelled-clock bookkeeping per served batch in the bulk cells: the
program's ``telemetry`` span (``record_batch`` and the hardware
mirror, inside ``epilogue``), mean in ms (moves ``img_per_s``)."""
import spans


def value(run):
    return spans.mean_ms(run, "telemetry")
