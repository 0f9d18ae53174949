"""Host-to-device transfer time per served batch in the bulk cells: the
program's ``h2d`` span (the batch's one transfer in ``CNNServer.step``,
inside ``stack``), mean in ms (moves ``img_per_s``)."""
import spans


def value(run):
    return spans.mean_ms(run, "h2d")
