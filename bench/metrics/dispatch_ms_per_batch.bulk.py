"""Host time to dispatch the jitted pipeline per served batch in the
bulk cells: the program's ``dispatch`` span (bucket pad, parameter
pytree, enqueue; inside ``exec``), mean in ms (moves ``img_per_s``)."""
import spans


def value(run):
    return spans.mean_ms(run, "dispatch")
