"""Host time to dispatch the jitted pipeline per served batch in the
online cells: the program's ``dispatch`` span (bucket pad, parameter
pytree, enqueue; inside ``exec``), mean in ms (moves ``p50_ms``)."""
import spans


def value(run):
    return spans.mean_ms(run, "dispatch")
