"""Host time per served batch in the online cells: the program's ``batch``
span less its ``exec`` child, mean in ms (moves ``p50_ms``)."""


def value(run):
    return run.host_ms_per_batch()
