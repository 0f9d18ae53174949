"""Host time per served batch in the bulk cells: the program's ``batch``
span less its ``exec`` child, mean in ms (moves ``img_per_s``)."""


def value(run):
    return run.host_ms_per_batch()
