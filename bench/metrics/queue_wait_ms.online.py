"""Mean wait from a request's due time to the start of the step that
served it (the batcher's window and the steps ahead of it), in ms."""
import numpy as np


def value(run):
    wait = (run.started - run.due)[run.counted]
    wait = wait[~np.isnan(wait)]
    return float(np.mean(wait)) * 1e3 if len(wait) else None
