"""Device time outside the Pallas kernels (XLA's depthwise, quantize
scales, pads, phase splits), per image answered in the traced window."""


def value(run):
    n = run.images_in_window()
    if run.trace is None or not n:
        return None
    return 1e3 * (run.trace.busy_s - run.trace.pallas_s) / n
