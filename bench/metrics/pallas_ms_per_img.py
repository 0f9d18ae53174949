"""Device time inside the Pallas kernels, per image answered in the
traced window."""


def value(run):
    n = run.images_in_window()
    if run.trace is None or not n or not run.trace.n_pallas:
        return None
    return 1e3 * run.trace.pallas_s / n
