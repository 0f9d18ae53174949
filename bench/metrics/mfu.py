"""The whole served step's share of the chip's int8 peak: the
configuration's operations per image (2 x every layer's MACs) times the
images answered in the traced window, over the window times the peak."""


def value(run):
    if run.trace is None or not run.trace.window_s:
        return None
    ops = run.ops_per_image * run.images_in_window()
    return 100.0 * ops / (run.trace.window_s * run.peaks["int8_ops_per_s"])
