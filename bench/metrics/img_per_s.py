"""Images whose answers reached the host in the window, per second of it."""


def value(run):
    return run.images_in_window() / run.window_s
