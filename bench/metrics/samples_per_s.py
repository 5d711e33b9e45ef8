"""Samples completed in the window over the window's length."""


def read(run):
    if run.samples_in_window is None or not run.window_s:
        return None
    return run.samples_in_window / run.window_s
