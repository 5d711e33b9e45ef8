"""The window's length over the training steps completed in it."""


def read(run):
    if not run.steps:
        return None
    return run.window_s / run.steps * 1e3
