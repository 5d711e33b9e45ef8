"""Seconds from the process's start to the window's: start-up, weights,
warm-up and, in a run that compiles, compilation."""


def read(run):
    return run.setup_s
