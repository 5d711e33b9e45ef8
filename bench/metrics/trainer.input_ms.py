"""Mean duration of the program's ``train.batch`` spans (making the
step's images and latents) that start in the window, on the main
thread."""
from harness import trace


def read(run):
    if run.trace is None:
        return None
    lo, hi = run.trace.window
    durs = [e - s for n, s, e in trace._main_thread(run.trace)
            if n == "train.batch" and lo <= s < hi]
    if not durs:
        return None
    return sum(durs) / len(durs) / 1e6
