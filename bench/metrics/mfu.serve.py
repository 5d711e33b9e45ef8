"""Samples per second times the generator's FLOPs per sample, over the
chip's peak."""
from harness import work


def read(run):
    if run.samples_in_window is None or not run.window_s:
        return None
    rate = run.samples_in_window / run.window_s
    flops = rate * work.generator_flops(run.cell.config)
    return work.percent(flops, run.peaks["flops_per_s"] * run.cell.chips)
