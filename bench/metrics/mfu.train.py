"""Steps per second times the FLOPs one G+D step requires (no
recomputation counted), over the chip's peak."""
from harness import work


def read(run):
    if not run.steps:
        return None
    flops = (run.steps / run.window_s
             * work.train_step_flops(run.cell.config,
                                     run.cell.traffic["global_batch"]))
    return work.percent(flops, run.peaks["flops_per_s"] * run.cell.chips)
