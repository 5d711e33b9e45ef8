"""Share of the generator's roofline: the least time of the generator
calls the window completed (per layer the larger of segregated FLOPs over
the FLOP peak and input, weight and output bytes over HBM bandwidth)
over the device time of the generator's executables (``jit_run``)."""
from harness import trace, work


def read(run):
    if run.trace is None or not run.dispatches:
        return None
    device_s = trace.module_seconds(run.trace,
                                    lambda n: n.startswith("jit_run"))
    if not device_s:
        return None
    least = sum(calls * work.generator_least_s(run.cell.config, bucket,
                                               run.peaks)
                for bucket, calls in run.dispatches.items())
    return work.percent(least, device_s)
