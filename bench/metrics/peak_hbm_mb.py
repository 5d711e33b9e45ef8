"""Peak device memory of the fullest chip in MB, read after the window and
before the correctness reference runs."""


def read(run):
    peak = run.device.get("memory_peak_bytes")
    return peak / 1e6 if peak else None
