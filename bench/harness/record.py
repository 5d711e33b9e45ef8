"""What one run measured, handed to every metric reader."""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass
class Run:
    cell: object                 # registry.Cell
    seed: int
    seconds: float
    peaks: dict
    device: dict = dataclasses.field(default_factory=dict)
    setup_s: float | None = None
    window_s: float | None = None
    attempted: int = 0
    failed: int = 0
    # serving
    latencies_s: np.ndarray | None = None   # per request due in the window
    samples_in_window: int | None = None
    lateness_s: np.ndarray | None = None
    queue_wait_s: np.ndarray | None = None
    rows_real: int | None = None
    rows_padded: int | None = None
    dispatches: dict | None = None          # bucket -> calls in the window
    # training
    steps: int | None = None
    # --trace 1
    trace: object | None = None             # trace.Trace
    # the correctness readings: name -> (value, limit); a reading passes
    # when it is at most its limit
    checks: dict = dataclasses.field(default_factory=dict)
    notes: dict = dataclasses.field(default_factory=dict)

    @property
    def correct(self) -> bool:
        return bool(self.checks) and all(
            np.isfinite(v) and v <= lim for v, lim in self.checks.values())
