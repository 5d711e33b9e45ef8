"""The per-layer metrics that read the program's own spans, each on a
small synthetic trace: the number, and ``None`` where there is nothing to
read."""
import types

import benchpath  # noqa: F401
import pytest

from harness import registry, trace

US = 1000  # the trace's clock counts ns; the events below are in us


def events(*evs):
    return [(n, s * US, e * US) for n, s, e in evs]


def run_of(host, dispatches=None):
    tr = trace.Trace(window=(0, 1000 * US), ops={}, modules={}, host=host)
    return types.SimpleNamespace(trace=tr, dispatches=dispatches)


SERVE = {
    "/host:CPU/main": events(
        ("bench.window", 0, 1000),
        # a dispatch that started before the window
        ("serve.dispatch", -50, 5),
        ("engine.step", 10, 100), ("serve.schedule", 10, 20),
        ("serve.pack", 20, 25), ("serve.dispatch", 25, 85),
        ("serve.readback", 70, 80), ("serve.slice", 85, 95),
        # a step that decided not to flush
        ("engine.step", 200, 230), ("serve.schedule", 200, 230),
        ("engine.step", 290, 350), ("serve.schedule", 290, 295),
        ("serve.pack", 295, 300), ("serve.dispatch", 300, 340),
        ("serve.slice", 340, 345),
        # the last dispatch of the window; its slice falls after it
        ("engine.step", 900, 1100), ("serve.schedule", 900, 910),
        ("serve.pack", 910, 915), ("serve.dispatch", 915, 1005),
        ("serve.slice", 1005, 1015)),
    # another thread's spans are not the engine's loop
    "/host:CPU/other": events(("serve.schedule", 0, 500),
                              ("serve.dispatch", 0, 500))}

TRAIN = {
    "/host:CPU/main": events(
        ("bench.window", 0, 1000),
        # a step that started before the window
        ("train.step", -40, 5), ("train.batch", -40, -30),
        ("train.step_fn", -30, 5), ("train.sync", -20, 0),
        ("trainer.run", 5, 1080),
        ("train.step", 10, 110), ("train.batch", 10, 30),
        ("train.step_fn", 30, 100), ("train.sync", 50, 95),
        ("train.step", 120, 200), ("train.batch", 120, 135),
        ("train.step_fn", 135, 195), ("train.sync", 140, 190),
        # the last step of the window ends after it
        ("train.step", 950, 1080), ("train.batch", 950, 960),
        ("train.step_fn", 960, 1070), ("train.sync", 970, 1060)),
    "/host:CPU/other": events(("train.step", 0, 900),
                              ("train.batch", 0, 900))}

BARE = {"/host:CPU/main": events(("bench.window", 0, 1000),
                                 ("engine.step", 10, 100),
                                 ("trainer.run", 100, 900))}


def read(name, run):
    return registry.metric_reader(name)(run)


def test_engine_host_ms_is_host_span_time_per_dispatch():
    # schedule, pack and slice inside the window: 10+5+10 + 30 + 5+5+5
    # + 10+5 us over the three dispatches the window completed
    got = read("engine.host_ms", run_of(SERVE, {4: 2, 8: 1}))
    assert got == pytest.approx(85 / 3 / 1000)
    assert read("engine.host_ms", run_of(SERVE, {})) is None
    assert read("engine.host_ms", run_of(SERVE, None)) is None
    assert read("engine.host_ms", run_of(BARE, {4: 1})) is None
    assert read("engine.host_ms",
                types.SimpleNamespace(trace=None, dispatches={4: 1})) is None


def test_engine_dispatch_ms_is_the_median_dispatch_in_the_window():
    # 60, 40 and 90 us start in the window; the one before it does not
    got = read("engine.dispatch_ms", run_of(SERVE, {4: 2, 8: 1}))
    assert got == pytest.approx(0.060)
    assert read("engine.dispatch_ms", run_of(BARE)) is None
    assert read("engine.dispatch_ms",
                types.SimpleNamespace(trace=None)) is None


def test_trainer_host_ms_is_each_step_less_its_sync():
    # (100 - 45), (80 - 50) and (130 - 90) us
    got = read("trainer.host_ms", run_of(TRAIN))
    assert got == pytest.approx((55 + 30 + 40) / 3 / 1000)
    assert read("trainer.host_ms", run_of(BARE)) is None
    assert read("trainer.host_ms", types.SimpleNamespace(trace=None)) is None


def test_trainer_host_ms_counts_a_step_without_sync_whole():
    host = {"/host:CPU/main": events(("bench.window", 0, 1000),
                                     ("train.step", 10, 60),
                                     ("train.step", 100, 200),
                                     ("train.sync", 150, 190))}
    got = read("trainer.host_ms", run_of(host))
    assert got == pytest.approx((50 + 60) / 2 / 1000)


def test_trainer_input_ms_is_the_mean_batch_in_the_window():
    got = read("trainer.input_ms", run_of(TRAIN))
    assert got == pytest.approx((20 + 15 + 10) / 3 / 1000)
    assert read("trainer.input_ms", run_of(BARE)) is None
    assert read("trainer.input_ms", types.SimpleNamespace(trace=None)) is None
