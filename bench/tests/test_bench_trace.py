import benchpath
import pytest

from harness import trace

# times in ns; one device, one host thread
SMALL = trace.Trace(
    window=(0, 100),
    ops={"/device:TPU:0": [("a", 10, 30), ("b", 25, 40), ("a", 60, 70),
                           ("c", 95, 120)]},
    modules={"/device:TPU:0": [("jit_run(1)", 10, 40), ("jit_other", 60, 70)]},
    host={"/host:CPU/main": [
        ("bench.window", 0, 100), ("engine.step", 5, 50),
        ("np.asarray(jax.Array)", 30, 50), ("loadgen.wait", 50, 60),
        ("engine.step", 60, 95)],
        "/host:CPU/other": [("np.asarray(jax.Array)", 45, 55)]})


def test_busy_is_the_union_of_ops_in_the_window():
    assert trace.busy_s(SMALL) == pytest.approx(45e-9)


def test_op_seconds_rank_ops_by_time_in_the_window():
    assert trace.op_seconds(SMALL) == [["a", 30e-9], ["b", 15e-9],
                                       ["c", 5e-9]]


def test_module_and_host_seconds():
    run = trace.module_seconds(SMALL, lambda n: n.startswith("jit_run"))
    assert run == pytest.approx(30e-9)
    assert trace.module_seconds(SMALL, lambda n: n == "nothing") is None
    assert trace.host_seconds(SMALL, "np.asarray(jax.Array)") == \
        pytest.approx(25e-9)
    assert trace.host_seconds(SMALL, "absent") is None


def test_idle_gaps_go_to_what_the_host_was_doing():
    gaps = dict(trace.idle_gaps(SMALL))
    assert gaps == pytest.approx({
        "engine.step": 30e-9, "engine.step/np.asarray(jax.Array)": 10e-9,
        "loadgen.wait": 10e-9, "other": 5e-9})


def test_no_device_no_busy():
    empty = trace.Trace(window=(0, 10), ops={}, modules={}, host={})
    assert trace.busy_s(empty) is None and trace.idle_gaps(empty) == []


def test_json_round_trip(tmp_path):
    p = tmp_path / "t.json"
    trace.save(SMALL, str(p))
    assert trace.load(str(p)) == SMALL


# 60 ms of a traced EB-GAN bulk window on one TPU v5 lite, as the
# reduction keeps it (events clipped to that span)
RECORDED = benchpath.BENCH / "tests" / "data" / "trace_ebgan_bulk.json"


def test_reduction_of_a_recorded_chip_trace():
    tr = trace.load(str(RECORDED))
    assert tr.window_s == pytest.approx(0.06)
    busy = trace.busy_s(tr)
    run = trace.module_seconds(tr, lambda n: n.startswith("jit_run"))
    copy = trace.host_seconds(tr, "np.asarray(jax.Array)")
    assert busy == pytest.approx(0.013875411)
    assert run == pytest.approx(0.013876183)
    assert copy == pytest.approx(0.03844742)
    assert busy < copy < tr.window_s
    top = trace.op_seconds(tr, 3)
    assert top[0][0].startswith("%transpose_conv2d_pair_pallas")
    gaps = trace.idle_gaps(tr)
    assert gaps[0][0] == "engine.step/np.asarray(jax.Array)"
    # every idle nanosecond goes to something the host was doing
    assert sum(s for _, s in gaps) == pytest.approx(tr.window_s - busy,
                                                    rel=1e-6)
