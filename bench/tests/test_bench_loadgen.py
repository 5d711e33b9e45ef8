import benchpath  # noqa: F401
import numpy as np
import pytest

from harness import loadgen

MIX = {"kind": "open", "rate_rps": 300, "base_seed": 11, "size_p": 0.6667,
       "max_size": 8, "check_random": 10, "check_largest": 4}
BIG = 2**31 + 977


@pytest.mark.parametrize("seed", [0, 5, BIG])
def test_open_schedule_is_a_function_of_the_seed(seed):
    a, b = loadgen.open_schedule(MIX, seed, 2.0), loadgen.open_schedule(
        MIX, seed, 2.0)
    np.testing.assert_array_equal(a[0], b[0])
    np.testing.assert_array_equal(a[1], b[1])


def test_open_schedules_differ_by_seed_but_offer_the_same_work():
    due1, n1 = loadgen.open_schedule(MIX, 1, 2.0)
    due2, n2 = loadgen.open_schedule(MIX, BIG, 2.0)
    assert not np.array_equal(due1, due2) and not np.array_equal(n1, n2)
    assert len(due1) == len(due2) == 600
    np.testing.assert_array_equal(np.sort(n1), np.sort(n2))
    np.testing.assert_allclose(np.sort(np.diff(due1, prepend=0)),
                               np.sort(np.diff(due2, prepend=0)))


def test_open_schedule_shape():
    due, sizes = loadgen.open_schedule(MIX, 3, 2.0)
    assert np.all(np.diff(due) >= 0) and due[0] > 0
    assert due[-1] == pytest.approx(2.0)
    assert sizes.min() >= 1 and sizes.max() <= 8
    assert 1.3 < sizes.mean() < 1.7


def test_latents_and_samples_follow_the_seed():
    _, sizes = loadgen.open_schedule(MIX, 3, 1.0)
    z1, z2 = loadgen.latents(3, sizes, 100), loadgen.latents(4, sizes, 100)
    assert [len(z) for z in z1] == list(sizes)
    assert z1[0].dtype == np.float32 and z1[0].shape[1] == 100
    assert not np.array_equal(z1[0], z2[0])
    pick = loadgen.open_sample(MIX, 3, sizes)
    assert len(pick) >= MIX["check_random"]
    largest = np.argsort(-sizes, kind="stable")[:4]
    assert set(largest.tolist()) <= set(pick.tolist())
    assert not np.array_equal(pick, loadgen.open_sample(MIX, 4, sizes))


def test_closed_loop_latents_and_sample():
    mix = {"check_random": 3, "check_within": 40}
    a, b = loadgen.ClosedLatents(7, 16, 100), loadgen.ClosedLatents(7, 16, 100)
    np.testing.assert_array_equal(a.next(), b.next())
    assert not np.array_equal(a.next(), loadgen.ClosedLatents(8, 16,
                                                              100).next())
    s = loadgen.closed_sample(mix, 7)
    assert 0 in s and len(s) == 4 and max(s) < 40
    assert s == loadgen.closed_sample(mix, 7)
    assert s != loadgen.closed_sample(mix, BIG)


def test_jax_seed_fits_32_bits():
    for s in (0, 1, 2**31 - 1, 2**31 + 5, 2**40):
        assert 0 <= loadgen.jax_seed(s) < 2**32
    assert loadgen.jax_seed(2**31 + 5) != loadgen.jax_seed(2**31 + 6)


def test_lateness_summary():
    s = loadgen.lateness_summary([0.001, 0.002, 0.010])
    assert s["max_ms"] == pytest.approx(10.0)
    assert s["p50_ms"] == pytest.approx(2.0)
