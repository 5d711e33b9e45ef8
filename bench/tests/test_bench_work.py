import benchpath  # noqa: F401
import pytest

from harness import registry, system, work


def config(name):
    return registry.load_json(registry.BENCH_DIR / "configs" / f"{name}.json")


@pytest.mark.parametrize("n_in,k,cin,cout,p", [
    (4, 4, 2048, 1024, 2), (32, 4, 128, 3, 2), (7, 3, 5, 6, 1),
    (5, 5, 3, 2, 0), (6, 4, 8, 8, 1)])
def test_tconv_macs_match_the_program_count(n_in, k, cin, cout, p):
    system.import_program()
    from repro.core.segregation import flop_count

    assert work.tconv_macs(n_in, k, cin, cout, p) == flop_count(
        n_in, k, cin, cout, p, method="segregated")


@pytest.mark.parametrize("name,gflop", [("ebgan", 7.52), ("dcgan", 0.822)])
def test_generator_flops_per_sample(name, gflop):
    assert work.generator_flops(config(name)) / 1e9 == pytest.approx(
        gflop, rel=0.01)


def test_generator_flops_match_the_program_without_epilogue():
    system.import_program()
    from repro.models.gan import generator_flops

    for name in ("ebgan", "dcgan"):
        cfg = config(name)
        h0, c0, _ = cfg["layers"][0]
        proj = cfg["z_dim"] * h0 * h0 * c0
        program = generator_flops(system.program_config(cfg),
                                  method="segregated",
                                  include_epilogue=False)
        assert work.generator_flops(cfg) == 2 * (program + proj)


def test_least_time_is_bandwidth_bound_for_ebgan_tail():
    peaks = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    cfg = config("ebgan")
    t16 = work.generator_least_s(cfg, 16, peaks)
    # the last layer alone moves 16 * (128*128*64 + 256*256*64) fp32
    assert t16 > 16 * 4 * (128**2 * 64 + 256**2 * 64) / 819e9
    assert work.generator_least_s(cfg, 1, peaks) < t16


def test_train_step_flops_count_each_pass_once():
    cfg = config("dcgan")
    g = work.generator_flops(cfg) / 2
    d = sum(work.discriminator_macs(cfg))
    assert work.discriminator_macs(cfg)[0] == 32 * 32 * 16 * 3 * 64
    flops = work.train_step_flops(cfg, 128)
    # between three and four generator passes and under eight of D's
    assert 2 * 128 * (3 * g + 6 * d) < flops < 2 * 128 * (4 * g + 8 * d)


def test_percent_never_invents_a_zero_share():
    assert work.percent(1.0, 0.0) is None
    assert work.percent(1.0, 4.0) == 25.0
