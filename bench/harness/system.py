"""The system under test as the harness builds it, and the plain reference.

The program is imported from ``src/``; the reference comes from the file
the configuration names, beside it under ``bench/configs/``.
"""
from __future__ import annotations

import contextlib
import importlib.util
import sys

from harness import loadgen
from harness.registry import BENCH_DIR, ROOT


def import_program() -> None:
    src = str(ROOT / "src")
    if src not in sys.path:
        sys.path.insert(0, src)


def reference(cfg: dict):
    """The configuration's plain reference module."""
    path = BENCH_DIR / "configs" / cfg["reference"]
    spec = importlib.util.spec_from_file_location(
        f"bench_reference_{path.stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def program_config(cfg: dict):
    """The configuration as the program's ``GANConfig``."""
    import_program()
    from repro.models.gan import GANConfig

    return GANConfig(cfg["name"], int(cfg["z_dim"]),
                     tuple(tuple(int(v) for v in layer)
                           for layer in cfg["layers"]),
                     kernel=int(cfg["kernel"]), padding=int(cfg["padding"]))


def generator_weights(ref, cfg: dict, seed: int):
    """Generator weights on the device, from the seed, in one jitted call."""
    import jax

    key = jax.random.key(loadgen.jax_seed(seed))
    return jax.jit(lambda k: ref.init_generator(k, cfg))(key)


def gan_weights(ref, cfg: dict, seed: int):
    """Generator and discriminator weights, from the seed, in one call."""
    import jax

    def both(k):
        kg, kd = jax.random.split(k)
        return ref.init_generator(kg, cfg), ref.init_discriminator(kd, cfg)

    return jax.jit(both)(jax.random.key(loadgen.jax_seed(seed)))


def annotate(on: bool):
    """``TraceAnnotation`` in a traced run; a no-op otherwise."""
    if on:
        import jax

        return jax.profiler.TraceAnnotation
    return lambda name: contextlib.nullcontext()
