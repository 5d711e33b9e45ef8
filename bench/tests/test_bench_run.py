import os
import subprocess
import sys

import benchpath


def test_the_harness_refuses_to_run_without_a_tpu():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    out = subprocess.run(
        [sys.executable, str(benchpath.BENCH / "run.py"), "--workload",
         "dcgan.serve.poisson", "--seed", "3", "--seconds", "1"],
        capture_output=True, text=True, env=env, timeout=120,
        cwd=benchpath.BENCH.parent)
    assert out.returncode == 2
    assert out.stdout == ""
    assert "no TPU" in out.stderr
