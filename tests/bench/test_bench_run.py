"""bench/run.py refuses to run without a TPU, and prints no result."""

import os
import subprocess
import sys

import benchcells

RUN = os.path.join(benchcells.ROOT, "bench", "run.py")


def test_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, RUN, "--workload", "internlm2-1.8b.capture", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=benchcells.ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr
