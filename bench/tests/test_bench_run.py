"""``bench/run.py`` refuses to run without a TPU and prints no result."""

import os
import subprocess
import sys

from bench import registry


def test_run_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join("bench", "run.py"), "--workload",
         "sw-queue.certify", "--seed", str(2**31 + 12345), "--seconds", "1",
         "--trace", "0"],
        cwd=registry.ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "TPU" in proc.stderr
