"""bench/run.py refuses to run anywhere but on a TPU, and prints no result."""
import os
import subprocess
import sys

from bench import spec


def test_run_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "vhost-64b.closed", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=spec.ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert "metrics" not in proc.stdout and proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr
