"""``bench/run.py`` refuses to run without a TPU and prints no result."""
import os
import subprocess
import sys

from bench import harness as H


def test_run_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cell = H.benchmark()["workloads"][0]["name"]
    p = subprocess.run([sys.executable, os.path.join(H.BENCH, "run.py"),
                        "--workload", cell, "--seed", str(2 ** 31 + 7),
                        "--seconds", "1", "--trace", "0"],
                       capture_output=True, text=True, env=env, cwd=H.ROOT,
                       timeout=300)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert not any(line.startswith("{") for line in p.stdout.splitlines())
