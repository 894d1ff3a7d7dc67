"""Without a TPU the command ends with a non-zero exit and no result."""
import os
import subprocess
import sys

from chipbench import bench


def test_cpu_machine_exits_nonzero_with_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(bench.BENCH_DIR / "run.py"), "--workload",
         "minitron-4b.decode", "--seed", "2147483701", "--seconds", "1",
         "--trace", "0"], cwd=str(bench.ROOT), env=env, capture_output=True,
        text=True, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr
