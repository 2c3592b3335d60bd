"""The entry refuses to run without a TPU, and prints no result then."""

import os
import subprocess
import sys

from benchmarks.chip import harness


def test_entry_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [
            sys.executable,
            str(harness.CHIP_DIR / "run.py"),
            "--workload", "prod8.backlog",
            "--seed", str(2**31 + 3),
            "--seconds", "1",
            "--trace", "0",
        ],
        cwd=harness.REPO,
        env=env,
        capture_output=True,
        text=True,
        timeout=300,
    )
    assert proc.returncode != 0
    assert proc.stdout == ""
    assert "not run" in proc.stderr
