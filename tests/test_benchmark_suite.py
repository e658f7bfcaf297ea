"""The benchmark's own tests (``benchmark/tests``: the harness, the
trace reductions, the kinds at a tiny size on the CPU) run with the
repository's tests, in one subprocess: a program change that breaks the
yardstick's tiny runs is then seen here and not first on the chip
(PERF.md section 7, left by PR 35 for the next PR that may touch
``tests/``)."""

import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
LIMIT_S = 600


def test_the_benchmarks_own_tests_pass():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = subprocess.run(
        [sys.executable, "-m", "pytest", "benchmark/tests", "-q", "-x",
         "-p", "no:cacheprovider"],
        cwd=ROOT, env=env, timeout=LIMIT_S, capture_output=True, text=True)
    assert done.returncode == 0, done.stdout[-4000:] + done.stderr[-2000:]
