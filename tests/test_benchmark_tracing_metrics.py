"""The benchmark's per-layer metric tests
(benchmark/tests/test_tracing_metrics.py), collected into tier-1 under
their own names; see tests/test_benchmark_harness.py. A file of its own
so that ``--dist loadfile`` gives the traced rehearsals their own
worker."""

import os
import subprocess
import sys

import pytest

from benchmark.tests.test_tracing_metrics import *  # noqa: F401,F403
from benchmark.tests.test_tracing_metrics import NEW, ROOT


@pytest.mark.parametrize("workload", sorted(NEW))
def test_traced_rehearsal_reports_the_new_metrics(workload):  # noqa: F811
    """The case of this name, run in an interpreter of its own. It holds
    ``setup_device_acquire_s`` above zero, which is true of a process
    that reaches the device itself, as the benchmark's does; a tier-1
    worker has had JAX up since an earlier file, reaches it in no time
    and reads 0.0."""
    case = ("benchmark/tests/test_tracing_metrics.py::"
            f"test_traced_rehearsal_reports_the_new_metrics[{workload}]")
    done = subprocess.run(
        [sys.executable, "-m", "pytest", case, "-q", "-p", "no:cacheprovider",
         "-p", "no:xdist", "-p", "no:randomly"],
        cwd=ROOT, env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stdout[-4000:] + done.stderr[-2000:]
