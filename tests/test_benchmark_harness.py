"""The benchmark harness's own tests (benchmark/tests/test_harness.py),
collected into tier-1 under their own names: a change that renames an
event, a span or a counter the harness reads fails here, on the CPU,
before a chip run reports ``output_malformed``. Nothing is defined here;
edit the cases where they live."""

from benchmark.tests.test_harness import *  # noqa: F401,F403
