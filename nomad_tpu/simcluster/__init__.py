"""In-process scale simulation & load generation for the control plane.

``simcluster`` drives the control plane as a whole, on the CPU, for the
tests: a :class:`~nomad_tpu.simcluster.simnode.SimFleet` of lightweight
node agents drives a real ``ClusterServer`` over real RPC — batched
registration, TTL heartbeats, alloc acknowledgement — while seeded
workload injectors (:mod:`~nomad_tpu.simcluster.workload`) push jobs
through the full register→heartbeat→eval→broker→worker→solver→
plan_apply→raft path, and the scenario runner
(:mod:`~nomad_tpu.simcluster.scenario`) watches the cluster event stream
(``nomad_tpu/events.py``) instead of poll-and-diff and emits one JSON
artifact per run with placements, p50/p95 plan latency, broker/
plan-queue depth peaks and heartbeat-timer load. Its timings are CPU
runs and no statement about speed rests on them: the yardstick is
``benchmark/run.py`` on the chip (``BENCHMARK.json``, ``PERF.md``).

Determinism posture: injectors are seeded PRNG streams in the style of
``nomad_tpu/faults.py`` (one stream per injector, salted by name), job
and node ids are derived from the seed, and the artifact carries a
canonical event digest (the multiset of per-key event-type sequences) so
a replay with the same seed is checkable against an earlier run.
"""

from nomad_tpu.simcluster.scenario import (  # noqa: F401
    SCENARIOS,
    ScenarioRunner,
    run_scenario,
)
from nomad_tpu.simcluster.simnode import SimFleet, sim_node  # noqa: F401
from nomad_tpu.simcluster.workload import (  # noqa: F401
    BatchBurstInjector,
    ExpressStreamInjector,
    NodeChurnInjector,
    SteadyServiceInjector,
    UpdateChurnInjector,
)

# Imported last (chaos builds on scenario + workload above); importing
# the compiler also registers the shipped chaos families in SCENARIOS.
from nomad_tpu.simcluster import chaos  # noqa: E402,F401
