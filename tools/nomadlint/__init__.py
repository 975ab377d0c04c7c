"""nomadlint: project-specific static analysis for the tpu-nomad tree.

Five AST-based passes encode the invariants the control plane's
correctness story rests on but nothing previously *checked*:

- **determinism** (DET0xx): scheduler / FSM / plan / simcluster decision
  paths must not draw from the global ``random`` module, stamp intervals
  with ``time.time()``, or iterate unordered sets — the seed-replay
  contract (simcluster event digests, fuzz families) only holds when every
  source of nondeterminism is a name-salted seeded stream (the
  ``faults.py`` pattern) or ``time.monotonic()``.
- **lockorder** (LCK0xx): extracts the whole-program lock graph (which
  locks each function acquires, which lock-holding regions call into
  which modules), computes a canonical acquisition order, and fails on
  cycles or edges that invert the committed order. The static result is
  validated dynamically by ``telemetry.LockWatchdog`` under tests.
- **excepts** (EXC0xx): no bare/broad ``except`` in raft append/apply,
  FSM, plan commit, and worker loops unless the handler re-raises,
  counts a telemetry metric, or fires a fault site — a swallowed raft
  error is a silent divergence, not a recovery.
- **tracehygiene** (TRC0xx): in ``tpu/`` and ``ops/``, Python control
  flow on traced values, unstable ``static_argnums``, and jitted
  functions closing over mutable module state — the retrace hazards
  ``ops/fit.py``'s jit_trace counters were added to catch at runtime.
- **observatory** (OBS0xx): the capacity observatory
  (``nomad_tpu/capacity.py``) is a read-only observer — scheduler /
  solver / state / raft / server decision paths must not import it;
  only the server composition root may construct it.

Findings are suppressed inline with ``# nomadlint: allow(RULE) -- reason``
(the reason is mandatory: an unexplained suppression is itself a finding,
META001) or grandfathered in the committed ``baseline.json``. Run as a
tier-1 gate: ``python -m tools.nomadlint --baseline``.
"""

from __future__ import annotations

from tools.nomadlint.registry import Finding, Rule, RULES  # noqa: F401
from tools.nomadlint.project import Project  # noqa: F401


def run_passes(project: "Project"):
    """Run all passes over ``project`` and return the findings,
    sorted for stable output/baseline comparison."""
    from tools.nomadlint import (
        determinism,
        excepts,
        lockorder,
        observatory,
        tracehygiene,
    )

    findings = []
    findings.extend(determinism.run(project))
    findings.extend(lockorder.run(project))
    findings.extend(excepts.run(project))
    findings.extend(tracehygiene.run(project))
    findings.extend(observatory.run(project))
    findings.extend(project.meta_findings())
    return sorted(findings, key=lambda f: (f.file, f.line, f.rule_id))
