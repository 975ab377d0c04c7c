"""Observatory pass (OBS001): the observatories are read-only.

``nomad_tpu/capacity.py`` (the capacity observatory),
``nomad_tpu/raft_observe.py`` (the raft & recovery observatory),
``nomad_tpu/read_observe.py`` (the read-path observatory) and
``nomad_tpu/profile_observe.py`` (the runtime self-observatory) observe
cluster state through change logs and plain-data books, and must stay
invisible to every decision path — the decision-invariance proofs (the
churn-frag-200 observatory-off contrast arm's digest equality; the
steady-10k digest staying byte-equal with the raft observatory on; the
read-storm reads-off contrast arm's digest equality) only mean
something if no placement, verify, or apply path can even *reach* an
observer's books. This pass enforces that
statically: any ``import`` of an observatory module (module-level or
function-local, plain or from-import) inside the decision scope is a
finding.

The composition roots are allowlisted by path: ``server/server.py``
constructs and starts the observers (lifecycle wiring only — the
ServerConfig parse and start/stop calls), and the exposition layer
(``api/``, ``bundle.py``) reads snapshots. Everything else in
scheduler/, server/, state/, raft/, tpu/, and ops/ is barred.
"""

from __future__ import annotations

import ast
from typing import List

from tools.nomadlint.project import Project, qualname_of
from tools.nomadlint.registry import Finding

# Where decisions are made: the solve path (scheduler/tpu/ops), the
# apply path (server/state/raft). The broader DET001 decision scope
# minus the leaf modules that cannot plausibly hold an import of the
# observatory's caliber (structs/network/events/faults are kept IN —
# cheap to check, and events.py importing the accountant would be just
# as much of a layering break).
OBSERVATORY_SCOPE = (
    "nomad_tpu/scheduler",
    "nomad_tpu/server",
    "nomad_tpu/state",
    "nomad_tpu/raft",
    "nomad_tpu/tpu",
    "nomad_tpu/ops",
    "nomad_tpu/structs.py",
    "nomad_tpu/network.py",
    "nomad_tpu/events.py",
    "nomad_tpu/faults.py",
)

# The one legitimate construction site: the server's composition root
# builds the observers and starts/stops them (slo monitor, express
# lane, capacity accountant, raft observatory). It may not READ the
# books either — but that is a review concern; the static bar is the
# import, and the composition root needs exactly that.
COMPOSITION_ROOTS = ("nomad_tpu/server/server.py",)

TARGET_MODULES = ("nomad_tpu.capacity", "nomad_tpu.raft_observe",
                  "nomad_tpu.read_observe", "nomad_tpu.profile_observe")
_TARGET_LEAVES = tuple(m.rsplit(".", 1)[1] for m in TARGET_MODULES)


def _match(name: str):
    for target in TARGET_MODULES:
        if name == target or name.startswith(target + "."):
            return target
    return None


def run(project: Project) -> List[Finding]:
    out: List[Finding] = []
    for mod in project.scoped(OBSERVATORY_SCOPE):
        if mod.relpath in COMPOSITION_ROOTS:
            continue
        findings: List[Finding] = []
        for node in ast.walk(mod.tree):
            hit = None
            if isinstance(node, ast.Import):
                for alias in node.names:
                    if _match(alias.name):
                        hit = alias.name
            elif isinstance(node, ast.ImportFrom):
                m = node.module or ""
                if _match(m):
                    hit = m
                elif m == "nomad_tpu":
                    for alias in node.names:
                        if alias.name in _TARGET_LEAVES:
                            hit = f"nomad_tpu.{alias.name}"
            if hit is not None:
                findings.append(Finding(
                    "OBS001", mod.relpath, node.lineno,
                    qualname_of(node, mod.modname),
                    f"decision-path module imports {hit} — an "
                    "observatory must stay invisible to scheduler/apply "
                    "paths (read-only observer contract)",
                    snippet=mod.snippet(node.lineno),
                ))
        out.extend(project.filter_allowed(mod, findings))
    return out
