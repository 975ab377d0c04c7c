"""Rule registry + findings. Rule IDs are STABLE: baselines, allow()
comments, and test fixtures reference them, so an ID is never renumbered
or reused — a retired rule keeps its row with ``retired=True``."""

from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Dict, Optional


@dataclass(frozen=True)
class Rule:
    id: str
    pass_name: str          # determinism | lockorder | excepts | tracehygiene | observatory | meta
    title: str
    description: str
    retired: bool = False


RULES: Dict[str, Rule] = {
    r.id: r for r in (
        Rule("DET001", "determinism",
             "global random in decision path",
             "Decision-path code must draw from a seeded, name-salted "
             "PRNG stream (random.Random(seed ^ crc32(name)), the "
             "faults.py pattern), never the process-global random module "
             "— a global draw couples replay determinism to every other "
             "caller's draw ordering."),
        Rule("DET002", "determinism",
             "time.time() in decision path",
             "Interval/deadline arithmetic must use time.monotonic() "
             "(wall clock steps under NTP); time.time() is allowed only "
             "for user-facing timestamps, with an allow() reason."),
        Rule("DET003", "determinism",
             "unordered set iteration in decision path",
             "Iterating a set drives decisions in hash order, which "
             "varies across processes (PYTHONHASHSEED for str keys). "
             "Iterate sorted(s) or a list/dict instead."),
        Rule("LCK001", "lockorder",
             "lock-order cycle",
             "The static lock graph contains a cycle: two lock-holding "
             "regions can acquire the participating locks in opposite "
             "orders, which is a deadlock waiting for the right "
             "interleaving."),
        Rule("LCK002", "lockorder",
             "lock acquisition inverts canonical order",
             "A lock-holding region acquires a lock ranked EARLIER in "
             "the committed canonical order (tools/nomadlint/"
             "lock_order.json). Either restructure, or regenerate the "
             "order with --write-lock-order if the canonical order "
             "legitimately changed."),
        Rule("LCK003", "lockorder",
             "lock order drift",
             "The committed lock_order.json does not match a fresh "
             "computation over the current tree (locks added/removed or "
             "graph edges changed). Regenerate with --write-lock-order."),
        Rule("EXC001", "excepts",
             "broad except swallows hot-path error",
             "An `except Exception` in raft/FSM/plan/worker hot paths "
             "must re-raise, count a telemetry metric, or fire a fault "
             "site — a silently eaten raft/FSM error is a state "
             "divergence with no forensics."),
        Rule("EXC002", "excepts",
             "bare except in hot path",
             "Bare `except:` also catches KeyboardInterrupt/SystemExit; "
             "catch a type, or at minimum `except Exception` with "
             "telemetry."),
        Rule("TRC001", "tracehygiene",
             "Python control flow on traced value",
             "`if`/`while`/`for` on a traced argument inside a jitted "
             "function fails under jit or silently burns a retrace per "
             "distinct value; use lax.cond/select/fori_loop or hoist the "
             "branch to a static argument."),
        Rule("TRC002", "tracehygiene",
             "unstable or non-hashable static argument",
             "A static_argnums/static_argnames argument fed an unhashable "
             "value (list/dict/set) raises at call time; one fed an "
             "unstable value (fresh container/varying scalar per call) "
             "recompiles every call."),
        Rule("TRC003", "tracehygiene",
             "jitted function closes over mutable module state",
             "A jit-decorated function reading module state that is "
             "mutated elsewhere bakes the traced-time value into the "
             "compiled executable — later mutations are silently "
             "ignored (the ops/fit.py retrace-counter hazard class)."),
        Rule("OBS001", "observatory",
             "decision path imports the capacity observatory",
             "The capacity observatory (nomad_tpu/capacity.py) is a "
             "READ-ONLY observer of cluster state (Omega's shared-state "
             "posture): scheduler, solver, state, raft, and server "
             "decision paths must never import it — a placement that "
             "consults the observer's books couples decisions to poll "
             "timing and voids the decision-invariance contract the "
             "churn-frag-200 digest arm pins. Only the composition "
             "roots (server/server.py wiring, api/ exposition) may "
             "construct or read it."),
        Rule("META001", "meta",
             "allow() without a reason",
             "`# nomadlint: allow(RULE)` must carry `-- <reason>`: an "
             "unexplained suppression hides the invariant it waives."),
        Rule("META002", "meta",
             "allow() for unknown rule",
             "The allow() names a rule id that does not exist — likely a "
             "typo that suppresses nothing."),
    )
}


@dataclass
class Finding:
    rule_id: str
    file: str               # repo-relative path
    line: int
    qualname: str           # enclosing module/class/function for stable keys
    message: str
    # Baseline identity deliberately excludes the line number: unrelated
    # edits above a grandfathered finding must not read as drift. The
    # stripped source line disambiguates repeated findings in one scope.
    snippet: str = ""
    extra: dict = field(default_factory=dict)

    def key(self) -> str:
        return f"{self.rule_id}|{self.file}|{self.qualname}|{self.snippet}"

    def render(self) -> str:
        return (f"{self.file}:{self.line}: {self.rule_id} "
                f"[{self.qualname}] {self.message}")


# -- allow() directives ------------------------------------------------------

# `# nomadlint: allow(RULE1, RULE2) -- reason` ; the reason is mandatory
# and checked by META001. Matches anywhere in a source line so it can ride
# a trailing comment.
_ALLOW_RE = re.compile(
    r"#\s*nomadlint:\s*allow\(([A-Za-z0-9_,\s]+)\)(?:\s*--\s*(.+?))?\s*$"
)


@dataclass
class Allow:
    rules: tuple
    reason: Optional[str]
    line: int


def parse_allow(source_line: str, lineno: int) -> Optional[Allow]:
    m = _ALLOW_RE.search(source_line)
    if not m:
        return None
    rules = tuple(r.strip() for r in m.group(1).split(",") if r.strip())
    reason = m.group(2).strip() if m.group(2) else None
    return Allow(rules=rules, reason=reason or None, line=lineno)
