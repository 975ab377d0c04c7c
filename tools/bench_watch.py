"""SLO regression gate over the banked SIMLOAD artifact families.

    python tools/bench_watch.py --slo-gate

compares every family's newest banked round against its previous one (or
judges a first-round family absolutely against its declared objectives)
and exits non-zero on a regression: latency objectives, the device-solve
economy, recovery, read-path, read-lane, runtime and chaos gates. One JSON
verdict line per family and gate goes to stdout. This is the path
tools/tier1.py and release checks call.
"""

from __future__ import annotations

import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def log(event: str, **kw) -> None:
    print(json.dumps({"event": event, **kw}))


# ---------------------------------------------------------------------------
# SLO regression gate: banked SIMLOAD artifacts vs their previous round
# ---------------------------------------------------------------------------

# Latency-percentile regression tolerance: a new artifact that is inside
# its SLO threshold never fails the gate; one outside it fails only when
# it is ALSO >25% worse than the banked baseline (p50-scale numbers at
# ~20ms jitter a few percent run-to-run; 25% is a real regression).
SLO_GATE_TOLERANCE = 0.25


def _attribution_of(artifact: dict) -> dict:
    """A SIMLOAD artifact's latency percentiles in evaluate_artifact
    shape. Pre-r08 artifacts carry no ``latency_attribution`` — but their
    ``plan_latency_ms`` IS submit→placed (EvalUpdated(pending) → first
    PlanApplied, the same event anchors), so a banked r07 baseline still
    gates the placed-side objectives."""
    att = artifact.get("latency_attribution")
    if att:
        return att
    return {"submit_to_placed_ms": artifact.get("plan_latency_ms") or {}}


def _objectives_for(artifact: dict) -> dict | None:
    """Objective set for one artifact family: scenario-scoped overrides
    first (slo.SCENARIO_OBJECTIVES — e.g. churn-fragmentation's probe
    wave races a deregistration stop storm by design and is judged
    against its own declared bound, not the 250ms steady-state SLO),
    plus the express lane's own target (express_placed_p50_ms < 1ms)
    when the artifact carries express observations — the express-mix
    family gates ABSOLUTELY on its headline number instead of skipping
    it. None = the default set (evaluate_artifact's convention)."""
    from nomad_tpu.slo import (
        DEFAULT_OBJECTIVES,
        EXPRESS_OBJECTIVES,
        SCENARIO_OBJECTIVES,
    )

    objectives = SCENARIO_OBJECTIVES.get(artifact.get("scenario") or "")
    if _attribution_of(artifact).get("express_placed_ms"):
        return {**(objectives or DEFAULT_OBJECTIVES), **EXPRESS_OBJECTIVES}
    return objectives


def slo_gate(new_artifact: dict, baseline_artifact: dict,
             objectives: dict | None = None,
             tolerance: float = SLO_GATE_TOLERANCE) -> dict:
    """Gate a fresh SIMLOAD artifact against a banked baseline: for each
    SLO objective (nomad_tpu.slo; default set when ``objectives`` is
    None), FAIL when the new run misses an objective the baseline met, or
    when its observed percentile is outside the threshold AND more than
    ``tolerance`` worse than the baseline. Objectives neither run can
    observe (no samples) are reported, not failed."""
    from nomad_tpu.slo import evaluate_artifact

    new_checks = evaluate_artifact(_attribution_of(new_artifact), objectives)
    base_checks = {
        c["objective"]: c
        for c in evaluate_artifact(_attribution_of(baseline_artifact),
                                   objectives)
    }
    checks, ok = [], True
    for c in new_checks:
        base = base_checks.get(c["objective"], {})
        verdict = dict(c)
        verdict["baseline_ms"] = base.get("observed_ms")
        regressed = False
        if c["met"] is False:
            if base.get("met"):
                regressed = True          # objective newly broken
            elif (base.get("observed_ms")
                    and c["observed_ms"]
                    > base["observed_ms"] * (1.0 + tolerance)):
                regressed = True          # already-out objective worsened
        verdict["regressed"] = regressed
        ok = ok and not regressed
        checks.append(verdict)
    return {"ok": ok, "tolerance": tolerance, "checks": checks}


def _banked_simload_pairs() -> list:
    """(scenario, newest artifact path, previous-round path or None) for
    every banked ``SIMLOAD_<scenario>_s<seed>[_rNN].json`` family.
    Un-suffixed artifacts count as round 0. Single-round families (a
    freshly introduced scenario — e.g. overdrive-100k's first bank) pair
    with None: the gate then checks the artifact ABSOLUTELY against its
    declared objectives instead of skipping it silently."""
    import re

    fams: dict = {}
    for f in sorted(os.listdir(REPO)):
        m = re.match(r"SIMLOAD_(.+_s\d+?)(?:_r(\d+))?\.json$", f)
        if m:
            fams.setdefault(m.group(1), []).append(
                (int(m.group(2) or 0), os.path.join(REPO, f))
            )
    out = []
    for fam, rounds in sorted(fams.items()):
        rounds.sort()
        out.append((fam, rounds[-1][1],
                    rounds[-2][1] if len(rounds) >= 2 else None))
    return out


def slo_gate_absolute(new_artifact: dict,
                      objectives: dict | None = None) -> dict:
    """First-round gate (no banked baseline yet): every OBSERVED
    objective must be met outright. Unobserved objectives (no samples —
    e.g. no running acks in an ack_cap=0 scenario) are reported, not
    failed."""
    from nomad_tpu.slo import evaluate_artifact

    checks = []
    ok = True
    for c in evaluate_artifact(_attribution_of(new_artifact), objectives):
        verdict = dict(c)
        verdict["baseline_ms"] = None
        verdict["regressed"] = c["met"] is False
        ok = ok and not verdict["regressed"]
        checks.append(verdict)
    return {"ok": ok, "tolerance": None, "checks": checks}


# Solver-economy gate tolerance: the panel's device-time-per-placement
# is box-noise-sensitive (rider-attributed walls under coalescing), so
# the regression bar is deliberately loose — it exists to catch a real
# batching/padding regression (2x-class), not scheduler jitter.
SOLVER_GATE_TOLERANCE = 0.5


def solver_gate(new_artifact: dict, baseline_artifact: dict,
                tolerance: float = SOLVER_GATE_TOLERANCE) -> dict | None:
    """Gate the solver panel's measured-window economy newest-vs-
    previous: FAIL when device-time-per-placement worsened more than
    ``tolerance`` relative. Also reports the batch-width histogram and
    the amortized per-eval device wall (the cross-eval batching win) so
    a gate log shows WHERE a regression came from. None when either
    artifact predates the solver_panel window section."""
    new_w = (new_artifact.get("solver_panel") or {}).get("window") or {}
    base_w = (baseline_artifact.get("solver_panel") or {}).get(
        "window") or {}
    new_v = new_w.get("device_ms_per_placement")
    base_v = base_w.get("device_ms_per_placement")
    # `is None`, not truthiness: a legitimate 0.0 baseline (sub-precision
    # walls) must keep the gate armed, not read as a pre-panel artifact.
    if new_v is None or base_v is None:
        return None
    if not base_v:
        base_v = 1e-9  # zero baseline: any measurable cost is a regression
    regressed = new_v > base_v * (1.0 + tolerance)
    return {
        "ok": not regressed,
        "tolerance": tolerance,
        "device_ms_per_placement": new_v,
        "baseline_ms_per_placement": base_v,
        "batch_widths": new_w.get("batch_widths"),
        "equiv": new_w.get("equiv"),
    }


# Recovery-gate tolerance: restart downtime and replay rates are box-
# noise-sensitive (re-election jitter alone spans 150-300ms), so the
# newest-vs-previous bar is deliberately loose — it exists to catch a
# real recovery regression (2x-class), not scheduler jitter.
RECOVERY_GATE_TOLERANCE = 0.5


def recovery_gate(new_artifact: dict, baseline_artifact: dict | None,
                  tolerance: float = RECOVERY_GATE_TOLERANCE) -> dict | None:
    """Gate a restart-family artifact's recovery story. ABSOLUTE (every
    round, baseline or not): the mid-load leader kill must have lost
    nothing — ``placements_survived`` is the digest-survival contract,
    not a statistic. RELATIVE (newest-vs-previous when a prior bank
    carries a restart section): replay rate (entries/s) must not drop
    more than ``tolerance``, and time-to-serving must not grow more than
    ``tolerance``. None when the artifact has no restart section (not a
    restart family)."""
    raft = new_artifact.get("raft") or {}
    restart = raft.get("restart")
    if not restart:
        return None
    recovery = raft.get("recovery") or {}
    survived = restart.get("placements_survived") is True
    checks = [{
        "check": "placements_survived",
        "value": restart.get("placements_survived"),
        "baseline": None,
        "regressed": not survived,
    }]
    ok = survived
    base_raft = (baseline_artifact or {}).get("raft") or {}
    base_recovery = base_raft.get("recovery") or {}
    if base_raft.get("restart"):
        new_rate = recovery.get("replay_entries_per_s")
        base_rate = base_recovery.get("replay_entries_per_s")
        if new_rate is not None and base_rate:
            regressed = new_rate < base_rate * (1.0 - tolerance)
            checks.append({"check": "replay_entries_per_s",
                           "value": new_rate, "baseline": base_rate,
                           "regressed": regressed})
            ok = ok and not regressed
        new_tts = recovery.get("time_to_serving_ms")
        base_tts = base_recovery.get("time_to_serving_ms")
        if new_tts is not None and base_tts:
            regressed = new_tts > base_tts * (1.0 + tolerance)
            checks.append({"check": "time_to_serving_ms",
                           "value": new_tts, "baseline": base_tts,
                           "regressed": regressed})
            ok = ok and not regressed
    return {"ok": ok, "tolerance": tolerance, "checks": checks}


# Read-gate tolerance: serving latency under an impolite read fleet is
# box-noise-sensitive (GIL contention with the placement path is the
# scenario's POINT), so the newest-vs-previous bar is deliberately loose
# — it exists to catch a real serving regression (2x-class), not
# scheduler jitter.
READ_GATE_TOLERANCE = 0.5


def read_gate(new_artifact: dict, baseline_artifact: dict | None,
              tolerance: float = READ_GATE_TOLERANCE) -> dict | None:
    """Gate a read-carrying family's serving story (the read-path
    observatory's artifact section, nomad_tpu/read_observe.py). Scoped:
    None when the artifact's reads section is absent or disabled — only
    families that actually drove a read fleet gate here. RELATIVE
    newest-vs-previous when the prior bank also carries an enabled reads
    section: the worst per-route read latency p95 must not grow more
    than ``tolerance``, and the staleness distribution's p99 (raft
    entries behind the leader commit) must not grow more than
    ``tolerance`` plus a 2-entry absolute slack (the distribution sits
    at 0-1 entries on a healthy single-member cell, where a pure
    relative bar would fail on noise). First-round families report the
    observed values without failing — there is no declared absolute
    bound for read latency; the family's write-path SLOs gate
    separately."""
    reads = new_artifact.get("reads") or {}
    if not reads.get("enabled"):
        return None

    def worst_p95(r: dict):
        vals = [(ep.get("latency_ms") or {}).get("p95")
                for ep in (r.get("endpoints") or {}).values()]
        vals = [v for v in vals if v is not None]
        return max(vals) if vals else None

    def staleness_p99(r: dict):
        return ((r.get("freshness") or {}).get("staleness_entries")
                or {}).get("p99")

    base_reads = (baseline_artifact or {}).get("reads") or {}
    if not base_reads.get("enabled"):
        base_reads = {}
    checks, ok = [], True
    for name, fn, slack in (
        ("read_latency_p95_ms", worst_p95, 0.0),
        ("staleness_age_p99_entries", staleness_p99, 2.0),
    ):
        value = fn(reads)
        if value is None:
            continue
        baseline = fn(base_reads) if base_reads else None
        regressed = (baseline is not None
                     and value > baseline * (1.0 + tolerance) + slack)
        checks.append({"check": name, "value": value,
                       "baseline": baseline, "regressed": regressed})
        ok = ok and not regressed
    return {"ok": ok, "tolerance": tolerance, "checks": checks}


# Read-lane gate: the consistency-lane contract checks on an artifact's
# ``reads.lanes`` section (nomad_tpu/server/read_path.py; objective
# vocabulary in slo.READ_LANE_OBJECTIVES). Mostly ABSOLUTE per-run
# invariants — stale age p95 inside the client bound, follower serve
# share >= the floor, zero linearizable violations / missing stamps —
# plus one main-vs-contrast row: with followers serving, the leader's
# plan p50 must stay within tolerance of the leader-only contrast arm
# (the read plane must relieve the leader, never tax the write path).
# The tolerance is CLIFF-scaled, not noise-scaled: the contrast arm
# doubles as the digest-invariance proof, so it runs observatory-OFF,
# and the observatory itself prices ~19% of plan p50 on this box (r16
# leader-only: 137.5 vs 116.0; r19 follower-serving: 968.8 vs 814.1 —
# the SAME ratio, i.e. the follower plane adds nothing on top). The row
# exists to catch the leader-pile-up cliff (multiples of contrast when
# read serving lands on the write path), so the bar sits above the
# measured observatory cost but far below any pile-up. The absolute
# slack covers sub-150ms p50s riding box scheduling noise.
READ_LANE_PLAN_TOLERANCE = 0.25
READ_LANE_PLAN_SLACK_MS = 50.0


def read_lane_gate(new_artifact: dict) -> dict | None:
    """Gate a read-lane-carrying artifact (reads.lanes present and
    enabled; the r19+ read-storm shape). Self-contained per run: rows
    come from slo.evaluate_read_lanes plus the contrast plan-p50
    comparison against the artifact's OWN leader-only arm — no banked
    baseline needed, so the contract binds from the first round."""
    from nomad_tpu.slo import evaluate_read_lanes

    rows = evaluate_read_lanes(new_artifact)
    if not rows:
        return None
    checks = [{"check": r["objective"], "value": r["observed"],
               "threshold": r["threshold"],
               "regressed": r["met"] is False} for r in rows]
    main_p50 = (new_artifact.get("plan_latency_ms") or {}).get("p50_ms")
    contrast = new_artifact.get("contrast") or {}
    contrast_p50 = (contrast.get("plan_latency_ms") or {}).get("p50_ms")
    if main_p50 is not None and contrast_p50 is not None:
        ceiling = (contrast_p50 * (1.0 + READ_LANE_PLAN_TOLERANCE)
                   + READ_LANE_PLAN_SLACK_MS)
        checks.append({
            "check": "leader_plan_p50_vs_contrast_ms",
            "value": main_p50, "threshold": round(ceiling, 2),
            "regressed": main_p50 > ceiling,
        })
    ok = not any(c["regressed"] for c in checks)
    return {"ok": ok, "checks": checks}


# Runtime-gate tolerance: RSS rides allocator noise and per-row mirror
# bytes only move when buffer/dtype layout changes, so the bar is loose
# — it exists to catch a real footprint regression (a new per-row
# buffer, a float64 slip, a leak past the bounded rings), not GC
# timing. Lock-wait p95 is scheduler-noisy at sim scale for the same
# reason.
RUNTIME_GATE_TOLERANCE = 0.5


def runtime_gate(new_artifact: dict, baseline_artifact: dict | None,
                 tolerance: float = RUNTIME_GATE_TOLERANCE) -> dict | None:
    """Gate a family's runtime economy (the runtime self-observatory's
    artifact section, nomad_tpu/profile_observe.py). Scoped: None when
    the artifact's profile section is absent or disabled. RELATIVE
    newest-vs-previous when the prior bank also carries an enabled
    profile section: peak RSS, the mirror's measured bytes-per-row (the
    1M-node projection's slope), and the worst per-site lock-wait p95
    must not grow more than ``tolerance``. First-round families report
    the observed values without failing."""
    prof = new_artifact.get("profile") or {}
    if not prof.get("enabled"):
        return None

    def rss_peak(p: dict):
        return ((p.get("bytes") or {}).get("rss") or {}).get("peak_bytes")

    def mirror_per_row(p: dict):
        return ((p.get("bytes") or {}).get("mirror")
                or {}).get("per_row_bytes")

    def worst_lock_wait_p95(p: dict):
        rows = (p.get("locks") or {}).get("contention") or []
        vals = [(r.get("wait_ms") or {}).get("p95") for r in rows]
        vals = [v for v in vals if v is not None]
        return max(vals) if vals else None

    base_prof = (baseline_artifact or {}).get("profile") or {}
    if not base_prof.get("enabled"):
        base_prof = {}
    checks, ok = [], True
    for name, fn in (
        ("rss_peak_bytes", rss_peak),
        ("mirror_per_row_bytes", mirror_per_row),
        ("lock_wait_p95_ms", worst_lock_wait_p95),
    ):
        value = fn(prof)
        if value is None:
            continue
        baseline = fn(base_prof) if base_prof else None
        regressed = (baseline is not None and baseline > 0
                     and value > baseline * (1.0 + tolerance))
        checks.append({"check": name, "value": value,
                       "baseline": baseline, "regressed": regressed})
        ok = ok and not regressed
    return {"ok": ok, "tolerance": tolerance, "checks": checks}


# Chaos-gate tolerance: rejoin and expiry-replacement times ride TTL
# jitter, snapshot transfer and re-election noise, so the newest-vs-
# previous bar is deliberately loose — it exists to catch a real
# recovery regression (2x-class), not scheduler jitter. The invariant
# half of the gate (exactly-once, digest equality) is absolute.
CHAOS_GATE_TOLERANCE = 0.5


def chaos_gate(new_artifact: dict, baseline_artifact: dict | None,
               tolerance: float = CHAOS_GATE_TOLERANCE) -> dict | None:
    """Gate a chaos-family artifact (nomad_tpu/simcluster/chaos.py).
    ABSOLUTE (every round, baseline or not): every declared chaos check
    — exactly-once re-placement, no duplicate PlanApplied, leader
    stability, flap-transition books, rejoin digest equality — must
    hold; the runner refuses to even bank a violating artifact, so a
    banked artifact with a failed check means someone hand-edited the
    bank. RELATIVE (newest-vs-previous when the prior bank carries the
    same metric): time-to-rejoin and the expiry->re-placement p95 must
    not grow more than ``tolerance``. None when the artifact has no
    chaos section (not a chaos family)."""
    chaos = new_artifact.get("chaos")
    if not chaos:
        return None
    failed = [c["check"] for c in chaos.get("checks", ())
              if not c.get("ok")]
    checks = [{
        "check": "chaos_invariants",
        "value": len(chaos.get("checks", ())) - len(failed),
        "baseline": None,
        "regressed": bool(failed) or chaos.get("ok") is not True,
        "failed": failed,
    }]
    ok = not checks[0]["regressed"]
    base_chaos = (baseline_artifact or {}).get("chaos") or {}

    def rejoin_ms(c: dict):
        return c.get("time_to_rejoin_ms")

    def expiry_p95(c: dict):
        return (c.get("expiry_replacement_ms") or {}).get("p95_ms")

    for name, fn in (("time_to_rejoin_ms", rejoin_ms),
                     ("expiry_replacement_p95_ms", expiry_p95)):
        value = fn(chaos)
        if value is None:
            continue
        baseline = fn(base_chaos)
        regressed = (baseline is not None and baseline > 0
                     and value > baseline * (1.0 + tolerance))
        checks.append({"check": name, "value": value,
                       "baseline": baseline, "regressed": regressed})
        ok = ok and not regressed
    return {"ok": ok, "tolerance": tolerance, "checks": checks}


def _cell_members(artifact: dict) -> int:
    """Cluster size the artifact's wall-clock numbers were measured on.
    The lanes section carries it explicitly (r19+); pre-lane artifacts
    are single-member cells."""
    lanes = ((artifact.get("reads") or {}).get("lanes") or {})
    try:
        return int(lanes.get("members") or 1)
    except (TypeError, ValueError):
        return 1


def slo_gate_scan(log=log) -> bool:
    """Run the SLO gate over every banked artifact family: newest-vs-
    previous where a prior round exists, absolute-against-objectives for
    first-round families; log one verdict per family. Families whose
    artifacts carry the solver-panel window additionally gate on the
    device-solve economy (solver_gate). A round that changes the
    family's CELL TOPOLOGY (single-member -> replicated cell, as
    read-storm did when the follower read plane landed) re-banks: its
    wall-clock numbers are measured on different machinery than the
    prior round's, so the newest-vs-previous comparison is
    apples-to-oranges and the family is judged absolutely against its
    declared objectives instead — logged, never silent. Returns overall
    pass."""
    ok = True
    for fam, new_path, base_path in _banked_simload_pairs():
        try:
            with open(new_path) as f:
                new = json.load(f)
            objectives = _objectives_for(new)
            if base_path is not None:
                with open(base_path) as f:
                    base_probe = json.load(f)
                if _cell_members(new) != _cell_members(base_probe):
                    log("slo-gate-rebank", family=fam,
                        new_members=_cell_members(new),
                        baseline_members=_cell_members(base_probe),
                        baseline=os.path.basename(base_path))
                    base_path = None
            if base_path is None:
                verdict = slo_gate_absolute(new, objectives)
                solver_verdict = None
                recovery_verdict = recovery_gate(new, None)
                read_verdict = read_gate(new, None)
                runtime_verdict = runtime_gate(new, None)
                chaos_verdict = chaos_gate(new, None)
            else:
                with open(base_path) as f:
                    base = json.load(f)
                verdict = slo_gate(new, base, objectives)
                solver_verdict = solver_gate(new, base)
                recovery_verdict = recovery_gate(new, base)
                read_verdict = read_gate(new, base)
                runtime_verdict = runtime_gate(new, base)
                chaos_verdict = chaos_gate(new, base)
        except (OSError, ValueError, KeyError) as e:
            log("slo-gate-error", family=fam, error=str(e))
            ok = False
            continue
        log("slo-gate", family=fam,
            new=os.path.basename(new_path),
            baseline=(os.path.basename(base_path) if base_path
                      else "<absolute>"),
            ok=verdict["ok"],
            regressed=[c["objective"] for c in verdict["checks"]
                       if c["regressed"]])
        ok = ok and verdict["ok"]
        if solver_verdict is not None:
            log("solver-gate", family=fam, ok=solver_verdict["ok"],
                device_ms_per_placement=solver_verdict[
                    "device_ms_per_placement"],
                baseline=solver_verdict["baseline_ms_per_placement"])
            ok = ok and solver_verdict["ok"]
        if recovery_verdict is not None:
            log("recovery-gate", family=fam, ok=recovery_verdict["ok"],
                regressed=[c["check"] for c in recovery_verdict["checks"]
                           if c["regressed"]])
            ok = ok and recovery_verdict["ok"]
        if read_verdict is not None:
            log("read-gate", family=fam, ok=read_verdict["ok"],
                regressed=[c["check"] for c in read_verdict["checks"]
                           if c["regressed"]])
            ok = ok and read_verdict["ok"]
        lane_verdict = read_lane_gate(new)
        if lane_verdict is not None:
            log("read-lane-gate", family=fam, ok=lane_verdict["ok"],
                regressed=[c["check"] for c in lane_verdict["checks"]
                           if c["regressed"]])
            ok = ok and lane_verdict["ok"]
        if runtime_verdict is not None:
            log("runtime-gate", family=fam, ok=runtime_verdict["ok"],
                regressed=[c["check"] for c in runtime_verdict["checks"]
                           if c["regressed"]])
            ok = ok and runtime_verdict["ok"]
        if chaos_verdict is not None:
            log("chaos-gate", family=fam, ok=chaos_verdict["ok"],
                regressed=[c["check"] for c in chaos_verdict["checks"]
                           if c["regressed"]])
            ok = ok and chaos_verdict["ok"]
    return ok


def main() -> None:
    if "--slo-gate" not in sys.argv[1:]:
        sys.exit("usage: python tools/bench_watch.py --slo-gate")
    sys.exit(0 if slo_gate_scan() else 1)


if __name__ == "__main__":
    main()
