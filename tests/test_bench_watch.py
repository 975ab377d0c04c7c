"""Unit tests for the banked-artifact regression gates
(tools/bench_watch.py --slo-gate)."""

import json
import os
import sys

sys.path.insert(
    0,
    os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                 "tools"),
)

import bench_watch  # noqa: E402


# ---------------------------------------------------------------------------
# SLO regression gate (tools/bench_watch.slo_gate)
# ---------------------------------------------------------------------------

_REAL_BANKED_PAIRS = bench_watch._banked_simload_pairs


def _artifact(p50=20.0, p95=80.0, n=100, attribution=True):
    block = {"n": n, "p50_ms": p50, "p95_ms": p95, "p99_ms": p95 * 2,
             "max_ms": p95 * 3}
    if attribution:
        return {"latency_attribution": {"submit_to_placed_ms": block,
                                        "submit_to_running_ms": {"n": 0}}}
    # Pre-r08 shape: plan latency only (same event anchors).
    return {"plan_latency_ms": block}


def test_slo_gate_passes_inside_threshold():
    """Inside the objective, the gate never fails — even 2x slower than
    the baseline (latency headroom is the SLO's to spend)."""
    verdict = bench_watch.slo_gate(_artifact(p95=200.0),
                                   _artifact(p95=80.0))
    assert verdict["ok"] is True
    placed = next(c for c in verdict["checks"]
                  if c["objective"] == "submit_to_placed_p95_ms")
    assert placed["met"] is True and placed["regressed"] is False
    assert placed["baseline_ms"] == 80.0


def test_slo_gate_fails_newly_broken_objective():
    """An objective the baseline met that the new run misses is a
    regression, full stop."""
    verdict = bench_watch.slo_gate(_artifact(p95=300.0),
                                   _artifact(p95=200.0))
    assert verdict["ok"] is False
    placed = next(c for c in verdict["checks"]
                  if c["objective"] == "submit_to_placed_p95_ms")
    assert placed["regressed"] is True


def test_slo_gate_tolerance_when_both_outside():
    """Both runs outside the objective: only a >tolerance worsening
    fails (the gate hunts regressions, not pre-existing debt)."""
    base = _artifact(p95=400.0)
    within = bench_watch.slo_gate(_artifact(p95=450.0), base)
    assert within["ok"] is True  # 12.5% worse, inside the 25% tolerance
    beyond = bench_watch.slo_gate(_artifact(p95=600.0), base)
    assert beyond["ok"] is False  # 50% worse


def test_slo_gate_pre_r08_baseline_fallback():
    """A banked r07 artifact has no latency_attribution; its
    plan_latency_ms (the same submit→placed event anchors) still gates
    the placed objectives."""
    verdict = bench_watch.slo_gate(
        _artifact(p95=300.0), _artifact(p95=100.0, attribution=False))
    placed = next(c for c in verdict["checks"]
                  if c["objective"] == "submit_to_placed_p95_ms")
    assert placed["baseline_ms"] == 100.0
    assert placed["regressed"] is True
    # Unobservable objectives (no running samples either side) are
    # reported, never failed.
    running = next(c for c in verdict["checks"]
                   if c["objective"] == "submit_to_running_p95_ms")
    assert running["met"] is None and running["regressed"] is False


def test_slo_gate_scan_logs_per_family(tmp_path, monkeypatch):
    new = tmp_path / "SIMLOAD_x_s42_r08.json"
    old = tmp_path / "SIMLOAD_x_s42_r07.json"
    new.write_text(json.dumps(_artifact(p95=300.0)))
    old.write_text(json.dumps(_artifact(p95=100.0)))
    monkeypatch.setattr(
        bench_watch, "_banked_simload_pairs",
        lambda: [("x_s42", str(new), str(old))])
    logged = []

    def fake_log(event, **kw):
        logged.append({"event": event, **kw})

    assert bench_watch.slo_gate_scan(log=fake_log) is False
    assert logged == [{
        "event": "slo-gate", "family": "x_s42",
        "new": new.name, "baseline": old.name, "ok": False,
        "regressed": ["submit_to_placed_p95_ms"],
    }]


def test_banked_pair_discovery_orders_rounds(tmp_path, monkeypatch):
    for name in ("SIMLOAD_steady_s42.json", "SIMLOAD_steady_s42_r06.json",
                 "SIMLOAD_steady_s42_r08.json", "SIMLOAD_lone_s7.json",
                 "not_a_simload.json"):
        (tmp_path / name).write_text("{}")
    monkeypatch.setattr(bench_watch, "REPO", str(tmp_path))
    pairs = _REAL_BANKED_PAIRS()
    # Single-round families (a freshly banked scenario) pair with None:
    # the scan gates them absolutely instead of skipping them.
    assert pairs == [
        ("lone_s7", str(tmp_path / "SIMLOAD_lone_s7.json"), None),
        ("steady_s42",
         str(tmp_path / "SIMLOAD_steady_s42_r08.json"),
         str(tmp_path / "SIMLOAD_steady_s42_r06.json")),
    ]


def test_slo_gate_absolute_for_first_round_family():
    """A first-round family (no banked baseline — the overdrive-100k
    introduction case) gates absolutely: observed objectives must be met
    outright; unobserved ones are reported, not failed."""
    good = bench_watch.slo_gate_absolute(_artifact(p95=200.0))
    assert good["ok"] is True
    bad = bench_watch.slo_gate_absolute(_artifact(p95=300.0))
    assert bad["ok"] is False
    placed = next(c for c in bad["checks"]
                  if c["objective"] == "submit_to_placed_p95_ms")
    assert placed["regressed"] is True and placed["baseline_ms"] is None
    running = next(c for c in bad["checks"]
                   if c["objective"] == "submit_to_running_p95_ms")
    assert running["regressed"] is False  # unobserved (n=0)


def test_slo_gate_express_family_absolute(tmp_path, monkeypatch):
    """An artifact carrying express observations gates on the express
    objective too (absolute: express_placed_p50_ms < 1ms), while
    express-free families keep the default objective set."""
    express_good = _artifact(p95=100.0)
    express_good["latency_attribution"]["express_placed_ms"] = {
        "n": 300, "p50_ms": 0.7, "p95_ms": 0.9, "max_ms": 1.4}
    express_bad = _artifact(p95=100.0)
    express_bad["latency_attribution"]["express_placed_ms"] = {
        "n": 300, "p50_ms": 1.8, "p95_ms": 3.6, "max_ms": 80.0}

    assert bench_watch._objectives_for(_artifact()) is None
    objs = bench_watch._objectives_for(express_good)
    assert objs is not None and "express_placed_p50_ms" in objs

    good = bench_watch.slo_gate_absolute(
        express_good, bench_watch._objectives_for(express_good))
    assert good["ok"] is True
    bad = bench_watch.slo_gate_absolute(
        express_bad, bench_watch._objectives_for(express_bad))
    assert bad["ok"] is False
    check = next(c for c in bad["checks"]
                 if c["objective"] == "express_placed_p50_ms")
    assert check["observed_ms"] == 1.8 and check["regressed"] is True

    # Through the scan: the express family picks up its objective.
    lone = tmp_path / "SIMLOAD_express-mix_s42_r12.json"
    lone.write_text(json.dumps(express_bad))
    monkeypatch.setattr(
        bench_watch, "_banked_simload_pairs",
        lambda: [("express-mix_s42", str(lone), None)])
    logged = []
    assert bench_watch.slo_gate_scan(
        log=lambda event, **kw: logged.append(kw)) is False
    assert "express_placed_p50_ms" in logged[0]["regressed"]


def test_slo_gate_scan_absolute_arm(tmp_path, monkeypatch):
    lone = tmp_path / "SIMLOAD_over_s42_r09.json"
    lone.write_text(json.dumps(_artifact(p95=100.0)))
    monkeypatch.setattr(
        bench_watch, "_banked_simload_pairs",
        lambda: [("over_s42", str(lone), None)])
    logged = []
    assert bench_watch.slo_gate_scan(
        log=lambda event, **kw: logged.append({"event": event, **kw}))
    assert logged[0]["baseline"] == "<absolute>"
    assert logged[0]["ok"] is True


# ---------------------------------------------------------------------------
# scenario-scoped objectives + recovery gate
# ---------------------------------------------------------------------------


def test_objectives_for_scenario_scoped_family():
    """churn-fragmentation is judged against its declared scenario
    objective (the probe wave races a stop storm by design), not the
    250ms cell SLO — and the r13 bank honestly meets it."""
    from nomad_tpu.slo import SCENARIO_OBJECTIVES

    art = _artifact(p95=455.0)
    art["scenario"] = "churn-fragmentation"
    objectives = bench_watch._objectives_for(art)
    assert objectives == SCENARIO_OBJECTIVES["churn-fragmentation"]
    verdict = bench_watch.slo_gate_absolute(art, objectives)
    assert verdict["ok"] is True
    # The same artifact against the DEFAULT objectives fails — the
    # scenario scoping is load-bearing, not cosmetic.
    assert bench_watch.slo_gate_absolute(art, None)["ok"] is False


def _restart_artifact(survived=True, rate=60.0, tts=1000.0, p95=2000.0):
    art = _artifact(p95=p95)
    art["scenario"] = "restart-under-load"
    art["raft"] = {
        "enabled": True,
        "restart": {"placements_survived": survived,
                    "pre_kill_placements": 400,
                    "surviving_placements": 400 if survived else 399},
        "recovery": {"cold_start": True, "entries_replayed": 20,
                     "replay_entries_per_s": rate,
                     "time_to_serving_ms": tts},
    }
    return art


def test_recovery_gate_absolute_on_survival():
    """Digest/placement survival gates ABSOLUTELY, baseline or not."""
    good = bench_watch.recovery_gate(_restart_artifact(), None)
    assert good["ok"] is True
    bad = bench_watch.recovery_gate(_restart_artifact(survived=False),
                                    None)
    assert bad["ok"] is False
    assert [c["check"] for c in bad["checks"]
            if c["regressed"]] == ["placements_survived"]
    # Non-restart artifacts are not this gate's business.
    assert bench_watch.recovery_gate(_artifact(), None) is None


def test_recovery_gate_newest_vs_previous_tolerance():
    """Replay rate and time-to-serving gate newest-vs-previous at 50%
    tolerance: inside it passes, beyond it fails."""
    base = _restart_artifact(rate=60.0, tts=1000.0)
    within = bench_watch.recovery_gate(
        _restart_artifact(rate=40.0, tts=1400.0), base)
    assert within["ok"] is True
    slow_replay = bench_watch.recovery_gate(
        _restart_artifact(rate=20.0, tts=1000.0), base)
    assert slow_replay["ok"] is False
    assert [c["check"] for c in slow_replay["checks"] if c["regressed"]] \
        == ["replay_entries_per_s"]
    slow_serving = bench_watch.recovery_gate(
        _restart_artifact(rate=60.0, tts=2000.0), base)
    assert slow_serving["ok"] is False
    assert [c["check"] for c in slow_serving["checks"]
            if c["regressed"]] == ["time_to_serving_ms"]


def test_recovery_gate_rides_the_scan(tmp_path, monkeypatch):
    new = tmp_path / "SIMLOAD_restart-under-load_s42_r16.json"
    old = tmp_path / "SIMLOAD_restart-under-load_s42_r15.json"
    new.write_text(json.dumps(_restart_artifact(rate=20.0)))
    old.write_text(json.dumps(_restart_artifact(rate=60.0)))
    monkeypatch.setattr(
        bench_watch, "_banked_simload_pairs",
        lambda: [("restart-under-load_s42", str(new), str(old))])
    logged = []
    ok = bench_watch.slo_gate_scan(
        log=lambda event, **kw: logged.append({"event": event, **kw}))
    assert ok is False
    rec = next(r for r in logged if r["event"] == "recovery-gate")
    assert rec["ok"] is False
    assert rec["regressed"] == ["replay_entries_per_s"]


# ---------------------------------------------------------------------------
# Read gate (tools/bench_watch.read_gate)
# ---------------------------------------------------------------------------


def _reads_artifact(p95=10.0, staleness_p99=0.0, enabled=True):
    art = _artifact()
    art["scenario"] = "read-storm"
    art["reads"] = {
        "enabled": enabled,
        "endpoints": {
            "/v1/jobs": {"latency_ms": {"p95": p95}},
            "/v1/nodes": {"latency_ms": {"p95": p95 / 2}},
        },
        "freshness": {"staleness_entries": {"p99": staleness_p99}},
    }
    return art


def test_read_gate_scoped_to_read_carrying_families():
    """No reads section / reads disabled → not this gate's business;
    first-round read-carrying families report without failing (there is
    no declared absolute read-latency bound)."""
    assert bench_watch.read_gate(_artifact(), None) is None
    assert bench_watch.read_gate(_reads_artifact(enabled=False),
                                 None) is None
    first = bench_watch.read_gate(_reads_artifact(p95=40.0), None)
    assert first["ok"] is True
    lat = next(c for c in first["checks"]
               if c["check"] == "read_latency_p95_ms")
    assert lat["value"] == 40.0 and lat["baseline"] is None


def test_read_gate_newest_vs_previous_tolerance():
    """Worst-route p95 gates at 50% relative; the staleness p99 carries
    a 2-entry absolute slack on top (a healthy single-member cell sits
    at 0-1 entries, where a pure relative bar would fail on noise)."""
    base = _reads_artifact(p95=10.0, staleness_p99=0.0)
    within = bench_watch.read_gate(_reads_artifact(p95=14.0), base)
    assert within["ok"] is True
    slow = bench_watch.read_gate(_reads_artifact(p95=20.0), base)
    assert slow["ok"] is False
    assert [c["check"] for c in slow["checks"] if c["regressed"]] \
        == ["read_latency_p95_ms"]
    # Staleness: 0 → 2 rides the slack; 0 → 3 is a regression.
    noisy = bench_watch.read_gate(
        _reads_artifact(staleness_p99=2.0), base)
    assert noisy["ok"] is True
    stale = bench_watch.read_gate(
        _reads_artifact(staleness_p99=3.0), base)
    assert stale["ok"] is False
    assert [c["check"] for c in stale["checks"] if c["regressed"]] \
        == ["staleness_age_p99_entries"]
    # A reads-disabled baseline gives the new run a first-round pass,
    # not a divide-by-baseline surprise.
    off_base = _reads_artifact(enabled=False)
    assert bench_watch.read_gate(_reads_artifact(p95=99.0),
                                 off_base)["ok"] is True


def test_read_gate_rides_the_scan(tmp_path, monkeypatch):
    new = tmp_path / "SIMLOAD_read-storm_s42_r16.json"
    old = tmp_path / "SIMLOAD_read-storm_s42_r15.json"
    new.write_text(json.dumps(_reads_artifact(p95=30.0)))
    old.write_text(json.dumps(_reads_artifact(p95=10.0)))
    monkeypatch.setattr(
        bench_watch, "_banked_simload_pairs",
        lambda: [("read-storm_s42", str(new), str(old))])
    logged = []
    ok = bench_watch.slo_gate_scan(
        log=lambda event, **kw: logged.append({"event": event, **kw}))
    assert ok is False
    rec = next(r for r in logged if r["event"] == "read-gate")
    assert rec["ok"] is False
    assert rec["regressed"] == ["read_latency_p95_ms"]


def _lanes_artifact(share=1.0, age_p95=700.0, bound=5000.0,
                    violations=0, stamp_missing=0, members=3,
                    plan_p50=950.0, contrast_p50=820.0, enabled=True):
    """The r19+ read-storm shape: a lanes verdict section plus the
    leader-only contrast arm's plan books."""
    art = _artifact(attribution=False)
    art["scenario"] = "read-storm"
    art["plan_latency_ms"]["p50_ms"] = plan_p50
    art["reads"] = {"enabled": enabled, "lanes": {
        "enabled": enabled, "members": members,
        "follower_serve_share": share, "stale_bound_ms": bound,
        "stale_age_ms": {"n": 100, "p95": age_p95},
        "linear_violations": violations, "stamp_missing": stamp_missing,
    }}
    art["contrast"] = {"plan_latency_ms": {"p50_ms": contrast_p50},
                       "digest_matches": True,
                       "reads": {"enabled": False,
                                 "lanes": {"enabled": False}}}
    return art


def test_read_lane_gate_scoped_to_lane_carrying_artifacts():
    """No lanes section (pre-r19 banks) or lanes disabled (the contrast
    arm itself, single-member dev runs) → not this gate's business."""
    assert bench_watch.read_lane_gate(_artifact()) is None
    assert bench_watch.read_lane_gate(
        _lanes_artifact(enabled=False)) is None


def test_read_lane_gate_contract_rows():
    """The four absolute lane-contract rows plus the plan-p50 ceiling:
    a healthy r19-shaped artifact passes outright; each broken promise
    flips exactly its own row."""
    good = bench_watch.read_lane_gate(_lanes_artifact())
    assert good["ok"] is True
    assert [c["check"] for c in good["checks"]] == [
        "follower_serve_share", "stale_age_p95_bound_ratio",
        "linear_violations", "stamp_missing",
        "leader_plan_p50_vs_contrast_ms"]

    def regressed(art):
        v = bench_watch.read_lane_gate(art)
        return [c["check"] for c in v["checks"] if c["regressed"]]

    assert regressed(_lanes_artifact(share=0.5)) \
        == ["follower_serve_share"]
    assert regressed(_lanes_artifact(age_p95=6000.0)) \
        == ["stale_age_p95_bound_ratio"]
    assert regressed(_lanes_artifact(violations=1)) \
        == ["linear_violations"]
    assert regressed(_lanes_artifact(stamp_missing=3)) \
        == ["stamp_missing"]
    # A single-member cell cannot route around the leader: the share
    # row reports unjudged instead of failing a lane that cannot exist.
    solo = bench_watch.read_lane_gate(_lanes_artifact(members=1))
    share_row = next(c for c in solo["checks"]
                     if c["check"] == "follower_serve_share")
    assert share_row["regressed"] is False


def test_read_lane_gate_plan_ceiling_is_cliff_scaled():
    """The leader-relief row: plan p50 inside contrast*1.25 + 50ms
    passes (the tolerance prices the observatory-ON main arm, measured
    ~19% at r16/r19); a pile-up multiple fails it."""
    inside = bench_watch.read_lane_gate(
        _lanes_artifact(plan_p50=1000.0, contrast_p50=820.0))
    assert inside["ok"] is True
    cliff = bench_watch.read_lane_gate(
        _lanes_artifact(plan_p50=2500.0, contrast_p50=820.0))
    assert cliff["ok"] is False
    assert [c["check"] for c in cliff["checks"] if c["regressed"]] \
        == ["leader_plan_p50_vs_contrast_ms"]


def test_topology_change_rebanks_the_family(tmp_path, monkeypatch):
    """A round that changes the family's cell topology (read-storm went
    single-member -> 3-member when the follower read plane landed) is
    judged ABSOLUTELY against its declared objectives, never
    newest-vs-previous across different machinery — and the re-bank is
    logged, not silent."""
    new_art = _lanes_artifact()
    # Would regress 50%-relative vs the old bank, but meets the
    # scenario's declared 5s replicated-cell bound.
    new_art["plan_latency_ms"]["p95_ms"] = 3100.0
    old_art = _artifact(attribution=False)
    old_art["scenario"] = "read-storm"
    old_art["plan_latency_ms"]["p95_ms"] = 300.0
    new = tmp_path / "SIMLOAD_read-storm_s42_r19.json"
    old = tmp_path / "SIMLOAD_read-storm_s42_r16.json"
    new.write_text(json.dumps(new_art))
    old.write_text(json.dumps(old_art))
    monkeypatch.setattr(
        bench_watch, "_banked_simload_pairs",
        lambda: [("read-storm_s42", str(new), str(old))])
    logged = []
    ok = bench_watch.slo_gate_scan(
        log=lambda event, **kw: logged.append({"event": event, **kw}))
    assert ok is True
    rebank = next(r for r in logged if r["event"] == "slo-gate-rebank")
    assert rebank["new_members"] == 3
    assert rebank["baseline_members"] == 1
    gate = next(r for r in logged if r["event"] == "slo-gate")
    assert gate["baseline"] == "<absolute>"
    lane = next(r for r in logged if r["event"] == "read-lane-gate")
    assert lane["ok"] is True
