"""End-to-end latency attribution tests (PR 8): lifecycle timeline
stitching (nomad_tpu.lifecycle), the SLO layer (nomad_tpu.slo +
telemetry.BurnRateWindow), fixed-bucket histogram exposition, aggregate
trace-loss counters, the event-stream lifecycle-ordering contract the
stitcher rests on (per-key raft-index monotonicity across a real
bounce/refresh cycle), SSE resume-after-truncation, and the HTTP/SDK
surfaces (/v1/agent/slo, /v1/evaluation/<id>/timeline)."""

import json
import threading
import time
import urllib.request

import pytest

from nomad_tpu import events as events_mod
from nomad_tpu import lifecycle, mock, slo, structs, telemetry, trace
from nomad_tpu.server import Server, ServerConfig
from nomad_tpu.structs import Evaluation, Plan, Resources, generate_uuid

# ---------------------------------------------------------------------------
# lifecycle: synthetic-span stitching
# ---------------------------------------------------------------------------


def _span(name, start, end, **annotations):
    return {"trace_id": "ev1", "span_id": name, "parent_id": "",
            "name": name, "start": start, "end": end,
            "annotations": annotations}


def _full_span_set(t0):
    """A complete single-attempt lifecycle: every directly-mapped span
    plus the two derived stages, summing to 90ms of a 100ms e2e."""
    return [
        _span("eval", t0, t0 + 0.099, job_id="j1", type="service",
              triggered_by="job-register"),
        _span("broker.wait", t0, t0 + 0.020),
        _span("worker.wait_for_index", t0 + 0.020, t0 + 0.022),
        _span("worker.invoke_scheduler", t0 + 0.022, t0 + 0.090),
        _span("worker.submit_plan", t0 + 0.060, t0 + 0.090),
        _span("plan.queue_wait", t0 + 0.060, t0 + 0.065),
        _span("plan.evaluate", t0 + 0.065, t0 + 0.075, refresh_index=0),
        _span("plan.apply", t0 + 0.075, t0 + 0.085),
    ]


def test_stage_partition_reconciles_exactly():
    """The stage classification is a PARTITION of submit→placed: directly
    mapped spans + derived (parent-minus-children) stages + the explicit
    unattributed gap sum to the measured end-to-end latency."""
    t0 = 1000.0
    tl = lifecycle.stitch_eval(
        "ev1", _full_span_set(t0),
        {"submitted": t0, "placed": t0 + 0.100, "running": None,
         "job_id": "j1", "triggered_by": "job-register"},
    )
    assert tl.submit_to_placed_ms == pytest.approx(100.0)
    assert tl.stage_ms["broker_wait"] == pytest.approx(20.0)
    assert tl.stage_ms["raft_catchup"] == pytest.approx(2.0)
    # invoke_scheduler(68) minus nested submit_plan(30)
    assert tl.stage_ms["schedule_solve"] == pytest.approx(38.0)
    # submit_plan(30) minus queue_wait+evaluate+apply(25)
    assert tl.stage_ms["submit_overhead"] == pytest.approx(5.0)
    assert tl.stage_ms["plan_queue_wait"] == pytest.approx(5.0)
    assert tl.stage_ms["plan_verify"] == pytest.approx(10.0)
    assert tl.stage_ms["raft_commit"] == pytest.approx(10.0)
    assert tl.stage_ms["unattributed"] == pytest.approx(10.0)
    assert sum(tl.stage_ms.values()) == pytest.approx(100.0)
    assert tl.attempts == 1 and tl.bounces == 0
    # Segments are start-ordered and carry the queue/service kind.
    starts = [s["start_ms"] for s in tl.segments]
    assert starts == sorted(starts)
    kinds = {s["stage"]: s["kind"] for s in tl.segments}
    assert kinds["broker_wait"] == "queue"
    assert kinds["plan_verify"] == "service"

    att = lifecycle.attribution([tl])
    rec = att["reconciliation"]
    assert rec["attributed_fraction"] == pytest.approx(1.0, abs=0.01)
    assert att["submit_to_placed_ms"]["p95_ms"] == pytest.approx(100.0)
    # Waterfall shares over the partition sum to ~1.
    assert sum(w["share"] for w in att["waterfall"]) == pytest.approx(
        1.0, abs=0.01)


def test_bounce_becomes_visible_retry_segments():
    """A conflict/refresh cycle through the optimistic pipeline shows as
    attempts=2 + a bounce count + per-attempt segments — visible retry
    time, not lost time."""
    t0 = 2000.0
    spans = [
        _span("eval", t0, t0 + 0.2, job_id="j2"),
        _span("broker.wait", t0, t0 + 0.01),
        _span("worker.submit_plan", t0 + 0.02, t0 + 0.05),
        _span("plan.evaluate", t0 + 0.03, t0 + 0.04, refresh_index=7),
        _span("broker.wait", t0 + 0.05, t0 + 0.06),
        _span("worker.submit_plan", t0 + 0.07, t0 + 0.10),
        _span("plan.evaluate", t0 + 0.08, t0 + 0.09, refresh_index=0),
        _span("plan.apply", t0 + 0.09, t0 + 0.10),
    ]
    tl = lifecycle.stitch_eval("ev2", spans, {"submitted": t0,
                                              "placed": t0 + 0.11})
    assert tl.attempts == 2
    assert tl.bounces == 1
    attempts = {(s["stage"], s["attempt"]) for s in tl.segments}
    assert ("broker_wait", 2) in attempts
    assert ("plan_verify", 2) in attempts


def test_degraded_no_spans_still_anchors_end_to_end():
    """Tracing off (or trace evicted) is not an error: the end-to-end
    numbers come from event anchors alone and the waterfall is all
    unattributed."""
    tl = lifecycle.stitch_eval(
        "ev3", None,
        {"submitted": 10.0, "placed": 10.05, "running": 10.25},
    )
    assert tl.spans_seen == 0 and tl.attempts == 0
    assert tl.submit_to_placed_ms == pytest.approx(50.0)
    assert tl.submit_to_running_ms == pytest.approx(250.0)
    assert tl.stage_ms["unattributed"] == pytest.approx(50.0)
    assert tl.stage_ms["client_ack"] == pytest.approx(200.0)


def test_worst_k_and_empty_attribution():
    tls = []
    for i, e2e in enumerate((0.03, 0.09, 0.01)):
        tl = lifecycle.stitch_eval(f"e{i}", None,
                                   {"submitted": 0.0, "placed": e2e})
        tls.append(tl)
    worst = lifecycle.worst_k(tls, k=2)
    assert [w["eval_id"] for w in worst] == ["e1", "e0"]

    empty = lifecycle.attribution([])
    assert empty["timelines"] == 0
    assert empty["waterfall"] == []
    assert empty["reconciliation"]["attributed_fraction"] == 0.0


def test_scan_events_anchors_from_broker_events():
    """scan_events pulls submitted/placed/running anchors (and job
    metadata) off the typed stream, accepting Event objects and dicts."""
    broker = events_mod.EventBroker(register=False)
    broker.publish("Eval", "EvalUpdated", key="ev9", raft_index=1,
                   payload={"status": structs.EVAL_STATUS_PENDING,
                            "job_id": "j9", "triggered_by": "t"})
    broker.publish("Plan", "PlanApplied", key="ev9", raft_index=2,
                   payload={"n_allocs": 1})
    broker.publish("Alloc", "AllocClientUpdated", key="a1", raft_index=3,
                   payload={"client_status":
                            structs.ALLOC_CLIENT_STATUS_RUNNING,
                            "eval_id": "ev9", "job_id": "j9"})
    evs = broker.all_events()
    anchors = lifecycle.scan_events(evs)["ev9"]
    assert anchors["submitted"] is not None
    assert anchors["placed"] >= anchors["submitted"]
    assert anchors["running"] >= anchors["placed"]
    assert anchors["job_id"] == "j9"
    # Dict form (debug-bundle / artifact path) resolves identically.
    from_dicts = lifecycle.scan_events([e.to_dict() for e in evs])["ev9"]
    assert from_dicts == anchors


# ---------------------------------------------------------------------------
# the stitcher's core assumption: per-key lifecycle ordering on the
# event stream, across a REAL bounce/refresh cycle
# ---------------------------------------------------------------------------


def _seed_eval(srv, job_id):
    ev = Evaluation(
        id=generate_uuid(), priority=50,
        type=structs.JOB_TYPE_SERVICE,
        triggered_by=structs.EVAL_TRIGGER_JOB_REGISTER,
        job_id=job_id, status=structs.EVAL_STATUS_PENDING,
    )
    srv.raft.apply("eval_update", {"evals": [ev]})
    return ev


def _place_plan(eval_id, token, node_id, cpu, snapshot_index):
    alloc = mock.alloc()
    alloc.node_id = node_id
    alloc.eval_id = eval_id
    alloc.resources = Resources(cpu=cpu, memory_mb=64)
    alloc.task_resources = {}
    alloc.desired_status = structs.ALLOC_DESIRED_STATUS_RUN
    plan = Plan(eval_id=eval_id, eval_token=token, priority=50,
                snapshot_index=snapshot_index)
    plan.append_alloc(alloc)
    return plan


def test_event_ordering_and_timeline_across_bounce_cycle():
    """Per-key event sequences stay gapless and monotonically
    raft-index-ordered through a genuine optimistic bounce (conflict →
    RefreshIndex → re-plan → commit), and the stitched timeline shows
    the bounce as a visible retry instead of losing the eval."""
    srv = Server(ServerConfig(scheduler_backend="host", num_schedulers=0))
    srv.plan_queue.set_enabled(True)
    srv.eval_broker.set_enabled(True)
    try:
        node = mock.node()
        node.resources.cpu = 1000  # fits one 600 ask, not two
        srv.raft.apply("node_register", {"node": node})
        ev_a = _seed_eval(srv, "job-a")
        ev_b = _seed_eval(srv, "job-b")
        dq_a, tok_a, _ = srv.eval_dequeue(["service"], timeout=1.0)
        dq_b, tok_b, _ = srv.eval_dequeue(["service"], timeout=1.0)
        tokens = {dq_a.id: tok_a, dq_b.id: tok_b}

        snap_index = srv.raft.applied_index
        pend_a = srv.plan_queue.enqueue(
            _place_plan(dq_a.id, tokens[dq_a.id], node.id, 600, snap_index))
        pend_b = srv.plan_queue.enqueue(
            _place_plan(dq_b.id, tokens[dq_b.id], node.id, 600, snap_index))
        srv.plan_applier.start()
        res_a = pend_a.wait(timeout=5.0)
        res_b = pend_b.wait(timeout=5.0)
        assert res_a.node_allocation and not res_a.conflict
        assert res_b.conflict is True and res_b.refresh_index > 0

        # The refresh cycle: capacity arrives, the bounced plan re-plans
        # against the refreshed snapshot and commits.
        node2 = mock.node()
        node2.resources.cpu = 1000
        srv.raft.apply("node_register", {"node": node2})
        pend_b2 = srv.plan_queue.enqueue(
            _place_plan(dq_b.id, tokens[dq_b.id], node2.id, 600,
                        srv.raft.applied_index))
        res_b2 = pend_b2.wait(timeout=5.0)
        assert res_b2.node_allocation and res_b2.refresh_index == 0

        evs = srv.fsm.events.all_events()
        # Broker indices: strictly increasing, gapless.
        indices = [e.index for e in evs]
        assert indices == list(range(indices[0], indices[0] + len(evs)))
        # Per-key raft-index sequences: monotonically non-decreasing —
        # the stitcher's anchor-ordering assumption, across the bounce.
        by_key = {}
        for e in evs:
            by_key.setdefault(e.key, []).append(e.raft_index)
        for key, seq in by_key.items():
            assert seq == sorted(seq), f"raft order violated for {key}"
        # Lifecycle order for the bounced eval: pending before its (one)
        # PlanApplied — the bounced attempt committed nothing.
        b_types = [e.type for e in evs if e.key == ev_b.id]
        assert b_types.count("PlanApplied") == 1
        assert (b_types.index("EvalUpdated")
                < b_types.index("PlanApplied"))

        # The stitched timeline survives the bounce: the conflict cycle
        # is a counted retry, and the eval still reads placed.
        timelines = lifecycle.stitch(evs)
        tl = timelines[ev_b.id]
        assert tl.submit_to_placed_ms is not None
        assert tl.bounces >= 1
        assert tl.stage_ms.get("plan_verify", 0.0) > 0.0
    finally:
        srv.shutdown()


def test_real_workload_waterfall_reconciles():
    """Acceptance-shaped: a real host-backend workload's stitched stage
    sums reconcile with measured submit→placed within 10%."""
    srv = Server(ServerConfig(
        scheduler_backend="host", num_schedulers=1,
        min_heartbeat_ttl=300.0, prewarm_shapes=False,
    ))
    srv.start()
    try:
        for _ in range(3):
            srv.node_register(mock.node())
        for _ in range(3):
            ev_id, _ = srv.job_register(mock.job())
            ev = srv.wait_for_eval(ev_id, timeout=15.0)
            assert ev.status == structs.EVAL_STATUS_COMPLETE
        att = lifecycle.attribution(
            lifecycle.stitch(srv.fsm.events.all_events()).values())
        assert att["timelines"] == 3
        rec = att["reconciliation"]
        assert 0.9 <= rec["attributed_fraction"] <= 1.1, rec
        assert att["submit_to_placed_ms"]["n"] == 3
        stages = {w["stage"] for w in att["waterfall"]}
        assert stages == set(lifecycle.STAGES)
    finally:
        srv.shutdown()


# ---------------------------------------------------------------------------
# telemetry: fixed-bucket histogram exposition + BurnRateWindow
# ---------------------------------------------------------------------------


def test_prometheus_histogram_golden_format():
    """Golden exposition: cumulative ``le`` buckets with shared bounds —
    the aggregatable (histogram_quantile) companion to the summary."""
    sink = telemetry.InmemSink(histogram_buckets=[1.0, 10.0, 100.0])
    for v in (0.5, 5.0, 50.0, 500.0):
        sink.add_sample(("plan", "evaluate"), v)
    text = telemetry.prometheus_text(sink)
    golden = (
        "# TYPE plan_evaluate_ms_hist histogram\n"
        'plan_evaluate_ms_hist_bucket{le="1"} 1\n'
        'plan_evaluate_ms_hist_bucket{le="10"} 2\n'
        'plan_evaluate_ms_hist_bucket{le="100"} 3\n'
        'plan_evaluate_ms_hist_bucket{le="+Inf"} 4\n'
        "plan_evaluate_ms_hist_sum 555.5\n"
        "plan_evaluate_ms_hist_count 4"
    )
    assert golden in text
    # Bucket counts are process-lifetime cumulative: a second batch only
    # grows them (rate()/histogram_quantile() need monotonicity).
    sink.add_sample(("plan", "evaluate"), 0.1)
    assert 'plan_evaluate_ms_hist_bucket{le="1"} 2' in (
        telemetry.prometheus_text(sink))


def test_histogram_default_buckets_and_config_override():
    sink = telemetry.InmemSink()
    assert sink.buckets == telemetry.DEFAULT_HISTOGRAM_BUCKETS_MS
    custom = telemetry.InmemSink(histogram_buckets=[50.0, 5.0])
    assert custom.buckets == (5.0, 50.0)  # sorted on ingest


def test_burn_rate_window_math_and_bounds():
    w = telemetry.BurnRateWindow(window_s=60.0, objective=0.95,
                                 max_samples=8)
    for i in range(20):
        w.record(good=(i % 10 != 0), t=float(i))
    stats = w.stats(now=20.0)
    # Bounded at 8 samples, oldest evicted and counted.
    assert stats["total"] == 8 and stats["evicted"] == 12
    # Window pruning is monotonic-time arithmetic.
    late = w.stats(now=100.0)
    assert late["total"] == 0 and late["burn_rate"] == 0.0

    w2 = telemetry.BurnRateWindow(window_s=60.0, objective=0.95)
    for i in range(100):
        w2.record(good=(i % 10 != 0), t=float(i) * 0.1)
    s2 = w2.stats(now=10.0)
    # 10 bad of 100 against a 5% budget: burn rate 2.0, budget gone.
    assert s2["burn_rate"] == pytest.approx(2.0)
    assert s2["budget_remaining_fraction"] == 0.0
    with pytest.raises(ValueError):
        telemetry.BurnRateWindow(objective=1.5)


# ---------------------------------------------------------------------------
# trace: aggregate loss counters
# ---------------------------------------------------------------------------


def test_tracer_aggregate_loss_counters():
    tracer = trace.Tracer(max_traces=2, max_spans=2)
    for i in range(3):
        tracer.start_span(f"t{i}", "eval", root=True).finish()
    assert tracer.stats()["traces_evicted"] == 1
    for _ in range(3):
        tracer.start_span("t2", "fsm.apply").finish()
    stats = tracer.stats()
    # 4 finishes into a 2-span ring (root + 3): 2 dropped.
    assert stats["spans_dropped"] == 2
    assert stats["retained"] == 2
    assert set(stats) == {"enabled", "retained", "max_traces",
                          "max_spans", "spans_dropped", "traces_evicted"}


# ---------------------------------------------------------------------------
# slo: objectives, monitor, artifact evaluation
# ---------------------------------------------------------------------------


def test_objective_parse_spelling_and_validation():
    o = slo.Objective.parse("submit_to_placed_p95_ms", 250)
    assert (o.metric, o.percentile, o.threshold_ms) == (
        "submit_to_placed", 0.95, 250.0)
    with pytest.raises(ValueError):
        slo.Objective.parse("p95_submit_to_placed", 250)  # wrong shape
    with pytest.raises(ValueError):
        slo.Objective.parse("plan_apply_p95_ms", 250)  # unknown metric
    with pytest.raises(ValueError):
        slo.Objective.parse("submit_to_placed_p0_ms", 250)
    with pytest.raises(ValueError):
        slo.Objective.parse("submit_to_placed_p95_ms", 0)
    assert [o.name for o in slo.parse_objectives(None)] == sorted(
        slo.DEFAULT_OBJECTIVES)
    assert slo.parse_objectives({"submit_to_running_p50_ms": 100})[0].name \
        == "submit_to_running_p50_ms"


def _lifecycle_events(broker, eval_id, placed_dt, running_dt=None):
    """Publish one eval's pending→placed(→running) lifecycle with
    controlled inter-event latencies (Event.time is stamped at publish;
    rewrite it to shape the measured interval)."""
    broker.publish("Eval", "EvalUpdated", key=eval_id,
                   payload={"status": structs.EVAL_STATUS_PENDING,
                            "job_id": "j", "triggered_by": "t"})
    broker.publish("Plan", "PlanApplied", key=eval_id, payload={})
    evs = broker.all_events()
    evs[-1].time = evs[-2].time + placed_dt
    if running_dt is not None:
        broker.publish(
            "Alloc", "AllocClientUpdated", key="a-" + eval_id,
            payload={"client_status": structs.ALLOC_CLIENT_STATUS_RUNNING,
                     "eval_id": eval_id, "job_id": "j"})
        broker.all_events()[-1].time = evs[-2].time + (running_dt or 0)


def test_slo_monitor_accounting_and_snapshot():
    broker = events_mod.EventBroker(register=False)
    monitor = slo.SLOMonitor(
        broker, {"submit_to_placed_p95_ms": 250.0,
                 "submit_to_running_p95_ms": 1000.0})
    _lifecycle_events(broker, "ev-fast", placed_dt=0.050, running_dt=0.500)
    _lifecycle_events(broker, "ev-slow", placed_dt=0.400)
    monitor.poll()  # cursor 0 -> latest, no truncation charge

    snap = monitor.snapshot()
    placed = next(o for o in snap["objectives"]
                  if o["name"] == "submit_to_placed_p95_ms")
    # 1 bad of 2 against a 5% budget: breached, burn rate 10.
    assert placed["total"] == 2 and placed["bad"] == 1
    assert placed["met"] is False
    assert placed["burn_rate"] == pytest.approx(10.0)
    running = next(o for o in snap["objectives"]
                   if o["name"] == "submit_to_running_p95_ms")
    assert running["total"] == 1 and running["bad"] == 0
    assert running["met"] is True
    assert snap["samples"]["submit_to_placed"]["count"] == 2
    assert snap["samples"]["submit_to_running"]["count"] == 1
    assert snap["pending_evals"] == 0  # both evals resolved
    assert monitor.summary()["submit_to_placed_p95_ms"]["met"] is False

    # Duplicate PlanApplied (partial-commit follow-ups) must not
    # double-count the eval.
    broker.publish("Plan", "PlanApplied", key="ev-fast", payload={})
    monitor.poll()
    assert monitor.snapshot()["samples"]["submit_to_placed"]["count"] == 2


def test_slo_monitor_counts_ring_truncation():
    class _GappyBroker:
        def events_after(self, cursor):
            return 100, [], True

    monitor = slo.SLOMonitor(_GappyBroker(), {})
    monitor._cursor = 5
    monitor.poll()
    assert monitor.truncated_gaps == 1
    monitor.poll()  # cursor now past the gap: charged once per fall-off
    assert monitor.truncated_gaps == 2


def test_slo_monitor_warmup_reset_windows_past_cold_compile():
    """The PR 8 caveat, closed: a scenario's warmup eval (cold XLA
    compile, seconds) used to burn the live error budget forever.
    reset() at the warmup boundary wipes the books — counted — so the
    steady-state verdict reflects only post-boundary samples."""
    broker = events_mod.EventBroker(register=False)
    monitor = slo.SLOMonitor(
        broker, {"submit_to_placed_p95_ms": 250.0})
    # Warmup: one catastrophic cold-compile sample (4s >> 250ms).
    _lifecycle_events(broker, "ev-warmup", placed_dt=4.0)
    monitor.poll()
    assert monitor.snapshot()["objectives"][0]["met"] is False

    monitor.reset()
    snap = monitor.snapshot()
    assert snap["resets"] == 1
    assert snap["reset_excluded"] == 1
    assert snap["samples"]["submit_to_placed"]["count"] == 0
    assert snap["objectives"][0]["total"] == 0

    # Steady state: fast samples only -> the objective is met, the
    # warmup breach is gone from window AND reservoir.
    for i in range(5):
        _lifecycle_events(broker, f"ev-steady-{i}", placed_dt=0.020)
    monitor.poll()
    snap = monitor.snapshot()
    obj = snap["objectives"][0]
    assert obj["total"] == 5 and obj["bad"] == 0 and obj["met"] is True
    assert snap["samples"]["submit_to_placed"]["count"] == 5
    # A warmup eval whose placement lands only AFTER the boundary must
    # not leak a cross-boundary sample: its pending anchor was wiped.
    broker.publish("Plan", "PlanApplied", key="ev-warmup2", payload={})
    monitor.poll()
    assert monitor.snapshot()["samples"]["submit_to_placed"]["count"] == 5


def test_slo_monitor_samples_express_placed_events():
    """The express lane's in-line latency rides ExpressPlaced payloads
    into the express_placed metric (the async PlanApplied never charges
    it — express evals publish no pending EvalUpdated at all)."""
    broker = events_mod.EventBroker(register=False)
    monitor = slo.SLOMonitor(
        broker, {**slo.DEFAULT_OBJECTIVES, **slo.EXPRESS_OBJECTIVES})
    broker.publish("Express", "ExpressPlaced", key="ev-x",
                   payload={"job_id": "j", "tasks": 1,
                            "placed_ms": 0.42})
    broker.publish("Express", "ExpressPlaced", key="ev-y",
                   payload={"job_id": "j", "tasks": 1,
                            "placed_ms": 3.5})
    monitor.poll()
    snap = monitor.snapshot()
    assert snap["samples"]["express_placed"]["count"] == 2
    obj = next(o for o in snap["objectives"]
               if o["name"] == "express_placed_p50_ms")
    # p50 objective at 1ms: one good, one bad of two -> budget 50%,
    # burn rate 1.0, still met (<= 1.0).
    assert obj["total"] == 2 and obj["bad"] == 1
    assert obj["met"] is True
    # submit_to_placed untouched by express events.
    assert snap["samples"]["submit_to_placed"]["count"] == 0


def test_evaluate_artifact_checks_stricter_cut():
    att = {"submit_to_placed_ms": {"n": 50, "p50_ms": 40.0,
                                   "p95_ms": 180.0, "p99_ms": 900.0}}
    checks = slo.evaluate_artifact(
        att, {"submit_to_placed_p90_ms": 200.0,
              "submit_to_placed_p99_ms": 500.0,
              "submit_to_running_p95_ms": 1000.0})
    by_name = {c["objective"]: c for c in checks}
    # p90 objective, artifact cuts at 50/95/99: checked at the next
    # STRICTER recorded cut (p95).
    p90 = by_name["submit_to_placed_p90_ms"]
    assert p90["checked_percentile"] == 0.95
    assert p90["observed_ms"] == 180.0 and p90["met"] is True
    assert by_name["submit_to_placed_p99_ms"]["met"] is False
    # No running samples in the artifact: reported, not judged.
    assert by_name["submit_to_running_p95_ms"]["met"] is None


# ---------------------------------------------------------------------------
# agent config: telemetry { histogram_buckets, slo {} }
# ---------------------------------------------------------------------------


def test_agent_config_histogram_and_slo_blocks():
    from nomad_tpu.agent_config import _from_mapping

    fc = _from_mapping({"telemetry": {
        "histogram_buckets": [100, 5, 25],
        "slo": {"submit_to_placed_p95_ms": 250},
    }})
    assert fc.telemetry.histogram_buckets == [5.0, 25.0, 100.0]
    assert fc.telemetry.slo == {"submit_to_placed_p95_ms": 250.0}
    with pytest.raises(ValueError):
        _from_mapping({"telemetry": {"histogram_buckets": [0, 5]}})
    with pytest.raises(ValueError):
        _from_mapping({"telemetry": {"histogram_buckets": "wide"}})
    # A typo'd objective fails at config parse, not agent start.
    with pytest.raises(ValueError):
        _from_mapping({"telemetry": {"slo": {"submit_to_plcaed_p95_ms": 1}}})

    # Absent vs explicitly empty: no slo{} block (None) means the default
    # objective set downstream; an empty block is the documented
    # disable switch and must survive parse AND merge.
    assert _from_mapping({}).telemetry.slo is None
    disabled = _from_mapping({"telemetry": {"slo": {}}})
    assert disabled.telemetry.slo == {}

    # Per-objective merge: a later file overrides one threshold without
    # dropping the rest of the set.
    base = _from_mapping({"telemetry": {"slo": {
        "submit_to_placed_p95_ms": 250, "submit_to_running_p95_ms": 1000}}})
    override = _from_mapping({"telemetry": {"slo": {
        "submit_to_placed_p95_ms": 100}}})
    merged = base.merge(override)
    assert merged.telemetry.slo == {"submit_to_placed_p95_ms": 100.0,
                                    "submit_to_running_p95_ms": 1000.0}
    # A later empty block disables; a later absent block changes nothing.
    assert base.merge(disabled).telemetry.slo == {}
    assert base.merge(_from_mapping({})).telemetry.slo \
        == base.telemetry.slo


# ---------------------------------------------------------------------------
# HTTP + SDK surfaces (one dev agent for the module)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def agent(tmp_path_factory):
    from nomad_tpu.agent import Agent, AgentConfig

    config = AgentConfig(
        server_enabled=True, dev_mode=True, node_name="slo-dev",
        enable_debug=True,
        # Small ring so the truncation case is drivable over HTTP.
        event_buffer_size=64,
    )
    config.data_dir = str(tmp_path_factory.mktemp("slo-agent"))
    config.http_port = 0
    config.scheduler_backend = "host"
    a = Agent(config)
    a.start()
    yield a
    a.shutdown()


@pytest.fixture()
def client(agent):
    from nomad_tpu.api.client import ApiClient

    return ApiClient(address=agent.http.addr)


def _place_one(client, agent):
    job = mock.job()
    ev_id, _ = client.jobs().register(job)
    ev = agent.server.wait_for_eval(ev_id, timeout=15.0)
    assert ev.status == structs.EVAL_STATUS_COMPLETE
    return job, ev_id


def test_agent_slo_endpoint_live(client, agent):
    _place_one(client, agent)
    # The monitor is an event-ring consumer on a 0.25s poll cadence:
    # give it a beat to account the placement.
    deadline = time.monotonic() + 5.0
    snap = client.agent().slo()
    while (time.monotonic() < deadline
           and not snap["samples"]["submit_to_placed"]["count"]):
        time.sleep(0.05)
        snap = client.agent().slo()
    names = {o["name"] for o in snap["objectives"]}
    assert names == set(slo.DEFAULT_OBJECTIVES)
    placed = next(o for o in snap["objectives"]
                  if o["metric"] == "submit_to_placed")
    assert placed["observed"]["count"] >= 1
    assert placed["threshold_ms"] == 250.0
    assert "burn_rate" in placed and "budget_remaining_fraction" in placed
    # The monitor publishes through the ordinary sink: gauges ride the
    # metrics surface with zero extra wiring.
    prom = urllib.request.urlopen(
        client.address + "/v1/agent/metrics?format=prometheus",
        timeout=10).read().decode()
    assert "slo_submit_to_placed_p95_ms_burn_rate" in prom
    assert "nomad_trace_spans_dropped_total" in prom


def test_timeline_endpoints_and_sdk(client, agent):
    _, ev_id = _place_one(client, agent)
    tl = client.evaluations().timeline(ev_id)
    assert tl["eval_id"] == ev_id
    assert tl["submit_to_placed_ms"] is not None
    assert tl["spans_seen"] > 0
    assert set(tl["stage_ms"]) <= set(lifecycle.STAGE_KINDS)
    assert tl["segments"], "expected per-stage segments from live spans"

    allocs, _ = client.evaluations().allocations(ev_id)
    assert allocs
    atl = client.allocations().timeline(allocs[0]["id"])
    assert atl["alloc_id"] == allocs[0]["id"]
    assert atl["eval_id"] == ev_id

    from nomad_tpu.api.client import ApiError

    with pytest.raises(ApiError):
        client.evaluations().timeline("no-such-eval")
    with pytest.raises(ApiError):
        client.allocations().timeline("no-such-alloc")


def test_metrics_json_carries_trace_stats(client, agent):
    metrics = client.agent().metrics()
    assert "trace" in metrics
    assert {"spans_dropped", "traces_evicted", "retained"} <= set(
        metrics["trace"])


def test_sse_resume_after_truncation(client, agent):
    """A resume cursor that fell off the bounded ring gets the Truncated
    frame FIRST, then the retained tail — the SSE consumer knows to
    re-list instead of assuming continuity."""
    broker = agent.server.fsm.events
    start_index = broker.get_index()
    for i in range(200):  # blow past the 64-event ring
        broker.publish("Node", "NodeRegistered", key=f"trunc-{i}",
                       payload={})
    req = urllib.request.Request(
        client.address
        + f"/v1/event/stream?format=sse&index={max(start_index, 1)}"
        + "&wait=300ms"
    )
    with urllib.request.urlopen(req, timeout=15.0) as resp:
        body = resp.read().decode()
    frames = [f for f in body.split("\n\n") if f.strip()
              and not f.startswith(":")]
    assert frames, body
    events_seen = []
    for frame in frames:
        lines = dict(line.split(": ", 1) for line in frame.splitlines()
                     if ": " in line)
        events_seen.append(lines["event"])
    assert events_seen[0] == "Truncated"
    assert "NodeRegistered" in events_seen[1:]
    # The resumed tail itself is index-ordered and gapless.
    ids = [int(dict(line.split(": ", 1) for line in f.splitlines()
                    if ": " in line)["id"])
           for f in frames[1:]]
    assert ids == list(range(ids[0], ids[0] + len(ids)))


def test_debug_bundle_slo_and_timeline_sections(client, agent):
    _place_one(client, agent)
    bundle = client.agent().debug_bundle()
    assert bundle["slo"] is not None
    assert {o["name"] for o in bundle["slo"]["objectives"]} == set(
        slo.DEFAULT_OBJECTIVES)
    assert isinstance(bundle["timelines"], list)
    if bundle["timelines"]:
        worst = bundle["timelines"][0]
        assert worst["submit_to_placed_ms"] is not None
        assert "stage_ms" in worst
