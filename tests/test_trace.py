"""Observability surface: eval-lifecycle trace spans + metrics exposition.

Covers the span/tracer primitives (lifecycle, ring-buffer eviction,
cross-RPC context propagation), the end-to-end trace of a real scheduled
evaluation through a dev agent's HTTP API, and golden checks for the
Prometheus text exposition and Chrome trace-event export formats.
"""

import json
import time
import urllib.error
import urllib.request

import pytest

from nomad_tpu import structs, telemetry, trace
from nomad_tpu.trace import StageTimer, Tracer


# ---------------------------------------------------------------------------
# Span / tracer primitives
# ---------------------------------------------------------------------------


def test_span_lifecycle_and_parent_links():
    tr = Tracer(max_traces=8)
    root = tr.start_span("t1", "eval", root=True,
                         annotations={"job_id": "j1"})
    child = tr.start_span("t1", "worker.invoke_scheduler", parent=root)
    grand = tr.start_span("t1", "solver.staging", parent=child)
    grand.annotate("n_nodes", 10)
    grand.finish()
    child.finish()
    root.finish()

    spans = tr.get_trace("t1")
    by_name = {s["name"]: s for s in spans}
    assert set(by_name) == {"eval", "worker.invoke_scheduler",
                            "solver.staging"}
    assert by_name["eval"]["parent_id"] == ""
    assert by_name["worker.invoke_scheduler"]["parent_id"] == \
        by_name["eval"]["span_id"]
    assert by_name["solver.staging"]["parent_id"] == \
        by_name["worker.invoke_scheduler"]["span_id"]
    assert by_name["solver.staging"]["annotations"]["n_nodes"] == 10
    assert by_name["eval"]["annotations"]["job_id"] == "j1"
    for s in spans:
        assert s["end"] is not None and s["end"] >= s["start"]
        assert s["duration_ms"] is not None

    # finish is idempotent: a racing second finish keeps the first stamp
    end = by_name["eval"]["end"]
    root.finish()
    assert tr.get_trace("t1")[0]["end"] == end

    # root context registered for cross-component parenting
    assert tr.root_ctx("t1") == {"trace_id": "t1",
                                 "span_id": by_name["eval"]["span_id"]}


def test_open_spans_visible_and_summary():
    tr = Tracer()
    root = tr.start_span("t2", "eval", root=True)
    spans = tr.get_trace("t2")
    assert len(spans) == 1 and spans[0]["end"] is None
    root.finish()
    tr.mark_done("t2")
    summaries = tr.traces()
    assert summaries[0]["trace_id"] == "t2"
    assert summaries[0]["done"] is True
    assert summaries[0]["root"] == "eval"
    assert summaries[0]["duration_ms"] is not None


def test_ring_buffer_eviction():
    tr = Tracer(max_traces=4)
    for i in range(10):
        tr.start_span(f"t{i}", "eval", root=True).finish()
    assert tr.get_trace("t0") is None
    assert tr.get_trace("t5") is None
    for i in range(6, 10):
        assert tr.get_trace(f"t{i}") is not None
    assert len(tr.traces()) == 4


def test_per_trace_span_cap():
    tr = Tracer(max_spans=5)
    for i in range(9):
        tr.start_span("t", f"s{i}").finish()
    spans = tr.get_trace("t")
    assert len(spans) == 5
    assert tr.traces()[0]["dropped_spans"] == 4


def test_disabled_tracer_is_inert():
    tr = Tracer(enabled=False)
    span = tr.start_span("t", "eval", root=True)
    assert span is trace.NULL_SPAN
    span.annotate("k", 1).finish()
    assert tr.get_trace("t") is None
    assert tr.traces() == []


def test_cross_rpc_context_propagation():
    """The span context survives the request envelope: a Plan carries
    span_ctx through the wire codec, and a remote tracer adopts the
    leader's root so local spans parent on it."""
    from nomad_tpu.api.codec import from_dict, to_dict
    from nomad_tpu.structs import Plan

    leader = Tracer()
    root = leader.start_span("ev-1", "eval", root=True)
    submit = leader.start_span("ev-1", "worker.submit_plan", parent=root)

    plan = Plan(eval_id="ev-1", span_ctx=submit.ctx())
    wire = json.loads(json.dumps(to_dict(plan)))  # the RPC framing
    back = from_dict(Plan, wire)
    assert back.span_ctx == {"trace_id": "ev-1",
                             "span_id": submit.span_id}

    # Receiving side: parent a plan.apply span on the wire context.
    applier_span = leader.start_span(
        back.span_ctx["trace_id"], "plan.apply", parent=back.span_ctx
    )
    applier_span.finish()
    submit.finish()
    root.finish()
    by_name = {s["name"]: s for s in leader.get_trace("ev-1")}
    assert by_name["plan.apply"]["parent_id"] == submit.span_id

    # Follower posture: adopt_root lets a remote worker parent on the
    # leader's root without ever seeing the Span object.
    follower = Tracer()
    follower.adopt_root("ev-1", root.ctx())
    w = follower.start_span("ev-1", "worker.invoke_scheduler",
                            parent=follower.root_ctx("ev-1"))
    w.finish()
    spans = follower.get_trace("ev-1")
    assert spans[0]["parent_id"] == root.span_id


def test_stage_timer_durations_and_spans():
    tr = Tracer()
    st = StageTimer()
    with st.stage("staging"):
        time.sleep(0.002)
    with st.stage("execute"):
        time.sleep(0.001)
    with st.stage("execute"):
        pass
    d = st.durations_ms()
    assert d["staging"] >= 1.0
    assert set(d) == {"staging", "execute"}

    parent = tr.start_span("t", "worker.invoke_scheduler", root=True)
    st.emit_spans(parent)
    parent.finish()
    names = [s["name"] for s in tr.get_trace("t")]
    assert names.count("solver.execute") == 2
    assert "solver.staging" in names

    # The thread-local install + module-level stage() shorthand
    with trace.use_stages(StageTimer()) as st2:
        with trace.stage("readback"):
            pass
    assert "readback" in st2.durations_ms()
    # no timer installed -> inert
    assert trace.active_stages() is trace.NULL_STAGES
    with trace.stage("whatever"):
        pass


# ---------------------------------------------------------------------------
# Exposition formats (golden)
# ---------------------------------------------------------------------------


def test_prometheus_exposition_golden():
    sink = telemetry.InmemSink(interval=10.0, retain=60.0)
    sink.set_gauge(("nomad", "broker", "total_ready"), 3.0)
    sink.incr_counter(("nomad", "broker", "enqueue"), 1.0)
    sink.incr_counter(("nomad", "broker", "enqueue"), 1.0)
    sink.add_sample(("nomad", "worker", "invoke_scheduler", "tpu-batch"), 12.5)
    sink.add_sample(("nomad", "worker", "invoke_scheduler", "tpu-batch"), 7.5)

    text = telemetry.prometheus_text(sink)
    lines = text.strip().splitlines()
    assert "# TYPE nomad_broker_total_ready gauge" in lines
    assert "nomad_broker_total_ready 3" in lines
    assert "# TYPE nomad_broker_enqueue_total counter" in lines
    assert "nomad_broker_enqueue_total 2" in lines
    name = "nomad_worker_invoke_scheduler_tpu_batch_ms"
    assert f"# TYPE {name} summary" in lines
    assert f"{name}_sum 20" in lines
    assert f"{name}_count 2" in lines
    assert f"{name}_max 12.5" in lines
    # Summary quantiles from the reservoir (both samples retained here).
    assert f'{name}{{quantile="0.5"}} 7.5' in lines
    assert f'{name}{{quantile="0.99"}} 12.5' in lines
    # every exposed series name is valid for the Prometheus data model
    # (labels — {quantile="..."} — are not part of the name)
    for line in lines:
        if line.startswith("#"):
            continue
        metric = line.split(" ")[0].split("{")[0]
        assert metric[0].isalpha() or metric[0] in "_:"
        assert all(c.isalnum() or c in "_:" for c in metric)


def test_prometheus_counters_survive_interval_eviction():
    """Counters are process-lifetime cumulative: the ring evicting old
    intervals must never make an exposed _total decrease (Prometheus
    rate()/increase() treats decreases as counter resets)."""
    sink = telemetry.InmemSink(interval=0.01, retain=0.02)
    sink.incr_counter(("c",), 5.0)
    sink.add_sample(("s",), 3.0)
    time.sleep(0.05)
    # Roll the ring well past the first interval.
    for _ in range(4):
        sink.incr_counter(("c",), 1.0)
        time.sleep(0.015)
    text = telemetry.prometheus_text(sink)
    assert "c_total 9" in text        # 5 + 4x1, incl. evicted intervals
    assert "s_ms_sum 3" in text
    assert "s_ms_count 1" in text


def test_inmem_sink_data_structure():
    sink = telemetry.InmemSink()
    sink.set_gauge(("a", "b"), 1.0)
    sink.incr_counter(("c",), 2.0)
    sink.add_sample(("d",), 5.0)
    data = sink.data()
    assert len(data) == 1
    ivl = data[0]
    assert ivl["gauges"]["a.b"] == 1.0
    assert ivl["counters"]["c"]["sum"] == 2.0
    assert ivl["samples"]["d"] == {
        "count": 1, "sum": 5.0, "min": 5.0, "max": 5.0, "mean": 5.0,
        "stddev": 0.0, "last": 5.0, "p50": 5.0, "p95": 5.0, "p99": 5.0,
    }
    json.dumps(data)  # JSON-able as served


def test_chrome_trace_export_golden():
    tr = Tracer()
    root = tr.start_span("t", "eval", root=True)
    child = tr.start_span("t", "plan.apply", parent=root,
                          annotations={"alloc_index": 7})
    child.finish()
    root.finish()
    doc = tr.chrome_trace("t")
    assert doc["displayTimeUnit"] == "ms"
    events = doc["traceEvents"]
    complete = [e for e in events if e["ph"] == "X"]
    meta = [e for e in events if e["ph"] == "M"]
    assert {e["name"] for e in complete} == {"eval", "plan.apply"}
    for e in complete:
        assert e["pid"] == 1 and isinstance(e["tid"], int)
        assert e["ts"] > 0 and e["dur"] >= 0
    apply_ev = next(e for e in complete if e["name"] == "plan.apply")
    assert apply_ev["args"]["alloc_index"] == 7
    assert apply_ev["args"]["parent_id"]
    assert meta and meta[0]["name"] == "thread_name"
    json.dumps(doc)  # loads into Perfetto as-is
    assert tr.chrome_trace("nope") is None


def test_blocked_eval_wait_spans_all_finish():
    """An eval that transits the blocked queue gets two broker.wait
    segments (blocked->ready restart), BOTH finished — an open leaked
    span would render as a bogus until-now bar in the Chrome export."""
    from nomad_tpu.server.eval_broker import EvalBroker
    from nomad_tpu.structs import Evaluation, generate_uuid

    tracer = trace.configure(max_traces=32, enabled=True)
    b = EvalBroker(5.0, 3)
    b.set_enabled(True)
    job_id = generate_uuid()

    def _ev():
        return Evaluation(id=generate_uuid(), priority=50, type="service",
                          job_id=job_id, status=structs.EVAL_STATUS_PENDING)

    first, second = _ev(), _ev()
    b.enqueue(first)
    b.enqueue(second)  # blocks behind first (per-job serialization)

    ev, tok = b.dequeue(["service"], timeout=1.0)
    assert ev.id == first.id
    b.ack(ev.id, tok)  # unblocks second
    ev2, tok2 = b.dequeue(["service"], timeout=1.0)
    assert ev2.id == second.id
    b.ack(ev2.id, tok2)

    for tid in (first.id, second.id):
        summary = next(t for t in tracer.traces() if t["trace_id"] == tid)
        assert summary["open_spans"] == 0, f"leaked open span on {tid}"
        assert summary["done"] is True
    waits = [s for s in tracer.get_trace(second.id)
             if s["name"] == "broker.wait"]
    assert len(waits) == 2
    assert all(s["end"] is not None for s in waits)
    assert any(s["annotations"].get("blocked") for s in waits)


# ---------------------------------------------------------------------------
# End-to-end: dev agent -> HTTP trace + metrics endpoints
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def agent(tmp_path_factory):
    from nomad_tpu.agent import Agent, AgentConfig

    config = AgentConfig.dev()
    config.data_dir = str(tmp_path_factory.mktemp("trace-agent"))
    config.http_port = 0
    # The TPU factories (on the CPU jax backend) so the solver stage
    # spans ride the device path.
    config.scheduler_backend = "tpu"
    a = Agent(config)
    a.start()
    yield a
    a.shutdown()


def _get(agent, path):
    with urllib.request.urlopen(agent.http.addr + path, timeout=10) as resp:
        body = resp.read()
        return resp.status, resp.headers.get("Content-Type", ""), body


def _get_json(agent, path):
    status, _ctype, body = _get(agent, path)
    assert status == 200
    return json.loads(body.decode())


def test_eval_trace_end_to_end(agent):
    from nomad_tpu import mock
    from nomad_tpu.api import ApiClient

    client = ApiClient(address=agent.http.addr)
    deadline = time.monotonic() + 15
    while time.monotonic() < deadline:
        nodes, _ = client.nodes().list()
        if nodes and nodes[0]["status"] == "ready":
            break
        time.sleep(0.1)
    else:
        pytest.fail("dev node never became ready")

    job = mock.job()
    job.task_groups[0].count = 2
    job.task_groups[0].tasks[0].driver = "mock_driver"
    job.task_groups[0].tasks[0].config = {"run_for": "20", "exit_code": "0"}
    job.task_groups[0].tasks[0].resources.networks = []
    eval_id, _meta = client.jobs().register(job)

    # Eval terminal + the root span finished (ack lands just after the
    # status write).
    deadline = time.monotonic() + 60
    spans = None
    while time.monotonic() < deadline:
        ev, _ = client.evaluations().info(eval_id)
        if ev.status == structs.EVAL_STATUS_COMPLETE:
            doc = _get_json(agent, f"/v1/evaluation/{eval_id}/trace")
            spans = doc["spans"]
            root = next(s for s in spans if s["name"] == "eval")
            if root["end"] is not None:
                break
        time.sleep(0.1)
    else:
        pytest.fail("eval never completed with a finished root span")

    by_name = {}
    for s in spans:
        by_name.setdefault(s["name"], []).append(s)

    # The acceptance span set: broker enqueue->dequeue, scheduler
    # invocation, solver stage breakdown, plan submit/queue/apply, FSM.
    for required in (
        "eval", "broker.wait", "worker.invoke_scheduler",
        "solver.staging", "solver.transfer", "solver.execute",
        "solver.readback", "worker.submit_plan", "plan.queue_wait",
        "plan.evaluate", "plan.apply", "fsm.apply",
    ):
        assert required in by_name, f"missing span {required}: {list(by_name)}"

    ids = {s["span_id"]: s for s in spans}
    root = by_name["eval"][0]
    assert root["annotations"]["job_id"] == job.id
    assert root["annotations"]["outcome"] == "ack"

    eps = 5e-3  # clock-read ordering slack between threads
    for s in spans:
        # Monotonic, nesting-consistent timestamps.
        if s["end"] is not None:
            assert s["end"] >= s["start"]
        parent = ids.get(s["parent_id"])
        if parent is not None:
            assert s["start"] >= parent["start"] - eps
            if parent["end"] is not None and s["end"] is not None:
                assert s["end"] <= parent["end"] + eps
        # Every non-root span links back into the tree — but for the one
        # that precedes the root: the front door's handler returns before
        # (or just as) the broker opens the eval's root.
        if s["name"] not in ("eval", "frontdoor.job_register"):
            assert s["parent_id"] in ids

    # Solver stages nest under the scheduler invocation.
    inv = by_name["worker.invoke_scheduler"][0]
    for stage in ("solver.staging", "solver.transfer",
                  "solver.execute", "solver.readback"):
        assert any(s["parent_id"] == inv["span_id"]
                   for s in by_name[stage])
    # plan.* under the worker's submit span (the cross-boundary ctx).
    submit = by_name["worker.submit_plan"][0]
    assert by_name["plan.apply"][0]["parent_id"] == submit["span_id"]
    assert by_name["fsm.apply"][0]["annotations"]["msg_type"] in (
        "alloc_update", "eval_update",
    )

    # Chrome export of the same trace.
    doc = _get_json(agent, f"/v1/evaluation/{eval_id}/trace?format=chrome")
    names = {e["name"] for e in doc["traceEvents"] if e["ph"] == "X"}
    assert "eval" in names and "plan.apply" in names

    # Trace listing includes the completed trace.
    listing = _get_json(agent, "/v1/agent/traces")
    entry = next(t for t in listing if t["trace_id"] == eval_id)
    assert entry["done"] is True and entry["spans"] >= 10

    # Unknown eval -> 404
    try:
        _get(agent, "/v1/evaluation/ffffffff/trace")
    except urllib.error.HTTPError as e:
        assert e.code == 404
    else:
        pytest.fail("expected 404 for unknown trace")


def test_frontdoor_span_precedes_the_root(agent):
    """Job.Register's own time is in the eval's trace, ahead of the root
    span the broker opens at enqueue; the listing still names ``eval``."""
    from nomad_tpu import mock
    from nomad_tpu.api import ApiClient

    client = ApiClient(address=agent.http.addr)
    job = mock.job()
    job.task_groups[0].count = 1
    job.task_groups[0].tasks[0].driver = "mock_driver"
    job.task_groups[0].tasks[0].config = {"run_for": "20", "exit_code": "0"}
    job.task_groups[0].tasks[0].resources.networks = []
    eval_id, _meta = client.jobs().register(job)

    deadline = time.monotonic() + 60
    while time.monotonic() < deadline:
        spans = _get_json(agent, f"/v1/evaluation/{eval_id}/trace")["spans"]
        by_name = {s["name"]: s for s in spans}
        if by_name.get("eval", {}).get("end") is not None:
            break
        time.sleep(0.1)
    else:
        pytest.fail("eval never completed with a finished root span")

    door = by_name["frontdoor.job_register"]
    root = by_name["eval"]
    assert door["parent_id"] == "" and door["end"] is not None
    assert door["start"] <= root["start"]
    assert door["end"] <= root["start"] + 5e-3
    assert 0.0 <= door["annotations"]["cpu_ms"] <= door["duration_ms"] + 0.5
    for child in ("frontdoor.raft_job", "frontdoor.raft_eval"):
        s = by_name[child]
        assert s["parent_id"] == door["span_id"]
        assert door["start"] <= s["start"] <= s["end"] <= door["end"]
    assert by_name["frontdoor.raft_job"]["end"] <= \
        by_name["frontdoor.raft_eval"]["start"]
    # The eval's root opens inside the second apply (FSM -> broker enqueue).
    raft_eval = by_name["frontdoor.raft_eval"]
    assert raft_eval["start"] <= root["start"] <= raft_eval["end"] + 5e-3

    # The cuts inside staging and execute ride the same trace.
    names = {s["name"] for s in spans}
    assert {"solver.staging.mask", "solver.staging.usage_base",
            "solver.execute.hold", "solver.execute.launch",
            "solver.execute.wake", "solver.execute.device_wait"} <= names
    assert "cpu_ms" in by_name["plan.evaluate"]["annotations"]
    assert "cpu_ms" in by_name["solver.staging"]["annotations"]

    listing = _get_json(agent, "/v1/agent/traces")
    entry = next(t for t in listing if t["trace_id"] == eval_id)
    assert entry["root"] == "eval"
    assert entry["duration_ms"] == pytest.approx(root["duration_ms"], abs=0.01)


def test_agent_metrics_endpoints(agent):
    from nomad_tpu import mock

    # Self-sufficient: drive one eval through the pipeline so the broker
    # counters and fsm.apply samples exist even when this test runs alone
    # (a -k filter or single-test rerun must not depend on the e2e test
    # having populated the module-scoped agent first).
    job = mock.job()
    job.task_groups[0].count = 1
    job.task_groups[0].tasks[0].driver = "mock_driver"
    job.task_groups[0].tasks[0].config = {"run_for": "10", "exit_code": "0"}
    job.task_groups[0].tasks[0].resources.networks = []
    eval_id, _ = agent.server.job_register(job)
    agent.server.wait_for_eval(eval_id, timeout=30)

    doc = _get_json(agent, "/v1/agent/metrics")
    assert "intervals" in doc and doc["intervals"]
    merged_samples = {}
    merged_counters = {}
    for ivl in doc["intervals"]:
        merged_samples.update(ivl["samples"])
        merged_counters.update(ivl["counters"])
    # The new instrumentation feeds the sink: broker counters + fsm
    # per-message-type apply timers ride every job registration.
    assert any(k.endswith("broker.enqueue") for k in merged_counters)
    assert any(".fsm.apply." in k for k in merged_samples)

    # Device-mirror cache stats ride the same endpoint (the delta-roll
    # economy: rolls vs full rebuilds).
    assert "mirror_cache" in doc
    for k in ("hits", "misses", "delta_rolls", "full_rebuilds",
              "rows_restaged"):
        assert k in doc["mirror_cache"], doc["mirror_cache"]

    status, ctype, body = _get(agent, "/v1/agent/metrics?format=prometheus")
    assert status == 200
    assert ctype.startswith("text/plain")
    text = body.decode()
    assert "# TYPE " in text
    assert "broker_enqueue_total" in text
    assert "fsm_apply" in text
    assert "nomad_mirror_cache_delta_rolls_total" in text
    assert "nomad_mirror_cache_full_rebuilds_total" in text
