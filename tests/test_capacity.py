"""Capacity & solver observatory tests.

Four layers:

- **accountant math** against a raw StateStore: utilization / density /
  lane / fragmentation / stranded accounting on hand-built states, plus
  the DIFFERENTIAL contract — an accountant rolled forward through the
  change logs must report byte-identical aggregates to a fresh one that
  full-rebuilt from the same state (the device mirror's fuzz posture).
- **solver panel** units: padding economy, bucket occupancy, and the
  compile-trigger classification (precompile / bucket_crossing / first_roll).
- **PromText** units: the shared exposition line-builder's sanitation,
  TYPE-once, and conflict guards (the one-sanitizer satellite).
- **live-agent e2e**: /v1/agent/capacity and /v1/agent/solver over HTTP
  + SDK, the debug bundle's new sections, the GOLDEN full-scrape
  exposition test (TYPE-before-sample, no duplicate/conflicting TYPE,
  every name legal), and the structural SDK-parity gate (every
  /v1/agent/* GET route must have an AgentApi accessor — slo/admission/
  express each drifted in late; capacity/solver cannot).
"""

import inspect
import json
import re
import time
import urllib.request

import pytest

from nomad_tpu import mock, structs, telemetry
from nomad_tpu.capacity import (
    CapacityAccountant,
    CapacityConfig,
    DEFAULT_REFERENCE_SHAPES,
    FRAG_BINS,
)
from nomad_tpu.state.store import StateStore
from nomad_tpu.structs import Allocation, Job, Resources

# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------


def _node(i, cpu=4000, memory_mb=8192):
    n = mock.node()
    n.id = f"cap-node-{i:03d}"
    n.resources = Resources(cpu=cpu, memory_mb=memory_mb,
                            disk_mb=100 * 1024, iops=150)
    n.reserved = Resources()
    return n


def _job(job_id, jtype=structs.JOB_TYPE_SERVICE, express=False):
    job = mock.job()
    job.id = job_id
    job.name = job_id
    job.type = jtype
    job.express = express
    return job


def _alloc(job, node_id, cpu=500, memory_mb=256):
    return Allocation(
        id=structs.generate_uuid(),
        eval_id=structs.generate_uuid(),
        name=f"{job.name}.web[0]",
        node_id=node_id,
        job_id=job.id,
        job=job,
        task_group="web",
        resources=Resources(cpu=cpu, memory_mb=memory_mb),
        desired_status=structs.ALLOC_DESIRED_STATUS_RUN,
    )


def _accountant(store, **cfg):
    return CapacityAccountant(
        lambda: store, CapacityConfig.parse(cfg or None)
    )


# ---------------------------------------------------------------------------
# config parsing
# ---------------------------------------------------------------------------


def test_capacity_config_defaults_and_validation():
    cfg = CapacityConfig.parse(None)
    assert cfg.enabled and cfg.poll_interval == 1.0
    assert cfg.reference_shapes == [dict(s)
                                    for s in DEFAULT_REFERENCE_SHAPES]
    assert not CapacityConfig.parse({"enabled": False}).enabled
    with pytest.raises(ValueError):
        CapacityConfig.parse({"poll_intervall": 1.0})  # typo'd key
    with pytest.raises(ValueError):
        CapacityConfig.parse({"poll_interval": 0})
    with pytest.raises(ValueError):
        CapacityConfig.parse({"reference_shapes": []})
    with pytest.raises(ValueError):
        CapacityConfig.parse(
            {"reference_shapes": [{"name": "zero"}]})  # asks for nothing


# ---------------------------------------------------------------------------
# accountant math
# ---------------------------------------------------------------------------


def test_utilization_lanes_and_density():
    store = StateStore()
    store.upsert_nodes(1, [_node(i) for i in range(4)])
    svc = _job("svc")
    bat = _job("bat", jtype=structs.JOB_TYPE_BATCH)
    exp = _job("exp", jtype=structs.JOB_TYPE_BATCH, express=True)
    store.upsert_allocs(2, [
        _alloc(svc, "cap-node-000", cpu=1000, memory_mb=1024),
        _alloc(bat, "cap-node-001", cpu=400, memory_mb=512),
        _alloc(exp, "cap-node-001", cpu=100, memory_mb=128),
    ])
    acct = _accountant(store)
    acct.refresh()
    snap = acct.snapshot()
    assert snap["nodes"] == {"total": 4, "schedulable": 4, "occupied": 2}
    assert snap["total"]["cpu"] == 4 * 4000
    assert snap["used"]["cpu"] == 1500
    assert snap["lanes"]["service"]["used"]["cpu"] == 1000
    assert snap["lanes"]["batch"]["used"]["cpu"] == 400
    assert snap["lanes"]["express"]["used"]["cpu"] == 100
    assert snap["lanes"]["express"]["allocs"] == 1
    assert snap["utilization"]["cpu"] == pytest.approx(1500 / 16000)
    # Density judges only the two occupied nodes' capacity.
    assert snap["binpack_density"]["cpu"] == pytest.approx(1500 / 8000)
    # Fragmentation: the two empty nodes sit in the top decile, the two
    # occupied ones lower.
    assert sum(snap["fragmentation"]["free_fraction"]["cpu"]) == 4
    assert snap["fragmentation"]["free_fraction"]["cpu"][FRAG_BINS - 1] == 2


def test_stranded_capacity_definition():
    """Two nodes: one nearly full (free 300 cpu), one empty. A shape of
    1000 cpu fits only the empty node — the full node's free capacity is
    stranded with respect to it."""
    store = StateStore()
    store.upsert_nodes(1, [_node(0, cpu=4000), _node(1, cpu=4000)])
    job = _job("filler")
    store.upsert_allocs(2, [_alloc(job, "cap-node-000", cpu=3700,
                                   memory_mb=256)])
    acct = _accountant(store, reference_shapes=[
        {"name": "big", "cpu": 1000, "memory_mb": 512},
    ])
    acct.refresh()
    s = acct.snapshot()["stranded"][0]
    assert s["shape"] == "big"
    assert s["nodes_fitting"] == 1
    # free: 300 (node 0, stranded) + 4000 (node 1) = 4300
    assert s["stranded_pct"] == pytest.approx(300 / 4300, abs=1e-5)
    # 4 copies of 1000 cpu fit on the empty node.
    assert s["placeable_count"] == 4


def test_non_schedulable_nodes_excluded():
    store = StateStore()
    nodes = [_node(0), _node(1)]
    nodes[1].drain = True
    store.upsert_nodes(1, nodes)
    acct = _accountant(store)
    acct.refresh()
    snap = acct.snapshot()
    assert snap["nodes"]["total"] == 2
    assert snap["nodes"]["schedulable"] == 1
    assert snap["total"]["cpu"] == 4000


def test_incremental_roll_matches_full_rebuild():
    """The differential contract: after arbitrary node/alloc churn, the
    accountant that ROLLED through the change logs reports the same
    aggregates as a fresh accountant that rebuilt from scratch."""
    store = StateStore()
    store.upsert_nodes(1, [_node(i) for i in range(6)])
    rolled = _accountant(store)
    rolled.refresh()
    assert rolled.rebuilds == 1

    svc = _job("svc")
    bat = _job("bat", jtype=structs.JOB_TYPE_BATCH)
    allocs = [
        _alloc(svc, f"cap-node-{i:03d}", cpu=200 * (i + 1))
        for i in range(4)
    ]
    store.upsert_allocs(2, allocs)
    store.upsert_allocs(3, [_alloc(bat, "cap-node-005", cpu=900)])
    # Node churn too: a drain flip and a deletion.
    store.update_node_drain(4, "cap-node-002", True)
    store.delete_node(5, "cap-node-003")
    # Stop one alloc (its node's usage must roll back down).
    stopped = allocs[0].copy()
    stopped.desired_status = structs.ALLOC_DESIRED_STATUS_STOP
    store.upsert_allocs(6, [stopped])

    rolled.refresh()
    assert rolled.rolls >= 1 and rolled.rebuilds == 1

    fresh = _accountant(store)
    fresh.refresh()
    a, b = rolled.snapshot(), fresh.snapshot()
    for key in ("nodes", "total", "used", "free", "utilization",
                "binpack_density", "lanes", "fragmentation", "stranded"):
        assert a[key] == b[key], key


def test_store_replacement_forces_rebuild():
    store1 = StateStore()
    store1.upsert_nodes(1, [_node(0)])
    holder = {"store": store1}
    acct = CapacityAccountant(lambda: holder["store"],
                              CapacityConfig.parse(None))
    acct.refresh()
    assert acct.rebuilds == 1
    store2 = StateStore()
    store2.upsert_nodes(1, [_node(0), _node(1)])
    holder["store"] = store2
    acct.refresh()
    assert acct.rebuilds == 2
    assert acct.snapshot()["nodes"]["total"] == 2


def test_capacity_event_snapshot_published():
    from nomad_tpu.events import EventBroker, OBSERVER_TOPICS

    store = StateStore()
    store.upsert_nodes(1, [_node(0)])
    broker = EventBroker(register=False)
    acct = CapacityAccountant(lambda: store, CapacityConfig.parse(None),
                              events=broker)
    acct.refresh()
    acct.publish_event()
    events = broker.all_events()
    assert len(events) == 1
    e = events[0]
    assert e.topic == "Capacity" and e.type == "CapacitySnapshot"
    assert e.topic in OBSERVER_TOPICS
    assert "utilization" in e.payload and "stranded" in e.payload
    # The canonical determinism reduction ignores observer topics.
    from nomad_tpu.simcluster.scenario import canonical_events

    assert canonical_events(events)["groups"] == 0


# ---------------------------------------------------------------------------
# solver panel
# ---------------------------------------------------------------------------


def test_solver_panel_economy_and_triggers():
    from nomad_tpu.tpu.solver import SolverPanel

    panel = SolverPanel()
    with panel.precompile():
        panel.record_solve("exact", 100, 128, 8, 8, 0, 50.0)
    panel.record_solve("exact", 100, 128, 8, 8, 8, 1.0)     # warm: no record
    panel.record_solve("exact", 100, 128, 30, 32, 30, 12.0)  # first_roll
    panel.record_solve("waterfill", 900, 1024, 500, 0, 500, 20.0)  # crossing
    snap = panel.snapshot()
    assert snap["solves"] == 4
    assert snap["placed"] == 538
    assert snap["compiles"]["by_trigger"] == {
        "bucket_crossing": 1, "first_roll": 1, "precompile": 1,
    }
    # Padding economy: live/padded over every dispatched row.
    assert snap["node_padding_waste"] == pytest.approx(
        1 - (100 * 3 + 900) / (128 * 3 + 1024), abs=1e-4)
    assert snap["count_padding_waste"] == pytest.approx(
        1 - (8 + 8 + 30) / (8 + 8 + 32), abs=1e-4)
    buckets = {b["bucket"]: b for b in snap["node_buckets"]}
    assert buckets[128]["solves"] == 3
    assert buckets[128]["occupancy"] == pytest.approx(100 / 128, abs=1e-3)
    assert buckets[1024]["solves"] == 1
    assert snap["device_ms_per_placement"] > 0


# ---------------------------------------------------------------------------
# PromText: the one shared exposition builder
# ---------------------------------------------------------------------------


def test_promtext_sanitizes_and_types_once():
    b = telemetry.PromText()
    b.counter("nomad.weird-name.total", 3)
    b.counter("nomad.weird-name.total", 4, labels={"reason": 'a"b\n'})
    b.gauge("9starts_with_digit", 1.5)
    text = b.text()
    assert text.count("# TYPE nomad_weird_name_total counter") == 1
    assert 'reason="a\\"b\\n"' in text
    assert "_9starts_with_digit 1.5" in text


def test_promtext_conflicting_type_raises():
    b = telemetry.PromText()
    b.counter("nomad_x_total", 1)
    with pytest.raises(ValueError):
        b.gauge("nomad_x_total", 2)


# ---------------------------------------------------------------------------
# live agent e2e + golden exposition + SDK parity
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def agent(tmp_path_factory):
    from nomad_tpu.agent import Agent, AgentConfig

    config = AgentConfig.dev()
    config.data_dir = str(tmp_path_factory.mktemp("capacity-agent"))
    config.http_port = 0
    config.enable_debug = True
    config.capacity = {"poll_interval": 0.2, "events_interval": 0.5}
    a = Agent(config)
    a.start()
    # Wait for the dev node to register so the observatory has a cell.
    from nomad_tpu.api import ApiClient

    client = ApiClient(address=a.http.addr)
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        nodes, _ = client.nodes().list()
        if nodes and nodes[0]["status"] == "ready":
            break
        time.sleep(0.1)
    else:
        pytest.fail("dev node never became ready")
    yield a
    a.shutdown()


def _get(agent, path):
    with urllib.request.urlopen(agent.http.addr + path, timeout=10) as r:
        return r.status, r.read()


def _place_one(agent):
    from nomad_tpu.api import ApiClient

    client = ApiClient(address=agent.http.addr)
    job = mock.job()
    job.task_groups[0].count = 2
    job.task_groups[0].tasks[0].driver = "mock_driver"
    job.task_groups[0].tasks[0].config = {"run_for": "20",
                                          "exit_code": "0"}
    job.task_groups[0].tasks[0].resources.networks = []
    eval_id, _ = client.jobs().register(job)
    deadline = time.monotonic() + 60
    while time.monotonic() < deadline:
        ev, _ = client.evaluations().info(eval_id)
        if ev.status == structs.EVAL_STATUS_COMPLETE:
            return
        time.sleep(0.1)
    pytest.fail("eval never completed")


def test_capacity_endpoint_e2e(agent):
    _place_one(agent)
    status, body = _get(agent, "/v1/agent/capacity")
    assert status == 200
    snap = json.loads(body)
    assert snap["nodes"]["total"] >= 1
    assert snap["used"]["cpu"] > 0
    assert {s["shape"] for s in snap["stranded"]} == {
        "small", "medium", "large"}
    # Prometheus face of the same endpoint.
    status, body = _get(agent, "/v1/agent/capacity?format=prometheus")
    assert status == 200
    text = body.decode()
    assert "# TYPE nomad_capacity_utilization gauge" in text
    assert 'nomad_capacity_stranded_pct{shape="large"}' in text
    # SDK accessor parity for the new endpoints.
    from nomad_tpu.api import ApiClient

    api = ApiClient(address=agent.http.addr).agent()
    assert api.capacity()["nodes"] == snap["nodes"]
    solver = api.solver()
    assert solver["panel"]["solves"] >= 1
    assert solver["mirror_cache"]["hits"] >= 0
    assert "roll_ms" in solver["mirror_cache"]
    assert solver["panel"]["compiles"]["total"] >= 1


def test_capacity_events_flow(agent):
    """The periodic Capacity snapshots land on the event stream (and
    only there — the canonical digest reduction skips them)."""
    from nomad_tpu.api import ApiClient

    client = ApiClient(address=agent.http.addr)
    deadline = time.monotonic() + 15
    while time.monotonic() < deadline:
        _idx, events, _trunc = client.events().list(
            topics=["Capacity"])
        if events:
            assert events[0]["type"] == "CapacitySnapshot"
            assert "utilization" in events[0]["payload"]
            return
        time.sleep(0.2)
    pytest.fail("no Capacity snapshot event within 15s")


def test_debug_bundle_carries_capacity_and_solver(agent):
    from nomad_tpu.api import ApiClient
    from nomad_tpu.bundle import BUNDLE_SECTIONS

    bundle = ApiClient(address=agent.http.addr).agent().debug_bundle()
    assert set(BUNDLE_SECTIONS) <= set(bundle)
    assert bundle["capacity"]["nodes"]["total"] >= 1
    assert "stranded" in bundle["capacity"]
    assert bundle["solver"]["solves"] >= 1
    assert "node_padding_waste" in bundle["solver"]


# The Prometheus data-model grammar for metric names.
_NAME_RE = re.compile(r"^[a-zA-Z_:][a-zA-Z0-9_:]*$")


def test_golden_prometheus_exposition(agent):
    """Parse the FULL scrape and assert the exposition-format
    invariants every appender must jointly satisfy: a family's # TYPE
    line precedes its first sample, no family carries duplicate or
    conflicting TYPE lines (across appenders!), and every name matches
    the data-model grammar."""
    status, body = _get(agent, "/v1/agent/metrics?format=prometheus")
    assert status == 200
    typed = {}
    seen_sample_names = set()
    for lineno, line in enumerate(body.decode().splitlines(), 1):
        if not line or line.startswith("#"):
            if line.startswith("# TYPE "):
                _, _, name, mtype = line.split(None, 3)
                assert _NAME_RE.match(name), (lineno, name)
                # Duplicate TYPE lines (conflicting or not) are invalid
                # exposition, and TYPE must precede the first sample.
                assert name not in typed, \
                    f"line {lineno}: duplicate TYPE for {name}"
                assert name not in seen_sample_names, \
                    f"line {lineno}: TYPE after first sample of {name}"
                typed[name] = mtype
            continue
        # Sample line: name{labels} value  |  name value
        m = re.match(r"^([a-zA-Z_:][a-zA-Z0-9_:]*)(\{.*\})?\s+(\S+)$",
                     line)
        assert m, f"line {lineno}: unparseable sample {line!r}"
        name = m.group(1)
        seen_sample_names.add(name)
        float(m.group(3))  # value must parse
        # The family's TYPE must already be declared. Suffixed series
        # (_sum/_count/_bucket/_max summaries+histograms) hang off their
        # base family.
        base_candidates = [name] + [
            name[: -len(sfx)] for sfx in ("_sum", "_count", "_bucket")
            if name.endswith(sfx)
        ]
        assert any(c in typed for c in base_candidates), \
            f"line {lineno}: sample {name} with no preceding TYPE"
    # The observatory families made it onto the main scrape.
    assert "nomad_capacity_utilization" in typed
    assert "nomad_solver_solves_total" in typed


def test_sdk_parity_every_agent_get_route_has_accessor(agent):
    """STRUCTURAL parity gate: every /v1/agent/* route the HTTP server
    registers must be referenced by an AgentApi accessor. slo,
    admission, and express each drifted in one at a time before this
    test; capacity/solver (and whatever comes next) cannot."""
    from nomad_tpu.api.client import AgentApi

    sdk_source = inspect.getsource(AgentApi)
    missing = []
    for pattern, _template, _handler in agent.http.routes:
        path = pattern.pattern
        if not path.startswith(r"^/v1/agent/"):
            continue
        literal = path.lstrip("^").rstrip("$")
        if literal not in sdk_source:
            missing.append(literal)
    assert not missing, (
        f"/v1/agent routes without an AgentApi accessor: {missing}"
    )
