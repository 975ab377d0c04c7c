"""HTTP API + SDK tests (reference: command/agent/http_test.go,
*_endpoint_test.go, api/ package tests run against a live agent)."""

import threading
import time

import pytest

from nomad_tpu import mock, structs
from nomad_tpu.agent import Agent, AgentConfig
from nomad_tpu.api import ApiClient, ApiError, QueryOptions
from nomad_tpu.api.codec import from_dict, to_dict
from nomad_tpu.structs import Job


@pytest.fixture(scope="module")
def agent(tmp_path_factory):
    config = AgentConfig.dev()
    config.data_dir = str(tmp_path_factory.mktemp("agent"))
    config.http_port = 0  # auto-assign
    config.scheduler_backend = "host"
    a = Agent(config)
    a.start()
    yield a
    a.shutdown()


@pytest.fixture()
def client(agent):
    return ApiClient(address=agent.http.addr)


def test_codec_roundtrip():
    job = mock.job()
    data = to_dict(job)
    back = from_dict(Job, data)
    assert back.id == job.id
    assert back.task_groups[0].tasks[0].resources.cpu == 500
    assert back.task_groups[0].tasks[0].resources.networks[0].dynamic_ports == ["http"]
    assert back.constraints[0].l_target == "$attr.kernel.name"
    assert back.update.stagger == job.update.stagger
    # Unknown keys ignored
    data["bogus_field"] = 1
    from_dict(Job, data)


def test_agent_self(client, agent):
    info = client.agent().self_info()
    assert info["config"]["server_enabled"] is True
    assert info["config"]["client_enabled"] is True
    assert info["stats"]["leader"] is True
    assert client.status().leader() == agent.http.addr
    members = client.agent().members()
    assert len(members) == 1 and members[0]["leader"]
    # Device-solver health is operator-visible: the device this process
    # acquired at start (the suite pins the cpu backend) and the breaker
    # ride agent-info.
    solver = info["stats"]["server"]["scheduler"]
    assert solver["device"]["acquired"] is True
    assert solver["device"]["platform"] == "cpu"
    assert solver["device"]["count"] >= 1 and solver["device"]["device_kind"]
    assert solver["breaker"]["state"] == "closed"


def test_job_lifecycle_over_http(client, agent):
    # Wait for the dev client node to be ready
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
    job.task_groups[0].tasks[0].config = {"run_for": "60", "exit_code": "0"}
    job.task_groups[0].tasks[0].resources.networks = []

    eval_id, meta = client.jobs().register(job)
    assert eval_id
    assert meta.last_index > 0

    # Poll the eval to completion through the API
    deadline = time.monotonic() + 15
    while time.monotonic() < deadline:
        ev, _ = client.evaluations().info(eval_id)
        if ev.status == structs.EVAL_STATUS_COMPLETE:
            break
        time.sleep(0.1)
    else:
        pytest.fail(f"eval did not complete: {ev}")

    # Job visible in list + info
    jobs, _ = client.jobs().list()
    assert any(j["id"] == job.id for j in jobs)
    info, _ = client.jobs().info(job.id)
    assert info.id == job.id
    assert info.task_groups[0].count == 2

    allocs, _ = client.jobs().allocations(job.id)
    assert len(allocs) == 2

    evals, _ = client.jobs().evaluations(job.id)
    assert any(e.id == eval_id for e in evals)

    # Alloc detail incl. metrics
    alloc, _ = client.allocations().info(allocs[0]["id"])
    assert alloc.job_id == job.id
    assert alloc.metrics is not None

    # Eval allocations endpoint
    eallocs, _ = client.evaluations().allocations(eval_id)
    assert len(eallocs) == 2

    # Deregister
    client.jobs().deregister(job.id)
    deadline = time.monotonic() + 15
    while time.monotonic() < deadline:
        allocs, _ = client.jobs().allocations(job.id)
        if all(a["desired_status"] == "stop" for a in allocs):
            break
        time.sleep(0.1)
    else:
        pytest.fail("allocs never stopped")


def test_node_endpoints(client, agent):
    nodes, meta = client.nodes().list()
    assert len(nodes) == 1
    node_id = nodes[0]["id"]

    node, _ = client.nodes().info(node_id)
    assert node.id == node_id
    assert node.resources.cpu > 0

    out, _ = client.nodes().toggle_drain(node_id, True)
    node, _ = client.nodes().info(node_id)
    assert node.drain is True
    client.nodes().toggle_drain(node_id, False)

    client.nodes().force_evaluate(node_id)


def test_errors(client):
    with pytest.raises(ApiError) as e:
        client.jobs().info("does-not-exist")
    assert e.value.code == 404

    with pytest.raises(ApiError) as e:
        client.query("/v1/bogus-endpoint")
    assert e.value.code == 404

    # Invalid job rejected with 400
    with pytest.raises(ApiError) as e:
        client.jobs().register(Job(id="bad job"))
    assert e.value.code == 400


def test_blocking_query(client, agent):
    """?index=N blocks until the table index passes N (http.go:228-250)."""
    _, meta = client.jobs().list()
    start_index = meta.last_index

    result = {}

    def blocked():
        jobs, m2 = client.jobs().list(
            QueryOptions(wait_index=start_index, wait_time="10s")
        )
        result["index"] = m2.last_index
        result["done_at"] = time.monotonic()

    t = threading.Thread(target=blocked)
    t0 = time.monotonic()
    t.start()
    time.sleep(0.3)
    # Trigger a jobs-table write
    job = mock.job()
    job.task_groups[0].tasks[0].driver = "mock_driver"
    job.task_groups[0].tasks[0].config = {"run_for": "0.1"}
    job.task_groups[0].tasks[0].resources.networks = []
    agent.server.job_register(job)
    t.join(timeout=10)
    assert not t.is_alive(), "blocking query never returned"
    assert result["index"] > start_index
    assert result["done_at"] - t0 >= 0.25  # actually blocked


def test_agent_debug_gated_and_populated(tmp_path_factory):
    """/v1/agent/debug: 404 without enable_debug; with it, the pprof-
    analog payload carries thread stacks, gc stats, and the device/pallas/
    coalescer/mirror state (ref command/agent/http.go:115-119)."""
    import json
    import urllib.error
    import urllib.request

    from nomad_tpu.agent import Agent, AgentConfig

    # Gated off by default
    cfg = AgentConfig.dev()
    cfg.data_dir = str(tmp_path_factory.mktemp("dbg-off"))
    cfg.http_port = 0
    cfg.scheduler_backend = "host"
    a = Agent(cfg)
    a.start()
    try:
        with pytest.raises(urllib.error.HTTPError) as exc:
            urllib.request.urlopen(a.http.addr + "/v1/agent/debug",
                                   timeout=10)
        assert exc.value.code == 404
    finally:
        a.shutdown()

    cfg2 = AgentConfig.dev()
    cfg2.data_dir = str(tmp_path_factory.mktemp("dbg-on"))
    cfg2.http_port = 0
    cfg2.scheduler_backend = "host"
    cfg2.enable_debug = True
    a2 = Agent(cfg2)
    a2.start()
    try:
        with urllib.request.urlopen(a2.http.addr + "/v1/agent/debug",
                                    timeout=10) as resp:
            out = json.loads(resp.read())
        assert "MainThread" in out["threads"]
        assert out["gc"]["counts"]
        assert "dispatches" in out["coalescer"]
        assert "paths" in out["coalescer"]
        assert out["mirror_cache"]["capacity"] > 0
        assert out["device"]["platform"] == "cpu"
    finally:
        a2.shutdown()
