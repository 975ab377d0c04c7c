"""Raft & recovery observatory (nomad_tpu/raft_observe.py).

Covers the ISSUE-15 test satellites:

- stage-partition reconciliation: the write-path stages are a PARTITION
  of submit→applied by construction (same contract as lifecycle.py's
  waterfall) — unit-pinned on synthetic anchors and end-to-end against a
  live single-member raft node's own records;
- follower-lag math under a one-way partition (the PR 2 fault sites):
  the partitioned follower's match-index delta grows while the healthy
  follower keeps up, and healing converges the lag back to zero;
- e2e dev-cluster restart: a ClusterServer killed and rebuilt from its
  data dir reports entries_replayed > 0 and reproduces the pre-kill FSM
  state digest exactly;
- config validation, live-agent HTTP/SDK/Prometheus/bundle surfaces,
  and the observer-topic digest exclusion.
"""

import json
import time
import urllib.error
import urllib.request

import pytest

from nomad_tpu import faults, mock, structs
from nomad_tpu.raft.node import RaftConfig, RaftNode
from nomad_tpu.raft_observe import (
    ANCHORS,
    STAGES,
    RaftObserveConfig,
    RaftObservatory,
    fsm_state_digest,
    stage_partition,
)
from nomad_tpu.rpc import ConnPool, RPCServer
from nomad_tpu.server import ServerConfig
from nomad_tpu.server.cluster import (
    ClusterConfig,
    ClusterServer,
    form_cluster,
    wait_for_leader,
)


def _wait(predicate, timeout=15.0, msg="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if predicate():
            return
        time.sleep(0.02)
    raise AssertionError(f"timed out waiting for {msg}")


# ---------------------------------------------------------------------------
# config validation
# ---------------------------------------------------------------------------


def test_config_defaults_and_parse():
    cfg = RaftObserveConfig.parse(None)
    assert cfg.enabled and cfg.poll_interval == 1.0
    cfg = RaftObserveConfig.parse(
        {"enabled": False, "poll_interval": 0.5, "events_interval": 0})
    assert not cfg.enabled and cfg.events_interval == 0


@pytest.mark.parametrize("spec", [
    {"pol_interval": 1.0},           # typo'd key
    {"poll_interval": 0},            # nonsense cadence
    {"events_interval": -1},         # negative cadence
    "not-a-mapping",
])
def test_config_rejects_bad_specs(spec):
    with pytest.raises(ValueError):
        RaftObserveConfig.parse(spec)


def test_server_config_parses_raft_observe_block():
    cfg = ServerConfig(raft_observe={"poll_interval": 0.25})
    assert cfg.raft_observe_config.poll_interval == 0.25
    with pytest.raises(ValueError):
        ServerConfig(raft_observe={"bogus": 1})


# ---------------------------------------------------------------------------
# stage-partition reconciliation (the lifecycle.py contract)
# ---------------------------------------------------------------------------


def test_stage_partition_full_anchor_chain_reconciles():
    t0 = 100.0
    anchors = {a: t0 + i * 0.010 for i, a in enumerate(ANCHORS)}
    stages = stage_partition(anchors)
    assert set(stages) == set(STAGES)
    total = (anchors["resolved"] - anchors["submit"]) * 1000.0
    assert sum(stages.values()) == pytest.approx(total, abs=1e-9)
    for ms in stages.values():
        assert ms == pytest.approx(10.0, abs=1e-6)


def test_stage_partition_missing_anchors_collapse_to_zero():
    """A single-member cluster never stamps first_ack: the replicate
    stage must be exactly zero wide and the partition must still sum to
    the measured total."""
    anchors = {"submit": 1.0, "persisted": 1.002, "committed": 1.003,
               "fsm_start": 1.004, "fsm_end": 1.009, "resolved": 1.0095}
    stages = stage_partition(anchors)
    assert stages["replicate"] == 0.0
    total = (anchors["resolved"] - anchors["submit"]) * 1000.0
    assert sum(stages.values()) == pytest.approx(total, abs=1e-9)


def test_stage_partition_out_of_order_anchor_clamps():
    """An anchor stamped behind the running cursor (clock races across
    threads) clamps to zero width instead of going negative — the
    partition property survives."""
    anchors = {"submit": 5.0, "persisted": 5.010, "first_ack": 5.002,
               "committed": 5.012, "fsm_start": 5.013, "fsm_end": 5.014,
               "resolved": 5.015}
    stages = stage_partition(anchors)
    assert stages["replicate"] == 0.0
    assert all(ms >= 0 for ms in stages.values())
    total = (anchors["resolved"] - anchors["submit"]) * 1000.0
    assert sum(stages.values()) == pytest.approx(total, abs=1e-9)


class _KVFSM:
    def __init__(self):
        self.data = {}

    def apply(self, index, msg_type, payload):
        self.data[payload["k"]] = payload["v"]

    def snapshot_bytes(self):
        import pickle

        return pickle.dumps(self.data)

    def restore_bytes(self, data):
        import pickle

        self.data = pickle.loads(data)


def _make_node(node_id, peers, fsm, **kw):
    rpc = RPCServer()
    rpc.start()
    peers[node_id] = rpc.addr
    cfg = RaftConfig(node_id=node_id, peers=peers, bootstrap_expect=1,
                     **kw)
    return RaftNode(cfg, fsm, rpc, pool=ConnPool(timeout=2.0)), rpc


def test_write_path_records_reconcile_on_live_node():
    """End-to-end half of the reconciliation satellite: every finalized
    record's stage sums equal its own measured submit→applied, and the
    drained books land per msg_type in the observatory."""
    peers = {}
    node, rpc = _make_node("a", peers, _KVFSM())
    node.start()
    try:
        _wait(lambda: node.is_leader, msg="leadership")
        t0 = time.monotonic()
        for i in range(20):
            node.apply("kv", {"k": f"k{i}", "v": i}).result(5.0)
        wall_ms = (time.monotonic() - t0) * 1000.0
        seq, records = node.write_path_records(0)
        kv = [r for r in records if r["msg_type"] == "kv"]
        assert len(kv) == 20
        total = 0.0
        for rec in kv:
            stages = stage_partition(rec["anchors"])
            rec_total = (rec["anchors"]["resolved"]
                         - rec["anchors"]["submit"]) * 1000.0
            assert sum(stages.values()) == pytest.approx(
                rec_total, abs=1e-9)
            assert rec["bytes"] > 0
            total += rec_total
        # The per-entry totals must stay inside the measured loop wall
        # (they are sub-spans of it).
        assert total <= wall_ms + 1.0
        obs = RaftObservatory(lambda: node)
        obs.refresh()
        snap = obs.snapshot()
        assert snap["write_path"]["kv"]["count"] == 20
        assert snap["write_path"]["kv"]["bytes_per_entry"]["p50"] > 0
        assert snap["raft"]["commit_index"] == snap["raft"]["applied_index"]
        assert snap["log"]["appended_entries"] >= 20
    finally:
        node.shutdown()
        rpc.shutdown()


def test_write_path_ring_overflow_is_counted_not_silent():
    peers = {}
    node, rpc = _make_node("a", peers, _KVFSM())
    node.start()
    try:
        _wait(lambda: node.is_leader, msg="leadership")
        obs = RaftObservatory(lambda: node)
        obs.refresh()  # arms the cursor at the current sequence
        for i in range(1100):  # ring holds 1024
            node.apply("kv", {"k": "k", "v": i}).result(5.0)
        obs.refresh()
        assert obs.records_dropped > 0
        assert (obs.records_ingested + obs.records_dropped
                >= 1100)
    finally:
        node.shutdown()
        rpc.shutdown()


# ---------------------------------------------------------------------------
# follower lag under a one-way partition (PR 2 fault sites)
# ---------------------------------------------------------------------------


def test_follower_lag_under_one_way_partition():
    peers = {}
    fsm_a, fsm_b, fsm_c = _KVFSM(), _KVFSM(), _KVFSM()
    node_a, rpc_a = _make_node("a", peers, fsm_a)
    node_b, rpc_b = _make_node("b", peers, fsm_b)
    node_c, rpc_c = _make_node("c", peers, fsm_c)
    for n in (node_a, node_b, node_c):
        n.start()
    try:
        _wait(lambda: any(n.is_leader for n in (node_a, node_b, node_c)),
              timeout=30.0, msg="leadership")
        nodes = {"a": node_a, "b": node_b, "c": node_c}
        leader = next(n for n in nodes.values() if n.is_leader)
        lagger = "c" if leader.config.node_id != "c" else "b"
        obs = RaftObservatory(lambda: leader)
        # One-way partition of the leader's append stream to the lagger;
        # the lagger's own OUTBOUND votes drop too so its rising term
        # can't depose the leader mid-assertion (the PR 2 chaos tests'
        # one-way-edge posture). The other follower keeps the quorum.
        faults.get_registry().load({"seed": 7, "sites": {
            "raft.append": {
                "mode": "partition",
                "match": f"{leader.config.node_id}->{lagger}",
            },
            "raft.vote": {"mode": "partition", "match": f"{lagger}->"},
        }})
        for i in range(12):
            leader.apply("kv", {"k": f"k{i}", "v": i}).result(5.0)
        leader_applied = leader.applied_index
        obs.refresh()
        snap = obs.snapshot()
        peers_out = snap["replication"]["peers"]
        healthy = next(p for p in peers_out if p != lagger)
        assert peers_out[lagger]["lag_entries"] >= 12
        assert peers_out[healthy]["lag_entries"] == 0
        # The lagger's last ack predates the partition (or never came);
        # the healthy follower acked within the write burst.
        if peers_out[lagger]["last_ack_age_s"] is not None:
            assert (peers_out[lagger]["last_ack_age_s"]
                    > peers_out[healthy]["last_ack_age_s"])
        # Heal: replication resumes and the lagger catches up (leader-
        # agnostic — the lagger's inflated term may force a re-election
        # on first contact, which is raft working as designed).
        faults.get_registry().clear()
        _wait(lambda: nodes[lagger].applied_index >= leader_applied,
              timeout=20.0, msg="lag convergence")
    finally:
        faults.get_registry().clear()
        for n in (node_a, node_b, node_c):
            n.shutdown()
        for r in (rpc_a, rpc_b, rpc_c):
            r.shutdown()


# ---------------------------------------------------------------------------
# e2e dev-cluster restart: replay + state-digest survival
# ---------------------------------------------------------------------------


def test_cluster_restart_recovery_report_and_state_digest(tmp_path):
    """Kill a quiesced single-member ClusterServer, rebuild it from its
    data dir: the recovery report shows entries_replayed > 0 and the
    replayed FSM reproduces the pre-kill state digest exactly."""
    cfg = ServerConfig(scheduler_backend="host", num_schedulers=1)
    ccfg = ClusterConfig(raft_data_dir=str(tmp_path / "raft"))
    (srv,) = form_cluster(1, cfg, ccfg)
    job = mock.job()
    job.task_groups[0].count = 2
    try:
        wait_for_leader([srv])
        for _ in range(6):
            srv.node_register(mock.node())
        eval_id, _ = srv.job_register(job)
        srv.wait_for_eval(eval_id, timeout=15.0)
        applied = srv.raft.applied_index
        digest_before = fsm_state_digest(srv.state_store)
        # A warm start has nothing to recover; the report says so.
        assert srv.raft.recovery["cold_start"] is False
    finally:
        srv.shutdown()

    ccfg2 = ClusterConfig(raft_data_dir=str(tmp_path / "raft"))
    (srv2,) = form_cluster(1, cfg, ccfg2)
    try:
        wait_for_leader([srv2])
        _wait(lambda: srv2.raft.applied_index >= applied, msg="replay")
        obs = srv2.raft_observatory
        obs.refresh()
        recovery = obs.snapshot()["recovery"]
        assert recovery["cold_start"] is True
        assert recovery["entries_replayed"] > 0
        assert recovery["replayed_by_type"].get("node_register", 0) >= 6
        assert recovery["replay_wall_ms"] is not None
        assert recovery["time_to_leader_ms"] is not None
        _wait(lambda: srv2.raft.recovery["time_to_serving_ms"]
              is not None, msg="serving stamp")
        assert fsm_state_digest(srv2.state_store) == digest_before
        assert len(srv2.state_store.allocs_by_job(job.id)) == 2
    finally:
        srv2.shutdown()


# ---------------------------------------------------------------------------
# observer events are digest-excluded
# ---------------------------------------------------------------------------


def test_raft_snapshot_events_are_observer_topic():
    from nomad_tpu.events import OBSERVER_TOPICS, EventBroker
    from nomad_tpu.simcluster.scenario import canonical_events

    assert "Raft" in OBSERVER_TOPICS
    broker = EventBroker(register=False)
    broker.publish("Eval", "EvalUpdated", key="e1",
                   payload={"status": "pending"})
    base = canonical_events(broker.all_events())

    class _FakeRaft:
        applied_index = 3
        commit_index = 3

    obs = RaftObservatory(lambda: _FakeRaft(), events=broker)
    obs.refresh()
    obs.publish_event()
    obs.publish_event()
    assert obs.events_published == 2
    after = canonical_events(broker.all_events())
    assert after["digest"] == base["digest"]
    raft_events = [e for e in broker.all_events() if e.topic == "Raft"]
    assert len(raft_events) == 2
    assert raft_events[0].type == "RaftSnapshot"


# ---------------------------------------------------------------------------
# live-agent surfaces: HTTP + SDK + Prometheus + bundle
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def agent(tmp_path_factory):
    from nomad_tpu.agent import Agent, AgentConfig

    config = AgentConfig.dev()
    config.data_dir = str(tmp_path_factory.mktemp("raft-agent"))
    config.http_port = 0
    config.enable_debug = True
    config.raft_observe = {"poll_interval": 0.2, "events_interval": 0}
    a = Agent(config)
    a.start()
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


def test_raft_endpoint_e2e(agent):
    from nomad_tpu.api import ApiClient

    client = ApiClient(address=agent.http.addr)
    job = mock.job()
    job.task_groups[0].count = 1
    job.task_groups[0].tasks[0].driver = "mock_driver"
    job.task_groups[0].tasks[0].config = {"run_for": "20",
                                          "exit_code": "0"}
    job.task_groups[0].tasks[0].resources.networks = []
    eval_id, _ = client.jobs().register(job)
    deadline = time.monotonic() + 60
    while time.monotonic() < deadline:
        ev, _ = client.evaluations().info(eval_id)
        if ev.status == structs.EVAL_STATUS_COMPLETE:
            break
        time.sleep(0.1)
    else:
        pytest.fail("eval never completed")

    status, body = _get(agent, "/v1/agent/raft")
    assert status == 200
    snap = json.loads(body)
    # The dev agent runs the DevMode InProcRaft: attribution degrades
    # honestly — persistence/replication stages zero-wide, fsm_apply
    # carries the cost, the full stage set still partitions. (The
    # RaftNode face is covered by the raw-node tests above and the
    # restart-800 scenario.)
    assert "job_register" in snap["write_path"]
    books = snap["write_path"]["job_register"]
    assert books["count"] >= 1
    assert set(books["stages_ms"]) == set(STAGES)
    assert books["total_ms"]["max"] > 0
    assert snap["raft"]["applied_index"] >= 1
    assert snap["replication"]["commit_advance"]["entries_per_s"] >= 0

    # Prometheus face of the same endpoint + the main scrape.
    status, body = _get(agent, "/v1/agent/raft?format=prometheus")
    assert status == 200
    text = body.decode()
    assert "# TYPE nomad_raft_write_ms gauge" in text
    assert 'nomad_raft_write_ms{msg_type="job_register",quantile="p95"}' \
        in text
    status, body = _get(agent, "/v1/agent/metrics?format=prometheus")
    assert status == 200
    assert "nomad_raft_write_entries_total" in body.decode()

    # SDK accessor.
    from nomad_tpu.api import ApiClient as _C

    api = _C(address=agent.http.addr).agent()
    sdk = api.raft()
    assert sdk["raft"]["applied_index"] >= snap["raft"]["applied_index"]

    # Debug bundle carries the raft section.
    bundle = api.debug_bundle()
    assert "raft" in bundle
    assert bundle["raft"]["write_path"]

    # Metrics JSON body carries the compact summary.
    metrics = api.metrics()
    assert metrics["raft"]["applied_index"] >= 1


def test_raft_endpoint_disabled_404(tmp_path):
    from nomad_tpu.agent import Agent, AgentConfig

    config = AgentConfig.dev()
    config.data_dir = str(tmp_path / "agent")
    config.http_port = 0
    config.raft_observe = {"enabled": False}
    a = Agent(config)
    a.start()
    try:
        with pytest.raises(urllib.error.HTTPError) as err:
            urllib.request.urlopen(a.http.addr + "/v1/agent/raft",
                                   timeout=10)
        assert err.value.code == 404
    finally:
        a.shutdown()
