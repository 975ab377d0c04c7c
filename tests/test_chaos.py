"""Chaos scenario compiler + crash-recovery hardening
(nomad_tpu/simcluster/chaos.py, the journal checksum/torn-tail path in
nomad_tpu/raft/node.py, faults.py flap windows, and the heartbeat
wheel's batched mass expiry)."""

import json
import os
import pickle
import time

import pytest

from nomad_tpu import faults, mock, slo, structs
from nomad_tpu.raft.node import RaftConfig, RaftNode
from nomad_tpu.raft_observe import fsm_state_digest
from nomad_tpu.rpc import ConnPool, RPCServer
from nomad_tpu.server import ServerConfig
from nomad_tpu.server.cluster import (
    ClusterServer,
    form_cluster,
    wait_for_leader,
)
from nomad_tpu.simcluster.chaos import (
    FAMILIES,
    ChaosSpec,
    ChaosSpecError,
    RackFillInjector,
)
from nomad_tpu.simcluster.scenario import SCENARIOS
from tests.cluster_util import relaxed_cluster_cfg, retry_write


def _wait(predicate, timeout=10.0, msg="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if predicate():
            return
        time.sleep(0.02)
    raise AssertionError(f"timed out waiting for {msg}")


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.get_registry().clear()
    yield
    faults.get_registry().clear()


# ---------------------------------------------------------------------------
# Journal torn-tail recovery (satellite: truncate-corrupt-tail restart)
# ---------------------------------------------------------------------------

class KVFSM:
    def __init__(self):
        self.data = {}

    def apply(self, index, msg_type, payload):
        self.data[payload["k"]] = payload["v"]

    def snapshot_bytes(self):
        return pickle.dumps(self.data)

    def restore_bytes(self, data):
        self.data = pickle.loads(data)


def _raft_node(tmp_path, node_id="a"):
    rpc = RPCServer()
    rpc.start()
    cfg = RaftConfig(
        node_id=node_id, peers={node_id: rpc.addr},
        data_dir=str(tmp_path), snapshot_threshold=10_000,
        bootstrap_expect=1,
    )
    fsm = KVFSM()
    return RaftNode(cfg, fsm, rpc, pool=ConnPool(timeout=2.0)), rpc, fsm


def _write_entries(tmp_path, n=12):
    node, rpc, fsm = _raft_node(tmp_path)
    node.start()
    try:
        _wait(lambda: node.is_leader, msg="leadership")
        for i in range(n):
            node.apply("kv", {"k": f"k{i}", "v": i}).result(5.0)
        applied = node.applied_index
    finally:
        node.shutdown()
        rpc.shutdown()
    return applied


def test_journal_torn_tail_truncated_and_replayed(tmp_path):
    """A crash mid-append leaves a half-written last line: restart must
    replay cleanly to the last whole checksummed entry, count the
    truncation (never crash), and rewrite the journal so the next
    restart is clean."""
    applied = _write_entries(tmp_path, n=12)
    log_path = os.path.join(str(tmp_path), "raft-log.jsonl")
    raw = open(log_path).read().rstrip("\n")
    lines = raw.split("\n")
    # 12 kv entries plus the leader's no-op (paper 5.4.2) on election.
    assert len(lines) == 13
    # Tear the tail: keep 12 whole lines, half of the 13th, no newline.
    torn = "\n".join(lines[:12]) + "\n" + lines[12][: len(lines[12]) // 2]
    with open(log_path, "w") as f:
        f.write(torn)

    node2, rpc2, fsm2 = _raft_node(tmp_path)
    try:
        assert node2.recovery["journal_truncated_tail"] == 1
        node2.start()
        _wait(lambda: node2.applied_index >= applied - 1, msg="replay")
        # The torn entry is gone; every whole entry replayed.
        assert fsm2.data == {f"k{i}": i for i in range(11)}
    finally:
        node2.shutdown()
        rpc2.shutdown()

    # The clean prefix was rewritten: a THIRD load sees no truncation.
    # (12 replayed entries plus the no-op node2 committed on winning
    # its own election.)
    node3, rpc3, fsm3 = _raft_node(tmp_path)
    try:
        assert node3.recovery["journal_truncated_tail"] == 0
        assert node3.recovery["log_entries_loaded"] == 13
    finally:
        node3.shutdown()
        rpc3.shutdown()


def test_journal_bitflip_truncates_from_corrupt_line(tmp_path):
    """A flipped byte inside an entry body fails the per-line crc32:
    replay stops at the last entry BEFORE the corruption, even though
    the line is whole and later lines parse."""
    _write_entries(tmp_path, n=10)
    log_path = os.path.join(str(tmp_path), "raft-log.jsonl")
    lines = open(log_path).read().rstrip("\n").split("\n")
    # Corrupt entry 7's body (a digit inside the JSON), keep the frame.
    body = lines[6]
    pos = len(body) - 2
    flipped = body[:pos] + ("0" if body[pos] != "0" else "1") + body[pos:][1:]
    lines[6] = flipped
    with open(log_path, "w") as f:
        f.write("\n".join(lines) + "\n")

    node2, rpc2, fsm2 = _raft_node(tmp_path)
    try:
        assert node2.recovery["journal_truncated_tail"] == 1
        assert node2.recovery["log_entries_loaded"] == 6
    finally:
        node2.shutdown()
        rpc2.shutdown()


def test_journal_accepts_legacy_unchecksummed_lines(tmp_path):
    """Pre-checksum journals (lines starting at ``{``) still load — the
    upgrade path replays old journals unchanged."""
    _write_entries(tmp_path, n=6)
    log_path = os.path.join(str(tmp_path), "raft-log.jsonl")
    lines = open(log_path).read().rstrip("\n").split("\n")
    legacy = [ln[9:] if not ln.startswith("{") else ln for ln in lines]
    with open(log_path, "w") as f:
        f.write("\n".join(legacy) + "\n")
    node2, rpc2, _ = _raft_node(tmp_path)
    try:
        assert node2.recovery["journal_truncated_tail"] == 0
        assert node2.recovery["log_entries_loaded"] == 7
    finally:
        node2.shutdown()
        rpc2.shutdown()


# ---------------------------------------------------------------------------
# Follower crash + rejoin via chunked InstallSnapshot (satellite: digest
# equality under live write load)
# ---------------------------------------------------------------------------

def _member(name, peers, data_root, bind_port=0):
    cfg = ServerConfig(scheduler_backend="host", num_schedulers=1,
                       node_name=name)
    ccfg = relaxed_cluster_cfg(
        node_id=name, peers=peers, bootstrap_expect=3,
        bind_port=bind_port,
        raft_data_dir=os.path.join(data_root, name),
        snapshot_threshold=12, trailing_logs=4,
        snapshot_chunk_bytes=2048, suspicion_threshold=1000,
    )
    return ClusterServer(cfg, ccfg)


@pytest.mark.slow
def test_follower_crash_rejoin_fsm_digest_equal(tmp_path):
    """A follower killed mid-load and restarted past the leader's
    snapshot threshold rejoins via chunked InstallSnapshot while writes
    keep landing; afterwards its fsm_state_digest equals the leader's."""
    peers = {}
    servers = [_member(f"server-{i}", peers, str(tmp_path))
               for i in range(3)]
    restarted = None
    try:
        for s in servers:
            s.start()
        leader = wait_for_leader(servers, timeout=30.0)
        nodes = [mock.node() for _ in range(12)]
        for n in nodes:
            retry_write(lambda n=n: leader.node_register(n))
        job = mock.job()
        job.task_groups[0].count = 4
        eval_id, _ = retry_write(lambda: leader.job_register(job))
        leader.wait_for_eval(eval_id, timeout=30.0)

        follower = next(s for s in servers if s is not leader)
        fname = follower.cluster.node_id
        fport = int(follower.rpc_addr.rsplit(":", 1)[1])
        commit_at_kill = leader.raft.commit_index
        follower.shutdown()

        # Write load during the outage: enough applies to push the
        # leader's compaction past the downed follower's log position.
        for round_ in range(3):
            for n in nodes:
                retry_write(lambda n=n: leader.node_register(n))
        _wait(lambda: leader.raft.snapshot_index > commit_at_kill,
              timeout=30.0, msg="leader compaction past the kill point")

        restarted = _member(fname, peers, str(tmp_path), bind_port=fport)
        restarted.start()
        # Keep writing WHILE the snapshot install races live appends.
        for n in nodes[:6]:
            retry_write(lambda n=n: leader.node_register(n))
        _wait(lambda: restarted.raft.applied_index
              >= leader.raft.applied_index, timeout=45.0,
              msg="follower catch-up")
        assert restarted.raft.snapshot_chunks_received >= 2, (
            "rejoin should ride the chunked InstallSnapshot path")

        # Digest equality at a matched applied index (the leader may
        # still tick; retry until a stable pair is observed).
        def digests_match():
            la = leader.raft.applied_index
            if restarted.raft.applied_index < la:
                return False
            d1 = fsm_state_digest(leader.state_store)
            d2 = fsm_state_digest(restarted.state_store)
            return d1 == d2 and leader.raft.applied_index == la
        _wait(digests_match, timeout=30.0, msg="fsm digest equality")
    finally:
        for s in servers:
            if s.cluster.node_id != (restarted.cluster.node_id
                                     if restarted else None):
                try:
                    s.shutdown()
                except Exception:
                    pass
        if restarted is not None:
            restarted.shutdown()


# ---------------------------------------------------------------------------
# Flap windows (satellite: scheduled armed/disarmed timelines)
# ---------------------------------------------------------------------------

def test_flap_windows_deterministic_layout():
    flap = {"period": 1.0, "duty": 0.4, "count": 3, "jitter": 0.1}
    a = faults.FaultRule("raft.append", mode="drop", flap=dict(flap), seed=9)
    b = faults.FaultRule("raft.append", mode="drop", flap=dict(flap), seed=9)
    c = faults.FaultRule("raft.append", mode="drop", flap=dict(flap), seed=10)
    assert a.windows == b.windows
    assert a.windows != c.windows
    assert len(a.windows) == 3
    for i, (start, end) in enumerate(a.windows):
        assert i * 1.0 <= start <= i * 1.0 + 0.1
        assert abs((end - start) - 0.4) < 1e-6


def test_flap_transitions_booked_from_timeline():
    """Transition books are timeline-derived: a sparse check cadence
    (no decide() landing inside a disarmed gap) still books the missed
    disarm+arm pair, and a snapshot read after the last window reports
    exactly 2*count transitions."""
    r = faults.FaultRule(
        "raft.append", mode="drop", probability=1.0,
        flap={"period": 0.04, "duty": 0.5, "count": 4}, seed=3)
    # Sleep past ALL windows without a single check, then observe once.
    time.sleep(0.04 * 4 + 0.05)
    assert r.decide("a->b") is False  # spent: past the last window
    assert r.transitions == 8
    assert r.to_dict()["transitions"] == 8


def test_flap_disarmed_checks_consume_no_draw():
    r = faults.FaultRule(
        "raft.append", mode="drop", probability=0.5,
        windows=[(10.0, 11.0)], seed=3)
    for _ in range(5):
        assert r.decide("a->b") is False
    # Disarmed checks consume nothing: neither the check counter nor
    # the seeded decision stream advanced.
    assert r.checked == 0
    state = r._rng.getstate()
    assert state == r._rng.getstate()


def test_flap_validation():
    with pytest.raises(ValueError):
        faults.FaultRule("raft.append", mode="drop",
                         flap={"period": 0.0, "count": 1})
    with pytest.raises(ValueError):
        faults.FaultRule("raft.append", mode="drop",
                         flap={"period": 1.0, "duty": 1.5, "count": 1})
    with pytest.raises(ValueError):
        faults.FaultRule("raft.append", mode="drop",
                         flap={"period": 1.0, "count": 0})
    with pytest.raises(ValueError):
        faults.FaultRule("raft.append", mode="drop",
                         windows=[(0, 1)], flap={"period": 1.0, "count": 1})


def test_registry_snapshot_carries_flap_books():
    faults.get_registry().load({"sites": {
        "raft.append": {"mode": "drop", "probability": 1.0,
                        "flap": {"period": 0.02, "duty": 0.5, "count": 2}},
    }})
    time.sleep(0.06)
    faults.fire("raft.append", target="a->b")
    snap = faults.get_registry().snapshot()
    rules = snap["sites"]["raft.append"]
    assert rules[0]["transitions"] == 4
    assert rules[0]["flap"] == {"period": 0.02, "duty": 0.5, "count": 2}
    assert len(rules[0]["windows"]) == 2


# ---------------------------------------------------------------------------
# Batched mass expiry (satellite: heartbeat cohort death without an
# eval storm)
# ---------------------------------------------------------------------------

def test_node_batch_expire_single_upsert_same_fanout(tmp_path):
    """node_batch_expire marks every node down and coalesces the
    re-placement evals into ONE eval_upsert, with per-node eval sets
    identical to the single-node path."""
    cfg = ServerConfig(scheduler_backend="host", num_schedulers=1)
    (srv,) = form_cluster(1, cfg, relaxed_cluster_cfg())
    try:
        wait_for_leader([srv])
        nodes = [mock.node() for _ in range(6)]
        for n in nodes:
            srv.node_register(n)
        job = mock.job()
        job.task_groups[0].count = 4
        eval_id, _ = srv.job_register(job)
        srv.wait_for_eval(eval_id, timeout=30.0)
        hosting = sorted({a.node_id for a in
                          srv.state_store.allocs_by_job(job.id)})
        assert len(hosting) >= 2
        victims = hosting[:2]

        reply = srv.node_batch_expire(victims)
        assert reply["nodes"] == 2
        # One eval per job with allocs on each dead node — the fan-out
        # the single path would produce, batched.
        assert len(reply["eval_ids"]) == 2
        assert "eval_create_index" in reply
        for nid in victims:
            node = srv.state_store.node_by_id(nid)
            assert node.status == structs.NODE_STATUS_DOWN
        evs = [srv.state_store.eval_by_id(e) for e in reply["eval_ids"]]
        assert all(e is not None and e.job_id == job.id for e in evs)
        # Idempotent on already-down nodes: no new status applies, and
        # the fan-out still builds (a retry must not lose evals).
        reply2 = srv.node_batch_expire(victims)
        assert reply2["nodes"] == 2
    finally:
        srv.shutdown()


# ---------------------------------------------------------------------------
# Chaos spec grammar: parse-time validation
# ---------------------------------------------------------------------------

def _minimal(**over):
    raw = {
        "name": "t",
        "nodes": {"count": 8},
        "phases": [{"at": 0.0, "workload": [
            {"kind": "steady", "jobs": 1, "tasks_per_job": 1, "over": 1.0},
        ]}],
    }
    raw.update(over)
    return raw


def test_chaos_spec_minimal_parses_and_compiles():
    spec = ChaosSpec.parse(_minimal()).compile()
    assert spec.n_nodes == 8
    assert spec.deterministic is True
    injs = spec.injectors(42)
    acts = [a for i in injs for a in i.actions()]
    assert [a.kind for a in acts] == ["register_job"]


def test_chaos_spec_phase_offsets_shift_workload_actions():
    raw = _minimal(phases=[{"at": 2.5, "workload": [
        {"kind": "steady", "jobs": 2, "tasks_per_job": 1, "over": 1.0},
    ]}])
    injs = ChaosSpec.parse(raw).compile().injectors(7)
    ats = sorted(a.at for i in injs for a in i.actions())
    assert ats[0] >= 2.5


def test_chaos_spec_rejects_bad_specs():
    cases = [
        # unknown top-level key
        _minimal(bogus=1),
        # racks must divide count
        _minimal(nodes={"count": 8, "racks": 3}),
        # unknown workload kind
        _minimal(phases=[{"at": 0, "workload": [{"kind": "nope"}]}]),
        # missing required workload param
        _minimal(phases=[{"at": 0, "workload": [
            {"kind": "steady", "jobs": 1}]}]),
        # two directives in one phase
        _minimal(phases=[{"at": 0, "barrier": True,
                          "expand_spares": True}]),
        # kill.follower in a single-member cell
        _minimal(phases=[{"at": 0, "kill": {"follower": 0}}]),
        # kill.rack without racks
        _minimal(phases=[{"at": 0, "kill": {"rack": 0}}]),
        # restart without a prior kill
        _minimal(cluster={"members": 3},
                 run={"durable_raft": True},
                 phases=[{"at": 0, "restart": {"follower": True}}]),
        # restart without durable raft
        _minimal(cluster={"members": 3},
                 phases=[{"at": 0, "kill": {"follower": 0}},
                         {"at": 1, "restart": {"follower": True}}]),
        # expand_spares without spares
        _minimal(phases=[{"at": 0, "expand_spares": True}]),
        # unknown assert flag
        _minimal(**{"assert": {"definitely_fine": True}}),
        # storm_transitions without a storm
        _minimal(**{"assert": {"storm_transitions": True}}),
        # role placeholders without a 3-member cell
        _minimal(storm={"sites": {"raft.append": {
            "mode": "drop", "match": "{leader}->x"}}}),
        # phases out of order
        _minimal(phases=[
            {"at": 2.0, "barrier": True},
            {"at": 1.0, "workload": [{"kind": "steady", "jobs": 1,
                                      "tasks_per_job": 1, "over": 1.0}]},
        ]),
        # bad objective name
        _minimal(objectives={"not_a_metric": 100.0}),
    ]
    for raw in cases:
        with pytest.raises((ChaosSpecError, ValueError)):
            ChaosSpec.parse(raw)


def test_rack_nodes_are_contiguous_domains():
    cspec = ChaosSpec.parse(_minimal(nodes={"count": 16, "racks": 4}))
    assert cspec.rack_size == 4
    assert cspec.rack_nodes(0) == [f"sim-{i:05d}" for i in range(4)]
    assert cspec.rack_nodes(3) == [f"sim-{i:05d}" for i in range(12, 16)]


def test_rack_fill_injector_full_node_bijection():
    inj = RackFillInjector(42, jobs=4, over=3.0)
    acts = inj.actions()
    assert len(acts) == 4
    assert acts[-1].at == pytest.approx(3.0)
    job = acts[0].payload["build"]()
    assert job.task_groups[0].count == 1
    assert job.task_groups[0].tasks[0].resources.cpu == 4000


def test_storm_horizon_paces_run_past_last_flap_window():
    # A scheduled storm must not outlive the run: the compiler emits a
    # no-op settle action past the last window's end so a fast workload
    # cannot quiesce while flap edges are still in the future (which
    # would honestly — and flakily — under-count storm transitions).
    raw = _minimal(storm={"sites": {
        "raft.append": {"mode": "drop", "probability": 1.0,
                        "flap": {"period": 1.2, "duty": 0.5, "count": 5,
                                 "jitter": 0.2}},
        "raft.vote": {"mode": "drop", "probability": 1.0},
    }})
    cspec = ChaosSpec.parse(raw)
    assert cspec.storm_horizon() == pytest.approx(6.0)
    settles = [a for i in cspec.compile().injectors(42)
               for a in i.actions() if a.kind == "settle"]
    assert len(settles) == 1
    assert settles[0].at > 6.0
    # Explicit window lists bound the horizon by their max end; pure
    # probability storms have no schedule, hence nothing to outlive.
    windowed = ChaosSpec.parse(_minimal(storm={"sites": {
        "raft.append": {"mode": "drop", "windows": [[0.5, 1.0],
                                                    [2.0, 3.5]]}}}))
    assert windowed.storm_horizon() == pytest.approx(3.5)
    unscheduled = ChaosSpec.parse(_minimal(storm={"sites": {
        "raft.append": {"mode": "drop", "probability": 0.1}}}))
    assert unscheduled.storm_horizon() is None
    assert not [a for i in unscheduled.compile().injectors(42)
                for a in i.actions() if a.kind == "settle"]


def test_shipped_families_registered():
    for raw in FAMILIES:
        name = raw["name"]
        assert name in SCENARIOS
        assert SCENARIOS[name].chaos_check is not None
        assert name in slo.SCENARIO_OBJECTIVES
        # slo.py declares the same bounds statically (so a process that
        # never imports the chaos compiler judges a chaos artifact
        # identically). register() merges the spec's bounds over
        # DEFAULT_OBJECTIVES; the two sources must agree key-for-key.
        assert slo.SCENARIO_OBJECTIVES[name] == {
            **slo.DEFAULT_OBJECTIVES, **raw.get("objectives", {})}
    assert SCENARIOS["partition-flap"].cluster_members == 3
    assert SCENARIOS["rack-failure"].cluster_members == 1
    assert SCENARIOS["follower-crash-rejoin"].durable_raft is True
    # The compiled kill schedule targets one whole rack, node-id exact.
    acts = [a for i in SCENARIOS["rack-failure"].injectors(42)
            for a in i.actions()]
    kills = [a for a in acts if a.kind == "fail_nodes"]
    assert len(kills) == 1
    assert kills[0].payload["node_ids"] == [
        f"sim-{i:05d}" for i in range(24, 32)]
