"""Express lane tests (nomad_tpu/server/express.py).

The contract under test, end to end:

- sub-millisecond-class in-line placement for express-eligible jobs
  (eval committed COMPLETE asynchronously, allocations via the plan
  pipeline under a leased capacity reservation);
- **capacity safety**: express placements never violate capacity the
  slow path believes in — slow-path plans respect active leases at
  verify time, and an express placement only becomes durable through
  verified plan commit (fuzz-pinned);
- **exactly-once**: every express task places exactly once across
  verify-time bounces (EXPRESS_BOUNCE), lease expiry mid-commit, and
  leader failover (the new leader's books rebuild from uncommitted-entry
  reconciliation);
- admission classifies express into its own lane, and a SHED batch door
  sheds express too (express is not a rate-limit bypass).
"""

import threading
import time

import numpy as np
import pytest

from nomad_tpu import mock, structs
from nomad_tpu.server.admission import (
    AdmissionConfig,
    AdmissionController,
    LANE_EXPRESS,
    lane_for_job,
)
from nomad_tpu.server.express import (
    EVAL_TRIGGER_EXPRESS,
    EVAL_TRIGGER_EXPRESS_RECONCILE,
    EXPRESS_BOUNCE,
    ExpressConfig,
    ReservationLedger,
    express_eligible,
)
from nomad_tpu.server.plan_apply import evaluate_plan
from nomad_tpu.server.server import Server, ServerConfig
from nomad_tpu.simcluster.workload import build_job
from nomad_tpu.structs import (
    Allocation,
    Plan,
    RejectError,
    Resources,
    generate_uuid,
)


def _vec(cpu, mem=0, disk=0, iops=0):
    return np.array([cpu, mem, disk, iops], dtype=np.int64)


def _express_job(jid: str, count: int = 1, cpu: int = 100,
                 memory_mb: int = 64) -> "structs.Job":
    return build_job(jid, structs.JOB_TYPE_BATCH, count, cpu=cpu,
                     memory_mb=memory_mb, express=True)


def _dev_server(express=True, workers=1, **express_kw):
    cfg_express = {"enabled": True, **express_kw} if express else None
    srv = Server(ServerConfig(
        scheduler_workers=workers, scheduler_backend="host",
        prewarm_shapes=False, express=cfg_express,
    ))
    srv.start()
    return srv


def _register_nodes(srv, n, cpu=4000, memory_mb=8192):
    for i in range(n):
        node = mock.node()
        node.id = f"node-{i:03d}"
        node.resources.cpu = cpu
        node.resources.memory_mb = memory_mb
        srv.node_register(node)


def _wait(cond, timeout=10.0, interval=0.01):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(interval)
    return False


# ---------------------------------------------------------------------------
# Config + ledger units
# ---------------------------------------------------------------------------


def test_express_config_parse_validates():
    assert ExpressConfig.parse(None).enabled is False
    cfg = ExpressConfig.parse({"enabled": True, "lease_ttl": 5,
                               "probes": 8, "choices": 4})
    assert cfg.enabled and cfg.lease_ttl == 5.0 and cfg.choices == 4
    with pytest.raises(ValueError, match="unknown express config key"):
        ExpressConfig.parse({"enabledd": True})
    with pytest.raises(ValueError, match="lease_ttl"):
        ExpressConfig.parse({"lease_ttl": 0})
    with pytest.raises(ValueError, match="choices must be <="):
        ExpressConfig.parse({"probes": 2, "choices": 3})
    with pytest.raises(ValueError, match="max_leases"):
        ExpressConfig.parse({"max_leases": 0})


def test_ledger_reserve_release_expire():
    ledger = ReservationLedger(max_leases=2)
    l1 = ledger.reserve("ev1", {"n1": _vec(100, 64)}, ttl=10.0, now=0.0)
    l2 = ledger.reserve("ev2", {"n1": _vec(50, 32), "n2": _vec(10, 8)},
                        ttl=0.5, now=0.0)
    assert l1 is not None and l2 is not None
    # Cap enforced.
    assert ledger.reserve("ev3", {"n3": _vec(1)}, ttl=1.0, now=0.0) is None
    assert ledger.stats()["rejected_full"] == 1
    # Aggregated node debit.
    assert list(ledger.node_debit("n1")) == [150, 96, 0, 0]
    # TTL expiry drops only the due lease.
    expired = ledger.expire_due(now=1.0)
    assert [l.id for l in expired] == [l2.id]
    assert list(ledger.node_debit("n1")) == [100, 64, 0, 0]
    assert ledger.node_debit("n2") is None
    # Release is idempotent.
    assert ledger.release(l1.id) is True
    assert ledger.release(l1.id) is False
    assert ledger.active() == 0
    assert ledger.stats()["released"] == 1
    assert ledger.stats()["expired"] == 1


def test_ledger_debit_map_excludes_own_lease():
    ledger = ReservationLedger()
    l1 = ledger.reserve("ev1", {"n1": _vec(100, 64)}, ttl=10.0)
    ledger.reserve("ev2", {"n1": _vec(50, 32)}, ttl=10.0)
    full = ledger.debit_map()
    assert list(full["n1"]) == [150, 96, 0, 0]
    excl = ledger.debit_map(exclude=(l1.id,))
    assert list(excl["n1"]) == [50, 32, 0, 0]
    # Excluding the only lease on a node drops the node entirely.
    only = ReservationLedger()
    lease = only.reserve("ev", {"nX": _vec(10)}, ttl=10.0)
    assert only.debit_map(exclude=(lease.id,)) == {}


# ---------------------------------------------------------------------------
# Eligibility + admission lanes
# ---------------------------------------------------------------------------


def test_express_eligibility_shapes():
    cfg = ExpressConfig(enabled=True, max_tasks=4)
    job = _express_job("e1", count=2)
    assert express_eligible(job, cfg)
    # Lane off.
    assert not express_eligible(job, ExpressConfig(enabled=False))
    # Flag off.
    plain = build_job("e2", structs.JOB_TYPE_BATCH, 2)
    assert not express_eligible(plain, cfg)
    # Wrong type.
    svc = build_job("e3", structs.JOB_TYPE_SERVICE, 2, express=True)
    svc.express = True
    assert not express_eligible(svc, cfg)
    # Too many tasks.
    big = _express_job("e4", count=5)
    assert not express_eligible(big, cfg)
    # Network asks need the sequential port index.
    net = _express_job("e5")
    net.task_groups[0].tasks[0].resources.networks = [
        structs.NetworkResource(device="eth0", mbits=10)
    ]
    assert not express_eligible(net, cfg)
    # distinct_hosts needs the proposed-alloc iterator.
    dh = _express_job("e6", count=2)
    dh.constraints.append(structs.Constraint(
        operand=structs.CONSTRAINT_DISTINCT_HOSTS))
    assert not express_eligible(dh, cfg)


def test_lane_for_job_and_shed_covers_express():
    express = _express_job("e1")
    assert lane_for_job(express) == LANE_EXPRESS
    assert lane_for_job(build_job("b", structs.JOB_TYPE_BATCH, 1)) == "batch"
    assert lane_for_job(
        build_job("s", structs.JOB_TYPE_SERVICE, 1)) == "service"

    # A hot burn rate sheds batch AND express; service keeps flowing.
    ctl = AdmissionController(
        AdmissionConfig(shed_start_burn=1.0, shed_full_burn=2.0),
        burn_rate=lambda: 50.0,
    )
    with pytest.raises(RejectError) as e:
        ctl.admit_job(express, client_id="c1")
    assert e.value.reason == structs.REJECT_SHED
    with pytest.raises(RejectError):
        ctl.admit_job(build_job("b", structs.JOB_TYPE_BATCH, 1), "c1")
    ctl.admit_job(build_job("s", structs.JOB_TYPE_SERVICE, 1), "c1")
    assert ctl.by_lane[LANE_EXPRESS]["reject"] == 1


def test_express_rate_lane_meters_independently():
    """An exhausted express lane must not burn the same client's batch
    lane tokens (and vice versa) — (client, lane) keys the bucket."""
    ctl = AdmissionController(AdmissionConfig(
        client_rate=0.001, client_burst=1))
    ctl.admit_job(_express_job("e1"), client_id="c1")
    with pytest.raises(RejectError) as e:
        ctl.admit_job(_express_job("e2"), client_id="c1")
    assert e.value.reason == structs.REJECT_RATE_LIMITED
    # Same client, batch lane: its own fresh bucket.
    ctl.admit_job(build_job("b1", structs.JOB_TYPE_BATCH, 1), "c1")


# ---------------------------------------------------------------------------
# End-to-end placement on a dev server
# ---------------------------------------------------------------------------


def test_express_end_to_end():
    srv = _dev_server()
    try:
        _register_nodes(srv, 10)
        job = _express_job("exp-e2e", count=3)
        t0 = time.perf_counter()
        eval_id, _ = srv.job_register(job)
        submit_ms = (time.perf_counter() - t0) * 1000.0
        # In-line answer: no broker/worker/plan-queue on the submit path
        # (generous bound — suite boxes are noisy; the lane has no
        # measured latency claim: no benchmark cell drives it).
        assert submit_ms < 250.0
        lane = srv.express_lane
        assert lane.placed == 1 and lane.tasks_placed == 3

        ev = None

        def committed():
            nonlocal ev
            ev = srv.state_store.eval_by_id(eval_id)
            return ev is not None and ev.terminal_status()

        assert _wait(committed, 10.0)
        assert ev.status == structs.EVAL_STATUS_COMPLETE
        assert ev.triggered_by == EVAL_TRIGGER_EXPRESS
        allocs = srv.state_store.allocs_by_job(job.id)
        assert len(allocs) == 3
        assert all(a.desired_status == structs.ALLOC_DESIRED_STATUS_RUN
                   for a in allocs)
        assert _wait(lambda: lane.committed == 1, 5.0)
        assert lane.bounces == 0
        assert lane.ledger.active() == 0  # lease released on commit
        # Exactly one ExpressPlaced event, payload carrying the in-line
        # latency (the digest + SLO contract).
        placed_events = [e for e in srv.fsm.events.all_events()
                         if e.topic == "Express"]
        assert [e.type for e in placed_events] == ["ExpressPlaced"]
        assert placed_events[0].key == eval_id
        assert placed_events[0].payload["tasks"] == 3
        assert placed_events[0].payload["placed_ms"] > 0
        # The SLO monitor samples express_placed from that event.
        srv.slo_monitor.poll()
        snap = srv.slo_monitor.snapshot()
        assert snap["samples"]["express_placed"]["count"] == 1
        names = {o["name"] for o in snap["objectives"]}
        assert "express_placed_p50_ms" in names
    finally:
        srv.shutdown()


def test_express_lane_off_is_inert():
    """Default-off: an express-flagged job takes the ordinary path and
    the pipeline runs lease-blind (decision invariance)."""
    srv = _dev_server(express=False)
    try:
        assert srv.plan_applier.ledger is None
        _register_nodes(srv, 4)
        job = _express_job("exp-off", count=2)
        eval_id, _ = srv.job_register(job)
        ev = srv.wait_for_eval(eval_id, timeout=15.0)
        assert ev.status == structs.EVAL_STATUS_COMPLETE
        assert ev.triggered_by == structs.EVAL_TRIGGER_JOB_REGISTER
        assert srv.express_lane.placed == 0
        assert len(srv.state_store.allocs_by_job(job.id)) == 2
        assert not [e for e in srv.fsm.events.all_events()
                    if e.topic == "Express"]
    finally:
        srv.shutdown()


def test_express_ineligible_falls_back():
    srv = _dev_server()
    try:
        _register_nodes(srv, 4)
        # Express flag on a SERVICE job: ineligible, slow path, no books.
        job = build_job("svc-exp", structs.JOB_TYPE_SERVICE, 2)
        job.express = True
        eval_id, _ = srv.job_register(job)
        ev = srv.wait_for_eval(eval_id, timeout=15.0)
        assert ev.status == structs.EVAL_STATUS_COMPLETE
        assert srv.express_lane.placed == 0
        # Registering the SAME express job id twice: the second is an
        # update of a live job -> typed fallback, slow path.
        job2 = _express_job("exp-dup")
        srv.job_register(job2)
        assert _wait(lambda: srv.state_store.job_by_id("exp-dup")
                     is not None, 5.0)
        srv.job_register(job2)
        assert srv.express_lane.fallbacks.get("job_exists") == 1
    finally:
        srv.shutdown()


# ---------------------------------------------------------------------------
# Reservation-aware verification
# ---------------------------------------------------------------------------


def _snap_with_node(cpu=1000, memory_mb=1000):
    from nomad_tpu.state import StateStore

    state = StateStore()
    node = mock.node()
    node.id = "n1"
    node.resources = Resources(cpu=cpu, memory_mb=memory_mb,
                               disk_mb=10000, iops=100)
    node.reserved = None
    node.status = structs.NODE_STATUS_READY
    state.upsert_node(1, node)
    return state.snapshot()


def _alloc_on(node_id, cpu, mem, job_id="j1", eval_id=""):
    return Allocation(
        id=generate_uuid(), eval_id=eval_id or generate_uuid(),
        name="t[0]", node_id=node_id, job_id=job_id,
        resources=Resources(cpu=cpu, memory_mb=mem),
        desired_status=structs.ALLOC_DESIRED_STATUS_RUN,
        client_status=structs.ALLOC_CLIENT_STATUS_PENDING,
    )


def test_reservation_aware_verify_blocks_slow_plan():
    """A slow-path plan cannot verify into capacity an active lease
    holds; with no reservations the identical plan commits."""
    snap = _snap_with_node(cpu=1000)
    plan = Plan(eval_id="ev-slow")
    plan.append_alloc(_alloc_on("n1", cpu=600, mem=100))

    clean = evaluate_plan(_snap_with_node(cpu=1000), plan)
    assert clean.refresh_index == 0 and clean.node_allocation

    reserved = evaluate_plan(snap, plan,
                             reservations={"n1": _vec(600, 100)})
    assert reserved.refresh_index > 0
    assert not reserved.node_allocation


def test_reservations_only_charge_touched_nodes():
    """A lease on an UNRELATED node must not drag it into (or bounce)
    a plan that asked nothing of it."""
    snap = _snap_with_node(cpu=1000)
    plan = Plan(eval_id="ev-slow")
    plan.append_alloc(_alloc_on("n1", cpu=600, mem=100))
    result = evaluate_plan(snap, plan,
                           reservations={"elsewhere": _vec(10**9)})
    assert result.refresh_index == 0
    assert result.node_allocation


def test_express_plan_exempts_own_lease():
    """The express plan verifying its own async commit must not count
    its own reservation against itself — but must still respect every
    OTHER lease."""
    from nomad_tpu.server.plan_pipeline import evaluate_plans

    ledger = ReservationLedger()
    mine = ledger.reserve("ev-exp", {"n1": _vec(600, 100)}, ttl=30.0)

    plan = Plan(eval_id="ev-exp", all_at_once=True,
                express_lease=mine.id)
    plan.append_alloc(_alloc_on("n1", cpu=600, mem=100, eval_id="ev-exp"))
    [result] = evaluate_plans(_snap_with_node(cpu=1000), [plan],
                              ledger=ledger)
    assert result.refresh_index == 0 and result.node_allocation

    # Another lease holding the remainder of the node: now it bounces.
    ledger.reserve("ev-other", {"n1": _vec(600, 100)}, ttl=30.0)
    plan2 = Plan(eval_id="ev-exp", all_at_once=True,
                 express_lease=mine.id)
    plan2.append_alloc(_alloc_on("n1", cpu=600, mem=100,
                                 eval_id="ev-exp"))
    [result2] = evaluate_plans(_snap_with_node(cpu=1000), [plan2],
                               ledger=ledger)
    assert result2.refresh_index > 0
    assert not result2.node_allocation


def test_fused_prefix_respects_reservations():
    """The fused K x nodes pass charges lease debits as base usage: two
    columnar plans that both fit lease-blind, where the lease leaves
    room for only the first."""
    from nomad_tpu.server.plan_pipeline import evaluate_plans
    from nomad_tpu.structs import AllocBatch

    def batch(eval_id, cpu):
        return AllocBatch(
            eval_id=eval_id, job=build_job(eval_id, "batch", 1),
            tg_name="web", resources=Resources(cpu=cpu, memory_mb=1),
            node_ids=["n1"], node_counts=[1], name_idx=[0],
            ids_seed=7,
        )

    def plans():
        p1 = Plan(eval_id="ev1", snapshot_index=1)
        p1.append_batch(batch("ev1", 300))
        p2 = Plan(eval_id="ev2", snapshot_index=1)
        p2.append_batch(batch("ev2", 300))
        return [p1, p2]

    # Lease-blind: both fused plans commit.
    results = evaluate_plans(_snap_with_node(cpu=1000), plans())
    assert [bool(r.alloc_batches) for r in results] == [True, True]

    # A 500-cpu lease: the first 300 still fits (500+300), the second
    # would need 1100 > 1000 and bounces.
    ledger = ReservationLedger()
    ledger.reserve("ev-exp", {"n1": _vec(500, 0)}, ttl=30.0)
    results = evaluate_plans(_snap_with_node(cpu=1000), plans(),
                             ledger=ledger)
    assert bool(results[0].alloc_batches) is True
    assert bool(results[1].alloc_batches) is False
    assert results[1].refresh_index > 0


# ---------------------------------------------------------------------------
# Failure modes: bounce, lease expiry mid-commit, failover
# ---------------------------------------------------------------------------


def test_bounce_on_taken_capacity_places_exactly_once():
    """Stall the committer, take the promised capacity out from under
    the lease (expired) through the ordinary raft path, then let the
    commit proceed: the all_at_once plan bounces atomically
    (EXPRESS_BOUNCE) and the SAME allocation (id stable) re-places on
    another node — exactly once."""
    srv = _dev_server(workers=0, lease_ttl=5.0)
    try:
        _register_nodes(srv, 3, cpu=1000, memory_mb=1000)
        lane = srv.express_lane
        lane.commit_gate.clear()
        job = _express_job("exp-bounce", cpu=600, memory_mb=100)
        eval_id, _ = srv.job_register(job)
        assert lane.placed == 1
        entry = lane._pending[0]
        [alloc] = entry.allocs
        original_id, chosen = alloc.id, alloc.node_id

        # The lease expires mid-commit...
        expired = lane.ledger.expire_due(now=time.monotonic() + 3600.0)
        assert [l.id for l in expired] == [entry.lease.id]
        # ...and the slow path takes the capacity the lease was holding
        # (a filler alloc straight through raft — deterministic).
        filler = _alloc_on(chosen, cpu=900, mem=800, job_id="filler")
        srv.raft.apply("alloc_update", {"allocs": [filler]}).result()

        lane.commit_gate.set()
        assert _wait(lambda: lane.committed == 1, 15.0)
        assert lane.bounces >= 1
        allocs = [a for a in srv.state_store.allocs_by_job(job.id)]
        assert len(allocs) == 1                      # exactly once
        assert allocs[0].id == original_id           # same task
        assert allocs[0].node_id != chosen           # re-placed
        outcomes = [o["outcome"] for o in lane._outcomes]
        assert EXPRESS_BOUNCE in outcomes
        # Final ledger state: nothing leaks.
        assert lane.ledger.active() == 0
    finally:
        srv.shutdown()


def test_bounce_exhaustion_reconciles_via_slow_path():
    """No capacity anywhere on re-place: the entry reconciles as a
    PENDING eval for the ordinary scheduler (typed, counted) — never
    silently dropped, never doubly placed."""
    srv = _dev_server(workers=1, max_bounces=1, lease_ttl=5.0)
    try:
        _register_nodes(srv, 2, cpu=1000, memory_mb=1000)
        lane = srv.express_lane
        lane.commit_gate.clear()
        job = _express_job("exp-rec", cpu=600, memory_mb=100)
        orig_eval, _ = srv.job_register(job)
        entry = lane._pending[0]
        lane.ledger.expire_due(now=time.monotonic() + 3600.0)
        # Fill EVERY node: re-place cannot fit anywhere.
        fillers = [_alloc_on(f"node-{i:03d}", cpu=950, mem=950,
                             job_id="filler") for i in range(2)]
        srv.raft.apply("alloc_update", {"allocs": fillers}).result()
        lane.commit_gate.set()
        assert _wait(lambda: lane.reconciled == 1, 15.0)
        # The reconcile eval is durable and pending (or already failed
        # terminal after delivery attempts — it rode the broker).
        evs = srv.state_store.evals_by_job(job.id)
        reconcile = next(e for e in evs if e.triggered_by
                         == EVAL_TRIGGER_EXPRESS_RECONCILE)
        # The ORIGINAL eval (handed to the submitter) reached a terminal
        # status, chained to its reconcile successor — monitors polling
        # it must not hang forever.
        original = srv.state_store.eval_by_id(orig_eval)
        assert original is not None and original.terminal_status()
        assert original.next_eval == reconcile.id
        # Nothing placed for the express job (capacity is full).
        live = [a for a in srv.state_store.allocs_by_job(job.id)
                if not a.terminal_status()]
        assert live == []
        # Bounced at least once, then found no fit on re-place and
        # reconciled (no_fit_on_bounce) rather than looping.
        assert entry.bounces >= 1
    finally:
        srv.shutdown()


def test_backlog_full_falls_back_without_deadlock():
    """A full committer backlog declines typed (and must not deadlock:
    the decision is made under the lane lock, the fallback accounting
    re-takes it)."""
    srv = _dev_server(workers=1, max_pending=1)
    try:
        _register_nodes(srv, 4)
        lane = srv.express_lane
        lane.commit_gate.clear()
        srv.job_register(_express_job("exp-q1"))
        assert lane.backlog() == 1
        # Backlog at cap: the next express submission falls back to the
        # slow path inline (bounded wait proves no deadlock).
        done = threading.Event()
        out = {}

        def second():
            out["ret"] = srv.job_register(_express_job("exp-q2"))
            done.set()

        t = threading.Thread(target=second, daemon=True)
        t.start()
        assert done.wait(10.0), "submit deadlocked on a full backlog"
        assert lane.fallbacks.get("backlog_full") == 1
        lane.commit_gate.set()
        assert _wait(lambda: lane.committed == 1, 10.0)
        # Both jobs end up placed exactly once (one express, one slow).
        for jid, want in (("exp-q1", 1), ("exp-q2", 1)):
            assert _wait(lambda j=jid, w=want: len(
                srv.state_store.allocs_by_job(j)) == w, 15.0)
    finally:
        srv.shutdown()


def test_duplicate_submission_in_commit_window_is_idempotent():
    """A same-job retry arriving BEFORE the first entry's async commit
    lands must not place a second copy (job_by_id can't see the
    duplicate yet): the in-flight guard answers with the ORIGINAL
    submission's eval id — the idempotent retry a client whose first
    register timed out expects."""
    srv = _dev_server(workers=1)
    try:
        _register_nodes(srv, 4)
        lane = srv.express_lane
        lane.commit_gate.clear()
        first_eval, _ = srv.job_register(_express_job("exp-dup2", count=2))
        assert lane.placed == 1
        # Retry while the first entry is still uncommitted: same eval
        # id back, no second placement, nothing sent to the slow path.
        retry_eval, _ = srv.job_register(_express_job("exp-dup2", count=2))
        assert retry_eval == first_eval
        assert lane.placed == 1 and lane.duplicates == 1
        lane.commit_gate.set()
        assert _wait(lambda: lane.committed == 1, 10.0)
        assert _wait(lambda: len(
            srv.state_store.allocs_by_job("exp-dup2")) == 2, 15.0)
        time.sleep(0.3)
        live = [a for a in srv.state_store.allocs_by_job("exp-dup2")
                if not a.terminal_status()]
        assert len(live) == 2  # exactly once, not 4
        # Post-commit, a re-register is a real update: slow path.
        srv.job_register(_express_job("exp-dup2", count=2))
        assert lane.fallbacks.get("job_exists") == 1
    finally:
        srv.shutdown()


def test_ineligible_same_job_retry_awaits_commit():
    """A same-id retry that is express-INELIGIBLE (flag dropped) can't
    ride the duplicate guard — the slow path must wait out the in-flight
    express commit so its scheduler sees the committed allocs and the
    reconciler no-ops instead of double-placing."""
    srv = _dev_server(workers=1)
    try:
        _register_nodes(srv, 4)
        lane = srv.express_lane
        srv.job_register(_express_job("exp-flip"))
        # Immediately re-register the same id with the flag DROPPED:
        # express declines it; the slow path must not race the commit.
        plain = build_job("exp-flip", structs.JOB_TYPE_BATCH, 1)
        ev2, _ = srv.job_register(plain)
        srv.wait_for_eval(ev2, timeout=15.0)
        assert _wait(lambda: lane.committed == 1, 10.0)
        time.sleep(0.3)
        live = [a for a in srv.state_store.allocs_by_job("exp-flip")
                if not a.terminal_status()]
        assert len(live) == 1  # exactly once, not 2
    finally:
        srv.shutdown()


def test_stop_drains_pending_entries_to_reconcile():
    """A clean shutdown with placed-but-uncommitted entries reconciles
    them into durable pending evals — the callers were already told
    'placed', and a rolling restart must not lose that work."""
    srv = _dev_server(workers=0)
    try:
        _register_nodes(srv, 4)
        lane = srv.express_lane
        lane.commit_gate.clear()
        for k in range(3):
            srv.job_register(_express_job(f"exp-stop-{k}"))
        assert lane.backlog() == 3
    finally:
        srv.shutdown()
    assert lane.reconciled == 3
    for k in range(3):
        evs = srv.state_store.evals_by_job(f"exp-stop-{k}")
        assert any(e.triggered_by == EVAL_TRIGGER_EXPRESS_RECONCILE
                   for e in evs)


def test_leases_of_distinct_submissions_stack():
    """Two stalled submissions must not be promised the same capacity:
    the second pick sees the first's lease debit."""
    srv = _dev_server(workers=0, probes=16)
    try:
        _register_nodes(srv, 2, cpu=1000, memory_mb=1000)
        lane = srv.express_lane
        lane.commit_gate.clear()
        srv.job_register(_express_job("exp-a", cpu=600, memory_mb=100))
        srv.job_register(_express_job("exp-b", cpu=600, memory_mb=100))
        assert lane.placed == 2
        nodes = [e.allocs[0].node_id for e in lane._pending]
        assert nodes[0] != nodes[1]  # 600+600 > 1000: must not stack
        assert lane.ledger.active() == 2
        lane.commit_gate.set()
        assert _wait(lambda: lane.committed == 2, 15.0)
        assert lane.bounces == 0
    finally:
        srv.shutdown()


# ---------------------------------------------------------------------------
# Capacity-safety + exactly-once fuzz family
# ---------------------------------------------------------------------------


def _node_usage(snap):
    """{node_id: int64[4]} summed LIVE alloc usage, objects + blocks."""
    usage = {}
    for node in snap.nodes():
        total = np.zeros(4, dtype=np.int64)
        for a in structs.filter_terminal_allocs(
                snap.allocs_by_node(node.id)):
            if a.resources is not None:
                total += np.asarray(a.resources.as_vector(),
                                    dtype=np.int64)
        usage[node.id] = total
    return usage


@pytest.mark.parametrize("seed", [11, 42, 1337])
def test_fuzz_capacity_safety_and_exactly_once(seed):
    """Seeded interleavings of express submissions and slow-path jobs on
    a small tight cell, with committer stalls and forced lease expiry
    injected: at quiesce, NO node exceeds its capacity (the invariant
    the leased-reservation verify protects) and every express task
    placed exactly once (or its entry reconciled into a pending eval —
    never both, never neither)."""
    from random import Random

    rng = Random(seed)
    srv = _dev_server(workers=2, lease_ttl=2.0, probes=32)
    try:
        n_nodes, cpu = 6, 2000
        _register_nodes(srv, n_nodes, cpu=cpu, memory_mb=4000)
        lane = srv.express_lane
        express_jobs = []
        slow_jobs = []
        # Offered-cpu budget: stay under ~65% of cluster capacity so
        # every task CAN place (exactly-once is only meaningful when
        # capacity exists; full-cell behavior is pinned by the dedicated
        # bounce/reconcile tests above). Fragmentation headroom rides
        # the margin.
        budget = int(n_nodes * cpu * 0.65)
        offered = 0
        for round_no in range(30):
            r = rng.random()
            if r < 0.55:
                count = rng.randrange(1, 3)
                job_cpu = rng.choice([100, 300, 500])
                jid = f"exp-{seed}-{round_no}"
                job = _express_job(jid, count=count, cpu=job_cpu,
                                   memory_mb=64)
                if offered + count * job_cpu > budget:
                    continue
                offered += count * job_cpu
                express_jobs.append(job)
                srv.job_register(job)
            elif r < 0.85:
                count = rng.randrange(1, 4)
                job_cpu = rng.choice([200, 400])
                jid = f"slow-{seed}-{round_no}"
                job = build_job(jid, structs.JOB_TYPE_BATCH, count,
                                cpu=job_cpu, memory_mb=64)
                if offered + count * job_cpu > budget:
                    continue
                offered += count * job_cpu
                slow_jobs.append(job)
                srv.job_register(job)
            elif r < 0.93:
                # Stall the committer briefly mid-stream.
                lane.commit_gate.clear()
                time.sleep(rng.random() * 0.05)
                lane.commit_gate.set()
            else:
                # Force every outstanding lease to expire mid-commit.
                lane.ledger.expire_due(now=time.monotonic() + 3600.0)
            if rng.random() < 0.3:
                time.sleep(0.01)
        lane.commit_gate.set()

        def quiesced():
            if lane.backlog() or lane.ledger.active():
                return False
            for ev in srv.state_store.evals():
                if not ev.terminal_status():
                    return False
            stats = srv.eval_broker.snapshot_stats()
            return (stats.total_ready + stats.total_unacked
                    + stats.total_blocked) == 0

        assert _wait(quiesced, 60.0), "fuzz run did not quiesce"

        snap = srv.state_store.snapshot()
        # Capacity safety: every node within its envelope.
        for node in snap.nodes():
            used = _node_usage(snap)[node.id]
            total = np.asarray(node.resources.as_vector(), dtype=np.int64)
            reserved = (np.asarray(node.reserved.as_vector(), np.int64)
                        if node.reserved is not None else 0)
            assert (used + reserved <= total).all(), (
                f"node {node.id} over capacity: {used}+{reserved} "
                f"> {total}"
            )
        # Exactly-once: every express task has exactly one live alloc,
        # OR its entry reconciled (pending/complete eval through the
        # slow path) — and reconciled jobs still end at exactly the
        # requested count once that eval completes.
        for job in express_jobs:
            want = sum(tg.count for tg in job.task_groups)
            live = [a for a in snap.allocs_by_job(job.id)
                    if not a.terminal_status()]
            assert len(live) == want, (
                f"express job {job.id}: {len(live)} live allocs, "
                f"want {want}"
            )
            assert len({a.id for a in live}) == want
    finally:
        srv.shutdown()


def test_same_seed_same_express_decisions():
    """The seeded streams (express.pick / express.lease_jitter) replay:
    two servers with the same seed and the same submission sequence
    place every express task on the same nodes with the same TTLs."""

    def run():
        srv = _dev_server(workers=0)
        try:
            _register_nodes(srv, 8)
            placements = []
            for k in range(10):
                srv.express_lane.commit_gate.clear()
                srv.job_register(_express_job(f"exp-{k}", count=2))
                entry = srv.express_lane._pending[-1]
                placements.append((
                    tuple(a.node_id for a in entry.allocs),
                    round(entry.lease.granted_ttl, 9),
                ))
            return placements
        finally:
            srv.shutdown()

    assert run() == run()


# ---------------------------------------------------------------------------
# HTTP + SDK surface
# ---------------------------------------------------------------------------


def test_agent_express_endpoint_and_metrics(tmp_path):
    """/v1/agent/express (SDK agent().express()), nomad_express_* prom
    lines, the metrics-JSON express block, and the debug bundle's
    express section — the operator surface over a live agent."""
    from nomad_tpu.agent import Agent, AgentConfig
    from nomad_tpu.api.client import ApiClient

    config = AgentConfig(
        server_enabled=True, dev_mode=True, node_name="exp-dev",
        enable_debug=True, express={"enabled": True},
    )
    config.data_dir = str(tmp_path)
    config.http_port = 0
    config.scheduler_backend = "host"
    agent = Agent(config)
    agent.start()
    try:
        for i in range(4):
            node = mock.node()
            node.id = f"http-node-{i}"
            agent.server.node_register(node)
        client = ApiClient(address=agent.http.addr)
        eval_id, _ = client.jobs().register(_express_job("exp-http"))
        assert _wait(lambda: agent.server.express_lane.committed == 1,
                     10.0)

        snap = client.agent().express()
        assert snap["enabled"] is True
        assert snap["placed"] == 1 and snap["committed"] == 1
        assert snap["place_ms"]["count"] == 1
        assert snap["ledger"]["granted"] == 1
        assert snap["recent_outcomes"][-1]["outcome"] == "EXPRESS_COMMITTED"
        assert snap["config"]["max_tasks"] == 16

        metrics = client.agent().metrics()
        assert metrics["express"]["placed"] == 1

        import urllib.request

        text = urllib.request.urlopen(
            agent.http.addr + "/v1/agent/metrics?format=prometheus"
        ).read().decode()
        assert "nomad_express_placed_total 1" in text
        assert "nomad_express_committed_total 1" in text
        assert "nomad_express_leases 0" in text

        bundle = client.agent().debug_bundle()
        assert bundle["express"]["placed"] == 1
        # The express eval's timeline resolves over HTTP with the
        # express stage classification (in-line pick/lease partition).
        tl = client.evaluations().timeline(eval_id)
        assert tl["triggered_by"] == "express"
        assert tl["submit_to_placed_ms"] is not None
        assert "express_pick" in tl["stage_ms"]
    finally:
        agent.shutdown()


# ---------------------------------------------------------------------------
# Leader failover with outstanding leases
# ---------------------------------------------------------------------------


def test_leader_failover_reconciles_outstanding_express():
    """Depose the leader (one-way outbound raft partition) while an
    express placement is still uncommitted: its lease is dropped on
    demotion (leader-local books), the committer forwards the entry to
    the NEW leader as a pending reconcile eval (Express.Reconcile), and
    the task places exactly once on the new leader's watch."""
    import sys

    sys.path.insert(0, "tests")
    from cluster_util import relaxed_cluster_cfg, retry_write

    from nomad_tpu import faults
    from nomad_tpu.server.cluster import form_cluster, wait_for_leader

    servers = form_cluster(3, ServerConfig(
        scheduler_backend="host", scheduler_workers=1,
        min_heartbeat_ttl=300.0, express={"enabled": True},
    ), base_cluster=relaxed_cluster_cfg())
    try:
        leader = wait_for_leader(servers)
        for i in range(4):
            node = mock.node()
            node.id = f"fo-node-{i}"
            retry_write(lambda n=node: leader.node_register(n))

        leader = wait_for_leader(servers)
        lane = leader.express_lane
        lane.commit_gate.clear()
        job = _express_job("exp-failover")
        eval_id, _ = retry_write(lambda: leader.job_register(job))
        # The submission may have been forwarded if leadership moved
        # under us; find the server whose lane holds it.
        holder = next((s for s in servers
                       if s.express_lane.backlog()), None)
        assert holder is not None
        assert holder.express_lane.ledger.active() == 1

        # One-way outbound partition of the holder: survivors elect.
        old_id = holder.cluster.node_id
        faults.get_registry().load({"seed": 7, "sites": {
            "raft.append": {"mode": "partition", "match": f"{old_id}->"},
            "raft.vote": {"mode": "partition", "match": f"{old_id}->"},
        }})
        survivors = [s for s in servers if s is not holder]
        deadline = time.monotonic() + 30.0
        new_leader = None
        while time.monotonic() < deadline:
            live = [s for s in survivors if s.raft.is_leader]
            if live:
                new_leader = live[0]
                break
            time.sleep(0.05)
        assert new_leader is not None, "no survivor took leadership"
        # Demotion drops the deposed leader's leases (its view is stale).
        assert _wait(lambda: not holder.raft.is_leader, 15.0)
        assert _wait(lambda: holder.express_lane.ledger.active() == 0,
                     10.0)

        # Release the committer: NotLeaderError -> Express.Reconcile
        # forward -> pending eval on the new leader -> placed there.
        holder.express_lane.commit_gate.set()

        def placed_once():
            live = [a for a in new_leader.state_store.allocs_by_job(
                        job.id)
                    if not a.terminal_status()]
            return len(live) == 1

        assert _wait(placed_once, 45.0), "express task not re-placed"
        evs = new_leader.state_store.evals_by_job(job.id)
        assert any(e.triggered_by == EVAL_TRIGGER_EXPRESS_RECONCILE
                   for e in evs)
        # Exactly once: still exactly one live alloc after settling.
        time.sleep(0.5)
        live = [a for a in new_leader.state_store.allocs_by_job(job.id)
                if not a.terminal_status()]
        assert len(live) == 1
    finally:
        faults.get_registry().clear()
        for srv in servers:
            srv.shutdown()
