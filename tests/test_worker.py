"""Worker unit tests (reference: nomad/worker_test.go): dequeue/ack/nack,
raft index sync barrier, scheduler invocation, the Planner interface
(submit with refresh, create/update eval), and leader pause."""

import time

import pytest

from nomad_tpu import mock, structs
from nomad_tpu.server import Server, ServerConfig
from nomad_tpu.server.worker import Worker
from nomad_tpu.structs import Evaluation, Plan, generate_uuid


@pytest.fixture
def srv():
    # No srv.start(): workers are driven by hand. Broker/plan queue are
    # enabled like on a leader.
    s = Server(ServerConfig(scheduler_backend="host", num_schedulers=0))
    s.plan_queue.set_enabled(True)
    s.eval_broker.set_enabled(True)
    s.plan_applier.start()
    yield s
    s.shutdown()


def _seed_job_eval(srv, count=1):
    node = mock.node()
    srv.raft.apply("node_register", {"node": node})
    job = mock.job()
    job.task_groups[0].count = count
    srv.raft.apply("job_register", {"job": job})
    ev = Evaluation(
        id=generate_uuid(),
        priority=job.priority,
        type=job.type,
        triggered_by=structs.EVAL_TRIGGER_JOB_REGISTER,
        job_id=job.id,
        status=structs.EVAL_STATUS_PENDING,
    )
    srv.raft.apply("eval_update", {"evals": [ev]})
    return node, job, ev


def test_worker_dequeue_invoke_ack(srv):
    """The full worker cycle by hand (worker_test.go dequeue + invoke):
    eval leaves the broker, the scheduler places, the ack clears the
    outstanding entry, and the eval completes."""
    node, job, ev = _seed_job_eval(srv, count=2)
    w = Worker(srv, worker_id=99)

    got = w._dequeue_evaluation()
    assert got is not None
    dq, token, wait_index = got
    assert dq.id == ev.id

    w._wait_for_index(dq.modify_index, 2.0)
    assert w._invoke_scheduler(dq, token) is True
    w._send_ack(dq.id, token, ack=True)

    assert len(srv.state_store.allocs_by_job(job.id)) == 2
    done = srv.state_store.eval_by_id(ev.id)
    assert done.status == structs.EVAL_STATUS_COMPLETE
    assert srv.eval_broker.stats.total_unacked == 0


def test_worker_nack_redelivers(srv):
    """A nacked eval is redelivered (eval_broker.go nack timer path is the
    async variant; explicit nack requeues immediately)."""
    _node, _job, ev = _seed_job_eval(srv)
    w = Worker(srv, worker_id=98)

    dq, token, _wi = w._dequeue_evaluation()
    w._send_ack(dq.id, token, ack=False)

    dq2, token2, _wi2 = w._dequeue_evaluation()
    assert dq2.id == ev.id
    assert token2 != token or token2 == token  # redelivered with a token
    w._send_ack(dq2.id, token2, ack=True)


def test_wait_for_index(srv):
    w = Worker(srv, worker_id=97)
    current = srv.raft.applied_index
    w._wait_for_index(current, 0.5)  # immediate
    with pytest.raises(TimeoutError):
        w._wait_for_index(current + 50, 0.2)


def test_submit_plan_stamps_token_and_refreshes(srv):
    """SubmitPlan stamps the outstanding EvalToken; a plan against a
    vanished node comes back with RefreshIndex and a fresh snapshot
    (worker.go:265-328)."""
    node, job, ev = _seed_job_eval(srv)
    w = Worker(srv, worker_id=96)
    dq, token, _wi = w._dequeue_evaluation()
    w.eval_token = token

    alloc = mock.alloc()
    alloc.job = job
    alloc.job_id = job.id
    alloc.eval_id = dq.id
    alloc.node_id = "no-such-node"
    plan = Plan(eval_id=dq.id, priority=50)
    plan.append_alloc(alloc)

    result, new_state = w.submit_plan(plan)
    assert plan.eval_token == token
    assert result.refresh_index > 0
    assert new_state is not None  # forced refresh
    assert not result.node_allocation
    w._send_ack(dq.id, token, ack=True)


def test_submit_plan_rejects_wrong_token(srv):
    """A plan whose token doesn't match the outstanding entry is refused —
    the split-brain guard (plan_apply.go:52-58)."""
    _node, _job, ev = _seed_job_eval(srv)
    w = Worker(srv, worker_id=95)
    dq, token, _wi = w._dequeue_evaluation()
    w.eval_token = "bogus-token"

    plan = Plan(eval_id=dq.id, priority=50)
    alloc = mock.alloc()
    plan.append_alloc(alloc)
    with pytest.raises(Exception):
        w.submit_plan(plan)
    w._send_ack(dq.id, token, ack=True)


def test_create_and_update_eval_replicate(srv):
    w = Worker(srv, worker_id=94)
    ev = Evaluation(
        id=generate_uuid(), priority=70, type="service",
        triggered_by=structs.EVAL_TRIGGER_ROLLING_UPDATE,
        job_id="some-job", status=structs.EVAL_STATUS_PENDING,
        wait=10.0,
    )
    w.create_eval(ev)
    stored = srv.state_store.eval_by_id(ev.id)
    assert stored is not None and stored.wait == 10.0

    ev.status = structs.EVAL_STATUS_COMPLETE
    w.update_eval(ev)
    assert srv.state_store.eval_by_id(ev.id).status == structs.EVAL_STATUS_COMPLETE


def _seed_n_jobs(srv, n, count=1):
    node = mock.node()
    node.resources.cpu = 500_000
    node.resources.memory_mb = 500_000
    srv.raft.apply("node_register", {"node": node})
    jobs, evals = [], []
    for _ in range(n):
        job = mock.job()
        job.task_groups[0].count = count
        # cpu/mem-bound: the mock NIC ask would cap the single test node
        # at ~20 total placements across all jobs
        job.task_groups[0].tasks[0].resources.networks = []
        srv.raft.apply("job_register", {"job": job})
        ev = Evaluation(
            id=generate_uuid(), priority=job.priority, type=job.type,
            triggered_by=structs.EVAL_TRIGGER_JOB_REGISTER, job_id=job.id,
            status=structs.EVAL_STATUS_PENDING,
        )
        jobs.append(job)
        evals.append(ev)
    srv.raft.apply("eval_update", {"evals": evals})
    return jobs, evals


def test_worker_batch_dequeue_drains_ready_evals(srv):
    """K queued evals for distinct jobs drain in ONE broker batch
    (eval_broker.py dequeue_batch wired through the server seam)."""
    jobs, evals = _seed_n_jobs(srv, 4)
    w = Worker(srv, worker_id=92)
    batch = w._dequeue_batch(4)
    assert len(batch) == 4
    assert {ev.id for ev, _, _ in batch} == {ev.id for ev in evals}
    # Each eval carries its own outstanding token
    assert len({token for _, token, _ in batch}) == 4
    for ev, token, _wi in batch:
        w._send_ack(ev.id, token, ack=True)


def test_batched_worker_processes_all_with_coalesced_dispatches():
    """End-to-end through the REAL server loop: K queued evals for K jobs,
    one batched worker, TPU backend. All jobs fully placed through the
    plan queue, the broker drain happened as one batch, and the concurrent
    device solves took no more dispatches than evals (they stack in the
    coalescing engine; fewer when timing allows)."""
    from nomad_tpu.ops.coalesce import GLOBAL_SOLVER

    s = Server(ServerConfig(
        scheduler_backend="tpu", num_schedulers=0, eval_batch_size=4,
    ))
    s.plan_queue.set_enabled(True)
    s.eval_broker.set_enabled(True)
    s.plan_applier.start()
    # This test assembles the server by hand instead of Server.start(),
    # so it claims the device the way start() would.
    from nomad_tpu.scheduler import acquire_device

    assert acquire_device()["platform"] == "cpu"
    try:
        # count > exact threshold so the water-fill/coalescer path runs
        jobs, evals = _seed_n_jobs(s, 4, count=200)
        dispatches_before = GLOBAL_SOLVER.dispatches
        w = Worker(s, worker_id=91)
        w.start()
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            done = [
                s.state_store.eval_by_id(ev.id) for ev in evals
            ]
            if all(
                d is not None and d.status == structs.EVAL_STATUS_COMPLETE
                for d in done
            ):
                break
            time.sleep(0.05)
        for job in jobs:
            allocs = [
                a for a in s.state_store.allocs_by_job(job.id)
                if a.desired_status == structs.ALLOC_DESIRED_STATUS_RUN
            ]
            assert len(allocs) == 200, (job.id, len(allocs))
        assert w.last_batch_size == 4  # one broker drain carried all four
        solves = GLOBAL_SOLVER.dispatches - dispatches_before
        assert 1 <= solves <= 4
        w.stop()
    finally:
        s.shutdown()


def test_worker_pause_blocks_processing(srv):
    """The leader pauses one worker (worker.go:77-93, leader.go:100-104):
    a paused worker must not dequeue."""
    w = Worker(srv, worker_id=93)
    w.set_pause(True)
    w.start()
    try:
        _node, job, ev = _seed_job_eval(srv)
        time.sleep(0.4)
        # Still queued: the paused worker never dequeued it
        assert srv.eval_broker.stats.total_ready == 1
        w.set_pause(False)
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            done = srv.state_store.eval_by_id(ev.id)
            if done is not None and done.status == structs.EVAL_STATUS_COMPLETE:
                break
            time.sleep(0.05)
        assert srv.state_store.eval_by_id(ev.id).status == structs.EVAL_STATUS_COMPLETE
    finally:
        w.stop()


def test_submit_plan_refresh_covers_own_commit(srv):
    """The post-plan refresh wait must cover max(refresh_index,
    alloc_index): waiting on refresh_index alone lets a worker on a
    lagging follower re-snapshot WITHOUT the allocs its own plan just
    committed — and re-place them (the chaos test's dominant
    duplicate-placement mode)."""
    _node, _job, ev = _seed_job_eval(srv)
    w = Worker(srv, worker_id=94)
    dq, token, _wi = w._dequeue_evaluation()

    waited = []
    w._wait_for_index = lambda idx, t: waited.append(idx)

    from nomad_tpu.server.worker import _EvalRun
    from nomad_tpu.structs import PlanResult

    run = _EvalRun(w, token)

    class FakeServer:
        @staticmethod
        def plan_submit(plan):
            # Partial plan: rejection forced a refresh at index 3, but
            # the accepted slice committed later, at index 9.
            return PlanResult(refresh_index=3, alloc_index=9)

        state_store = srv.state_store
        raft = srv.raft  # refresh re-stamps the transaction timestamp

    class FakeWorker:
        server = FakeServer
        _wait_for_index = staticmethod(w._wait_for_index)

    run.worker = FakeWorker()
    result, new_state = run.submit_plan(
        __import__("nomad_tpu.structs", fromlist=["Plan"]).Plan(
            eval_id=dq.id, priority=50)
    )
    assert waited == [9], waited  # max(3, 9), not 3
    assert new_state is not None
    w._send_ack(dq.id, token, ack=True)
