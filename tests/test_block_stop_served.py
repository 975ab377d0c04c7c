"""The served path of a whole-block stop, on the CPU: register jobs large
enough to place as blocks (more than TPUGenericScheduler's
BATCH_PLACE_THRESHOLD = 256 tasks; the benchmark's rehearsal churn stops
jobs of 60, which place as object rows and hold the fall-through),
deregister one, and read what the server wrote: the raft entry, the event
stream, the pipeline's counters, the store.
"""

import time

import pytest

from nomad_tpu import mock, structs
from nomad_tpu.server import ServerConfig
from nomad_tpu.server.cluster import form_cluster, wait_for_leader
from nomad_tpu.structs import Resources

from cluster_util import relaxed_cluster_cfg, retry_write

TASKS = 300
N_JOBS = 3


def _job():
    job = mock.job()
    job.type = structs.JOB_TYPE_BATCH
    tg = job.task_groups[0]
    tg.count = TASKS
    tg.tasks[0].resources = Resources(cpu=20, memory_mb=16)
    return job


def _wait_eval(srv, eval_id, timeout=120.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        ev = srv.state_store.eval_by_id(eval_id)
        if ev is not None and ev.terminal_status():
            return ev
        time.sleep(0.02)
    raise TimeoutError(f"evaluation {eval_id} did not end")


def _delta(after, before):
    return {k: after[k] - before[k] for k in after
            if k != "max_batch_seen" and after[k] != before[k]}


@pytest.fixture(scope="module")
def served():
    """One server on the solver backend, three block jobs placed, the
    first deregistered and a fourth placed after it; what was read at
    each step."""
    from benchmark.generators.watcher import event_placed

    servers = form_cluster(1, ServerConfig(
        scheduler_backend="tpu", num_schedulers=2,
        min_heartbeat_ttl=300.0,
    ), base_cluster=relaxed_cluster_cfg())
    try:
        srv = wait_for_leader(servers)
        for _ in range(16):
            node = mock.node()
            retry_write(lambda n=node: srv.node_register(n))
        jobs = [_job() for _ in range(N_JOBS)]
        for job in jobs:
            eval_id, _ = srv.job_register(job)
            assert _wait_eval(srv, eval_id).status == "complete"
        out = {"srv": srv, "jobs": jobs}
        out["blocks_placed"] = len(srv.state_store.alloc_blocks())
        before = srv.plan_applier.stats()
        log_len = len(srv.raft.log)
        stop_eval, _ = srv.job_deregister(jobs[0].id)
        out["stop_eval"] = _wait_eval(srv, stop_eval)
        out["stop_delta"] = _delta(srv.plan_applier.stats(), before)
        out["stop_entries"] = [
            e for e in srv.raft.log[log_len:] if e.msg_type == "alloc_update"]
        before = srv.plan_applier.stats()
        later = _job()
        eval_id, _ = srv.job_register(later)
        assert _wait_eval(srv, eval_id).status == "complete"
        out["later"] = later
        out["later_delta"] = _delta(srv.plan_applier.stats(), before)
        _latest, events, truncated = srv.fsm.events.events_after(0)
        assert not truncated
        out["events"] = events
        out["placed_by_events"] = sum(event_placed(e) for e in events)
        yield out
    finally:
        for s in servers:
            s.shutdown()


def test_jobs_over_the_threshold_place_as_blocks(served):
    from nomad_tpu.tpu.solver import TPUGenericScheduler

    assert TASKS > TPUGenericScheduler.BATCH_PLACE_THRESHOLD
    assert served["blocks_placed"] >= N_JOBS


def test_stop_is_acknowledged_after_its_entry_applied(served):
    srv, job = served["srv"], served["jobs"][0]
    assert served["stop_eval"].status == structs.EVAL_STATUS_COMPLETE
    rows = srv.state_store.allocs_by_job(job.id)
    assert len(rows) == TASKS
    assert {a.desired_status for a in rows} == {
        structs.ALLOC_DESIRED_STATUS_STOP}
    assert not structs.filter_terminal_allocs(rows)
    for other in served["jobs"][1:] + [served["later"]]:
        live = structs.filter_terminal_allocs(
            srv.state_store.allocs_by_job(other.id))
        assert len(live) == TASKS


def test_stop_entry_is_under_a_kilobyte(served):
    (entry,) = served["stop_entries"]
    assert 0 < entry.wire_bytes < 1024
    assert "stop_batches" in entry.payload and not entry.payload["allocs"]


def test_one_stop_event_a_block_and_none_counted_as_placed(served):
    stops = [e for e in served["events"] if e.type == "AllocStopped"]
    blocks = served["srv"].state_store.stopped_alloc_blocks()
    assert len(stops) == len(blocks) >= 1
    assert {e.key for e in stops} == {served["stop_eval"].id}
    assert sum(e.payload["count"] for e in stops) == TASKS
    assert {e.payload["block_id"] for e in stops} == {
        b.block_id for b in blocks}
    # The placements asked, no more: three jobs before the stop, one after.
    assert served["placed_by_events"] == (N_JOBS + 1) * TASKS
    # And no row-by-row stop was published.
    assert not [e for e in served["events"] if e.type == "AllocUpserted"
                and e.payload.get("desired_status") == "stop"]


def test_pipeline_counts_the_stop_as_whole_blocks(served):
    d = served["stop_delta"]
    assert d["stop_batch_members"] == TASKS
    assert "stop_batch_fallback_members" not in d
    assert d["stop_plans"] == 1 and d["committed"] == 1
    assert "scalar_plans" not in d and "fused_plans" not in d


def test_placing_plans_after_the_stop_meet_no_object_row(served):
    srv = served["srv"]
    assert srv.state_store.nodes_with_object_allocs() == set()
    d = served["later_delta"]
    assert d["committed"] >= 1
    assert "scalar_object_rows" not in d
