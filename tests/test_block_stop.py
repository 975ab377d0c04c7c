"""A stop of a whole stored block, held equal to the same stop row by row.

The block form (structs.AllocStopBatch -> state/store.py
``_apply_stop_batches``) moves the block from the live table to the
stopped one and touches no member; the object form is what the reference
does and what this repo did before: ``Plan.append_update`` a copy of every
member with ``desired_status = stop`` and ``upsert_allocs`` the lot. Two
stores are given the same blocks (same id seeds), one is stopped each way,
and every read that lists allocations, every usage reader, the snapshot,
a client's update, the core GC and a replica fed the same log have to
agree. (The served path is tests/test_block_stop_served.py.)
"""

import json
import logging
import pickle

import numpy as np
import pytest

from nomad_tpu import mock, structs
from nomad_tpu.raft.log_codec import decode_payload, encode_payload
from nomad_tpu.scheduler import new_scheduler
from nomad_tpu.scheduler.generic import ALLOC_NOT_NEEDED
from nomad_tpu.server.fsm import FSM
from nomad_tpu.server.plan_apply import evaluate_plan, stops_only
from nomad_tpu.server.plan_pipeline import (
    _PipelineTotals,
    _plan_touched_nodes,
    evaluate_plans,
)
from nomad_tpu.state import StateStore
from nomad_tpu.structs import (
    AllocBatch,
    AllocStopBatch,
    Evaluation,
    Plan,
    Resources,
    generate_uuid,
)

STOP = structs.ALLOC_DESIRED_STATUS_STOP
N_NODES = 6


# -- two stores holding the same blocks ----------------------------------------


def _job(name="stop-job"):
    job = mock.job()
    job.id = job.name = name
    job.type = structs.JOB_TYPE_BATCH
    return job


def _batch(job, node_ids, counts, seed, eval_id="ev-place", tg="web"):
    return AllocBatch(
        eval_id=eval_id, job=job, tg_name=tg,
        resources=Resources(cpu=20, memory_mb=32),
        task_resources={"web": Resources(cpu=20, memory_mb=32)},
        metrics=None, node_ids=list(node_ids), node_counts=list(counts),
        name_idx=np.arange(sum(counts)), ids_seed=seed,
    )


def _store(job, other, with_job=True):
    """A cell of N_NODES nodes holding two blocks of ``job`` (one placing
    evaluation, two task groups) and one block of ``other``."""
    store = StateStore()
    nodes = []
    for i in range(N_NODES):
        node = mock.node()
        node.id = f"bs-node-{i}"
        node.reserved.networks = []   # or plan verification goes scalar
        nodes.append(node)
    store.upsert_nodes(1, nodes)
    ids = [n.id for n in nodes]
    if with_job:
        store.upsert_alloc_blocks(10, [
            _batch(job, ids[:3], [4, 3, 2], seed=101),
            _batch(job, ids[2:5], [1, 5, 2], seed=102, tg="db"),
        ])
    store.upsert_alloc_blocks(
        11, [_batch(other, ids[1:4], [2, 2, 2], seed=103, eval_id="ev-other")])
    return store


def _stop_batches(state, job_id, eval_id="ev-stop"):
    return [
        AllocStopBatch(
            eval_id=eval_id, job_id=job_id, block_id=blk.block_id,
            n_live=blk.n_live, n_total=blk.n, ids_seed=blk.ids_seed,
            desired_status=STOP, desired_description=ALLOC_NOT_NEEDED,
            node_ids=blk.node_ids)
        for blk in state.job_alloc_blocks(job_id)
    ]


def _stop_rows(state, job_id):
    """The object form: what compute_job_allocs + Plan.append_update
    produce for a job that is gone."""
    plan = Plan()
    for a in structs.filter_terminal_allocs(state.allocs_by_job(job_id)):
        plan.append_update(a, STOP, ALLOC_NOT_NEEDED)
    return [a for rows in plan.node_update.values() for a in rows]


def _pair(index=20):
    """(block-stopped store, row-stopped store, job, other job)."""
    job, other = _job(), _job("other-job")
    blk_store, obj_store = _store(job, other), _store(job, other)
    blk_store.apply_stop_batches(index, _stop_batches(blk_store, job.id))
    obj_store.upsert_allocs(index, _stop_rows(obj_store, job.id))
    return blk_store, obj_store, job, other


def _row(a):
    return (a.id, a.node_id, a.job_id, a.eval_id, a.name, a.task_group,
            a.desired_status, a.desired_description, a.client_status,
            a.create_index, a.modify_index,
            tuple(a.resources.as_vector()))


def _rows(allocs):
    return sorted(_row(a) for a in allocs)


def _node_ids(store):
    return [n.id for n in store.nodes()]


def _all_ids(store):
    return sorted(a.id for a in store.allocs())


READS = {
    "allocs_by_job": lambda s, job, other: [
        _rows(s.allocs_by_job(j)) for j in (job.id, other.id, "nobody")],
    "allocs_by_node": lambda s, job, other: [
        _rows(s.allocs_by_node(n)) for n in _node_ids(s)],
    "alloc_by_id": lambda s, job, other: [
        _row(s.alloc_by_id(i)) for i in _all_ids(s)],
    "allocs_by_eval": lambda s, job, other: [
        _rows(s.allocs_by_eval(e))
        for e in ("ev-place", "ev-other", "ev-stop")],
    "allocs": lambda s, job, other: _rows(s.allocs()),
    "alloc_count": lambda s, job, other: s.alloc_count(),
    "has_allocs_for_job": lambda s, job, other: [
        s.has_allocs_for_job(j) for j in (job.id, other.id, "nobody")],
    "job_has_object_allocs": lambda s, job, other: [
        s.job_has_object_allocs(j) for j in (job.id, other.id)],
    "live_by_job": lambda s, job, other: [
        _rows(structs.filter_terminal_allocs(s.allocs_by_job(j)))
        for j in (job.id, other.id)],
    "indexes": lambda s, job, other: s.get_index("allocs"),
}


@pytest.mark.parametrize("read", sorted(READS))
@pytest.mark.parametrize("view", ["store", "snapshot"])
def test_reads_equal_row_form(read, view):
    blk_store, obj_store, job, other = _pair()
    if view == "snapshot":
        blk_store, obj_store = blk_store.snapshot(), obj_store.snapshot()
    assert READS[read](blk_store, job, other) == READS[read](
        obj_store, job, other)


def test_stopped_rows_carry_the_stop():
    blk_store, _obj, job, other = _pair(index=20)
    rows = blk_store.allocs_by_job(job.id)
    assert len(rows) == 17
    assert {a.desired_status for a in rows} == {STOP}
    assert {a.desired_description for a in rows} == {ALLOC_NOT_NEEDED}
    assert {a.modify_index for a in rows} == {20}
    assert {a.create_index for a in rows} == {10}
    assert all(a.terminal_status() for a in rows)
    assert {a.desired_status for a in blk_store.allocs_by_job(other.id)} == {
        structs.ALLOC_DESIRED_STATUS_RUN}


def test_block_stop_leaves_no_object_row_and_no_live_block():
    blk_store, obj_store, job, other = _pair()
    assert blk_store.nodes_with_object_allocs() == set()
    assert obj_store.nodes_with_object_allocs() != set()
    assert blk_store.allocs_objects() == []
    assert [b.job_id for b in blk_store.alloc_blocks()] == [other.id]
    assert blk_store.job_alloc_blocks(job.id) == []
    stopped = blk_store.stopped_alloc_blocks()
    assert sorted(b.tg_name for b in stopped) == ["db", "web"]
    assert all(b.desired_status == STOP for b in stopped)


# -- usage readers --------------------------------------------------------------


def _mirror_base(store):
    from nomad_tpu.scheduler.util import ready_nodes_in_dcs
    from nomad_tpu.tpu.mirror import NodeMirror

    snap = store.snapshot()
    mirror = NodeMirror(ready_nodes_in_dcs(snap, ["dc1"]))
    used, bw = mirror._base_usage_for(snap)
    return {nid: (tuple(int(x) for x in used[i]), int(bw[i]))
            for nid, i in mirror.index.items()}


def _verify_usage(store):
    from nomad_tpu.server.plan_apply import (
        _existing_block_usage_rows,
        _node_table,
    )

    snap = store.snapshot()
    table = _node_table(snap)
    usage, _net, _blocks = _existing_block_usage_rows(snap, table)
    out = {}
    for nid, row in table.rows.items():
        vec = np.zeros(4, dtype=np.int64)
        if usage is not None:
            vec = vec + usage[row]
        for a in snap.allocs_by_node_objects(nid):
            if not a.terminal_status():
                vec = vec + np.asarray(a.resources.as_vector())
        out[nid] = tuple(int(x) for x in vec)
    return out


@pytest.mark.parametrize("reader", [_mirror_base, _verify_usage],
                         ids=["mirror_usage_base", "plan_verify_usage"])
def test_usage_equal_row_form(reader):
    blk_store, obj_store, job, other = _pair()
    got = reader(blk_store)
    assert got == reader(obj_store)
    # And it is what a cell that never held the job reads.
    assert got == reader(_store(job, other, with_job=False))
    assert got != reader(_store(job, other))


def test_mirror_advances_over_a_block_stop_without_a_rebuild():
    """The mirror's base taken before the stop, advanced through the
    log's ``removed`` entry, is what a fresh walk gives after it."""
    from nomad_tpu.scheduler.util import ready_nodes_in_dcs
    from nomad_tpu.tpu.mirror import GLOBAL_MIRROR_CACHE, NodeMirror

    job, other = _job(), _job("other-job")
    store = _store(job, other)
    snap0 = store.snapshot()
    mirror = NodeMirror(ready_nodes_in_dcs(snap0, ["dc1"]))
    mirror._base_usage_for(snap0)
    gone = store.job_alloc_blocks(job.id)
    store.apply_stop_batches(20, _stop_batches(store, job.id))
    snap1 = store.snapshot()
    rows, added, removed = snap1.alloc_changes_since(11)
    assert rows == [] and added == []
    assert sorted(id(b) for b in removed) == sorted(id(b) for b in gone)
    assert snap1.alloc_node_changes_since(11) == {
        f"bs-node-{i}" for i in range(5)}
    before = GLOBAL_MIRROR_CACHE.stats()
    used, _bw = mirror._base_usage_for(snap1)
    after = GLOBAL_MIRROR_CACHE.stats()
    assert after["usage_rolls"] - before["usage_rolls"] == 1
    assert after["usage_rebuilds"] == before["usage_rebuilds"]
    fresh = NodeMirror(ready_nodes_in_dcs(snap1, ["dc1"]))
    np.testing.assert_array_equal(
        used[: mirror.n], fresh._compute_base_usage(snap1)[0][: fresh.n])


# -- the fallback: a block that changed between plan and apply -----------------


def _promote(store, blk, positions):
    members = []
    for pos in positions:
        m = blk.materialize_pos(pos)
        m.client_status = structs.ALLOC_CLIENT_STATUS_RUNNING
        members.append(m)
    store.update_allocs_from_client(15, members)


@pytest.mark.parametrize("promoted", [[1], [0, 1, 2, 3, 4]],
                         ids=["member_promoted", "block_dissolved"])
def test_changed_block_stops_exactly_the_members_named(promoted):
    job, other = _job(), _job("other-job")
    blk_store, obj_store = _store(job, other), _store(job, other)
    # The plan is made against the store as it stands ...
    batches = _stop_batches(blk_store, job.id)
    # ... then clients report on members of the first block, in both
    # worlds: the block is no longer what the batch says.
    for store in (blk_store, obj_store):
        web = [b for b in store.job_alloc_blocks(job.id)
               if b.tg_name == "web"][0]
        _promote(store, web, promoted)
    dissolved = len(promoted) > 1
    assert len(blk_store.job_alloc_blocks(job.id)) == (1 if dissolved else 2)
    outcomes = blk_store.apply_stop_batches(20, batches)
    obj_store.upsert_allocs(20, _stop_rows(obj_store, job.id))
    by_tg = {b.block_id: o for b, o in zip(batches, outcomes)}
    web_id = [b.block_id for b in batches if b.n_total == 9][0]
    # The untouched block went whole; the changed one member by member:
    # all nine of its members, the promoted ones included.
    assert [o is None for o in outcomes].count(True) == 1
    assert sorted(a.id for a in by_tg[web_id]) == sorted(
        AllocStopBatch(ids_seed=101, n_total=9).member_ids())
    for read in sorted(READS):
        assert READS[read](blk_store, job, other) == READS[read](
            obj_store, job, other), read
    assert _mirror_base(blk_store) == _mirror_base(obj_store)
    # The other job's block was never addressed.
    assert [b.n_live for b in blk_store.alloc_blocks()] == [6]


def test_fallback_passes_members_already_stopped_or_gone():
    job, other = _job(), _job("other-job")
    store = _store(job, other)
    batches = _stop_batches(store, job.id)
    web = [b for b in store.job_alloc_blocks(job.id) if b.tg_name == "web"][0]
    gone, stopped = web.materialize_pos(0), web.materialize_pos(1)
    stopped.desired_status = structs.ALLOC_DESIRED_STATUS_EVICT
    stopped.desired_description = "evicted meanwhile"
    store.upsert_allocs(15, [stopped])
    store.delete_eval(16, [], [gone.id])
    outcomes = store.apply_stop_batches(20, batches)
    rows = [o for o in outcomes if o is not None][0]
    assert len(rows) == 7 and gone.id not in {a.id for a in rows}
    assert store.alloc_by_id(gone.id) is None
    kept = store.alloc_by_id(stopped.id)
    assert (kept.desired_status, kept.desired_description,
            kept.modify_index) == (structs.ALLOC_DESIRED_STATUS_EVICT,
                                   "evicted meanwhile", 15)
    assert not structs.filter_terminal_allocs(store.allocs_by_job(job.id))


def test_stop_batch_for_another_jobs_block_id_is_not_taken_whole():
    job, other = _job(), _job("other-job")
    store = _store(job, other)
    batch = _stop_batches(store, other.id)[0]
    batch.job_id = job.id
    (rows,) = store.apply_stop_batches(20, [batch])
    # It names the block's ids, so those stop, as rows; but the block is
    # not moved on the word of a batch that names another job.
    assert rows is not None and len(rows) == 6
    assert store.stopped_alloc_blocks() == []


# -- addressing a member of a stopped block -----------------------------------


def test_client_update_to_a_member_of_a_stopped_block():
    blk_store, obj_store, job, other = _pair()
    member_id = AllocStopBatch(ids_seed=102, n_total=8).member_ids()[3]
    for store in (blk_store, obj_store):
        upd = store.alloc_by_id(member_id).copy()
        upd.client_status = structs.ALLOC_CLIENT_STATUS_DEAD
        upd.client_description = "task stopped"
        store.update_allocs_from_client(30, [upd])
    got = blk_store.alloc_by_id(member_id)
    assert (got.client_status, got.desired_status, got.modify_index) == (
        structs.ALLOC_CLIENT_STATUS_DEAD, STOP, 30)
    assert blk_store.alloc_object_by_id(member_id) is got
    for read in sorted(READS):
        if read in ("job_has_object_allocs",):
            continue
        assert READS[read](blk_store, job, other) == READS[read](
            obj_store, job, other), read
    # A terminal row: the gate for block-level reconciles stays shut on
    # neither side, and no usage moved.
    assert not blk_store.job_has_object_allocs(job.id)
    assert _mirror_base(blk_store) == _mirror_base(obj_store)
    db = [b for b in blk_store.stopped_alloc_blocks() if b.tg_name == "db"][0]
    assert db.excluded == frozenset({3}) and db.n_live == 7


def test_stopped_block_dissolves_once_half_promoted():
    blk_store, obj_store, job, other = _pair()
    ids = AllocStopBatch(ids_seed=102, n_total=8).member_ids()
    for store in (blk_store, obj_store):
        upds = []
        for i in ids[:4]:
            upd = store.alloc_by_id(i).copy()
            upd.client_status = structs.ALLOC_CLIENT_STATUS_DEAD
            upds.append(upd)
        store.update_allocs_from_client(30, upds)
    assert [b.tg_name for b in blk_store.stopped_alloc_blocks()] == ["web"]
    assert READS["allocs"](blk_store, job, other) == READS["allocs"](
        obj_store, job, other)


def test_upsert_superseding_a_member_of_a_stopped_block():
    blk_store, obj_store, job, other = _pair()
    member_id = AllocStopBatch(ids_seed=101, n_total=9).member_ids()[0]
    for store in (blk_store, obj_store):
        row = store.alloc_by_id(member_id).copy()
        row.desired_description = "said again"
        row.create_index = 0
        store.upsert_allocs(31, [row])
    assert READS["allocs"](blk_store, job, other) == READS["allocs"](
        obj_store, job, other)
    got = blk_store.alloc_by_id(member_id)
    assert (got.create_index, got.modify_index) == (10, 31)


def test_delete_of_a_member_of_a_stopped_block():
    blk_store, obj_store, job, other = _pair()
    member_id = AllocStopBatch(ids_seed=101, n_total=9).member_ids()[8]
    for store in (blk_store, obj_store):
        store.delete_eval(32, [], [member_id])
    assert blk_store.alloc_by_id(member_id) is None
    assert READS["allocs"](blk_store, job, other) == READS["allocs"](
        obj_store, job, other)


# -- the core GC ----------------------------------------------------------------


class _GcServer:
    """What CoreScheduler._eval_gc touches of a server."""

    class _Now:
        @staticmethod
        def nearest_index(_when):
            return 10 ** 9

    class _Raft:
        def __init__(self, store):
            self.store = store
            self.entries = []

        def apply(self, msg_type, payload):
            from concurrent.futures import Future

            assert msg_type == "eval_delete"
            self.entries.append(payload)
            self.store.delete_eval(99, payload["evals"], payload["allocs"])
            f = Future()
            f.set_result(99)
            return f

    def __init__(self, store):
        from nomad_tpu.server import ServerConfig

        self.config = ServerConfig()
        self.time_table = self._Now()
        self.raft = self._Raft(store)
        self.logger = logging.getLogger("test.gc")


@pytest.mark.parametrize("form", ["block", "rows"])
def test_core_gc_reaps_a_stopped_job(form):
    from nomad_tpu.server.core_sched import CoreScheduler

    blk_store, obj_store, job, other = _pair()
    store = blk_store if form == "block" else obj_store
    for eval_id, jid in (("ev-place", job.id), ("ev-other", other.id)):
        store.upsert_evals(40, [Evaluation(
            id=eval_id, job_id=jid, type=structs.JOB_TYPE_BATCH,
            status=structs.EVAL_STATUS_COMPLETE)])
    server = _GcServer(store)
    CoreScheduler(server, store.snapshot())._eval_gc(None)
    # The stopped job's evaluation and allocations are gone, the running
    # job's stay.
    assert store.eval_by_id("ev-place") is None
    assert store.eval_by_id("ev-other") is not None
    assert store.allocs_by_job(job.id) == []
    assert not store.has_allocs_for_job(job.id)
    assert store.allocs_by_eval("ev-place") == []
    assert len(store.allocs_by_job(other.id)) == 6
    (entry,) = server.raft.entries
    assert entry["evals"] == ["ev-place"]
    if form == "block":
        # A block goes with its evaluation: no member is named.
        assert entry["allocs"] == []
        assert store.stopped_alloc_blocks() == []
    else:
        assert len(entry["allocs"]) == 17


def test_core_gc_leaves_an_evaluation_with_a_live_block():
    from nomad_tpu.server.core_sched import CoreScheduler

    job, other = _job(), _job("other-job")
    store = _store(job, other)
    # One of the evaluation's two blocks stopped, the other still runs.
    store.apply_stop_batches(20, _stop_batches(store, job.id)[:1])
    store.upsert_evals(40, [Evaluation(
        id="ev-place", job_id=job.id, type=structs.JOB_TYPE_BATCH,
        status=structs.EVAL_STATUS_COMPLETE)])
    server = _GcServer(store)
    CoreScheduler(server, store.snapshot())._eval_gc(None)
    assert server.raft.entries == []
    assert len(store.allocs_by_job(job.id)) == 17


# -- the wire, the snapshot, a replica ------------------------------------------


def test_stop_entry_is_a_few_hundred_bytes_whatever_the_block():
    job = _job()
    small = _batch(job, ["n0"], [2], seed=7)
    big = _batch(job, [f"n{i}" for i in range(5000)], [20] * 5000, seed=8)
    sizes = []
    for batch in (small, big):
        store = StateStore()
        store.upsert_alloc_blocks(5, [batch])
        payload = {"allocs": [], "stop_batches": _stop_batches(store, job.id),
                   "plan": {"eval_id": "ev-stop", "stop_batches": 1}}
        sizes.append(len(json.dumps(encode_payload("alloc_update", payload))))
    assert sizes[1] < 1024
    assert abs(sizes[1] - sizes[0]) < 16


def test_stop_batch_wire_roundtrip():
    store = _store(_job(), _job("other-job"))
    (batch, _b2) = _stop_batches(store, "stop-job")
    wire = json.loads(json.dumps(batch.to_wire()))
    back = AllocStopBatch.from_wire(wire)
    for field in ("eval_id", "job_id", "block_id", "n_live", "n_total",
                  "ids_seed", "desired_status", "desired_description"):
        assert getattr(back, field) == getattr(batch, field), field
    # The footprint stays on the leader.
    assert back.node_ids == [] and "node_ids" not in wire
    blk = store.job_alloc_blocks("stop-job")[0]
    assert back.member_ids() == [blk.alloc_id(i) for i in range(blk.n)]


def _fsm_with_blocks(job, other):
    fsm = FSM()
    fsm.state = _store(job, other)
    return fsm


def _digest(store):
    return (_rows(store.allocs()),
            sorted((b.block_id, b.n_live) for b in store.alloc_blocks()),
            sorted((b.block_id, b.n_live, b.desired_status, b.modify_index)
                   for b in store.stopped_alloc_blocks()),
            store.get_index("allocs"))


@pytest.mark.parametrize("changed", [False, True],
                         ids=["whole", "with_fallback"])
def test_two_fsms_fed_one_log_end_equal(changed):
    """The leader applies the payload it built; a follower (and a
    restart) applies what the log's codec gives back. Same tables."""
    job, other = _job(), _job("other-job")
    leader, follower = (_fsm_with_blocks(job, other) for _ in range(2))
    batches = _stop_batches(leader.state, job.id)
    if changed:
        for fsm in (leader, follower):
            web = [b for b in fsm.state.job_alloc_blocks(job.id)
                   if b.tg_name == "web"][0]
            _promote(fsm.state, web, [2])
    payload = {"allocs": [], "stop_batches": batches,
               "plan": {"eval_id": "ev-stop", "allocs": 0,
                        "alloc_batches": 0, "update_batches": 0,
                        "stop_batches": len(batches)}}
    wire = json.loads(json.dumps(encode_payload("alloc_update", payload)))
    leader.apply(20, "alloc_update", payload)
    follower.apply(20, "alloc_update", decode_payload("alloc_update", wire))
    assert _digest(leader.state) == _digest(follower.state)
    for fsm in (leader, follower):
        assert fsm.stop_batch_members == (8 if changed else 17)
        assert fsm.stop_batch_fallback_members == (9 if changed else 0)
        _latest, events, _trunc = fsm.events.events_after(0)
        kinds = [(e.topic, e.type, e.key) for e in events]
        stops = [e for e in events if e.type == "AllocStopped"]
        assert len(stops) == (1 if changed else 2)
        assert {e.key for e in stops} == {"ev-stop"}
        assert sum(e.payload["count"] for e in stops) == (
            8 if changed else 17)
        assert all(e.payload["job_id"] == job.id
                   and e.payload["desired_status"] == STOP
                   and "columnar" not in e.payload for e in stops)
        # One PlanApplied, after the stop's own events.
        assert kinds[-1] == ("Plan", "PlanApplied", "ev-stop")
        rows = [e for e in events if e.type == "AllocUpserted"]
        assert len(rows) == (9 if changed else 0)
        assert all(e.payload["desired_status"] == STOP for e in rows)


def test_no_stop_event_reads_as_a_placement():
    from benchmark.generators.watcher import event_placed

    job, other = _job(), _job("other-job")
    fsm = _fsm_with_blocks(job, other)
    fsm.apply(20, "alloc_update", {
        "allocs": [], "stop_batches": _stop_batches(fsm.state, job.id)})
    _latest, events, _trunc = fsm.events.events_after(0)
    assert events and sum(event_placed(e) for e in events) == 0


@pytest.mark.parametrize("via", ["restore_bytes", "pickle"])
def test_snapshot_restore_keeps_every_read(via):
    blk_store, obj_store, job, other = _pair()
    if via == "restore_bytes":
        src = FSM()
        src.state = blk_store
        data = src.snapshot_bytes()
        dst = FSM()
        dst.restore_bytes(data)
        back = dst.state
        assert dst.last_restore["blocks"] == 3
    else:
        # A stopped block pickles its columns and the stop, not its rows.
        stopped = blk_store.stopped_alloc_blocks()
        for b in stopped:
            b.materialize()
        back = StateStore()
        restore = back.restore()
        for node in blk_store.nodes():
            restore.node_restore(node)
        for b in pickle.loads(pickle.dumps(
                blk_store.alloc_blocks() + stopped)):
            assert b._materialized is None
            restore.block_restore(b)
        restore.commit()
    assert [b.job_id for b in back.alloc_blocks()] == [other.id]
    assert len(back.stopped_alloc_blocks()) == 2
    for read in sorted(READS):
        assert READS[read](back, job, other) == READS[read](
            obj_store, job, other), read
    assert _mirror_base(back) == _mirror_base(obj_store)


def test_stored_block_wire_carries_the_stop():
    from nomad_tpu.state.blocks import StoredAllocBlock

    blk_store, _obj, _job_, _other = _pair()
    stopped = blk_store.stopped_alloc_blocks()[0]
    back = StoredAllocBlock.from_wire(
        json.loads(json.dumps(stopped.to_wire())))
    assert _rows(back.materialize()) == _rows(stopped.materialize())
    live = blk_store.alloc_blocks()[0]
    assert StoredAllocBlock.from_wire(live.to_wire()).desired_status == (
        structs.ALLOC_DESIRED_STATUS_RUN)


# -- the scheduler: when it names blocks and when it does not --------------------


BIG = 300   # above TPUGenericScheduler.BATCH_PLACE_THRESHOLD


class _Planner:
    """Commits as the FSM does: batches as blocks, stops as blocks."""

    def __init__(self, state):
        self.state = state
        self.plans = []
        self._index = 1000

    def submit_plan(self, plan):
        self.plans.append(plan)
        self._index += 1
        result = evaluate_plan(self.state.snapshot(), plan)
        result.alloc_index = self._index
        rows = [a for lst in result.node_update.values() for a in lst]
        rows += [a for lst in result.node_allocation.values() for a in lst]
        if rows:
            self.state.upsert_allocs(self._index, rows)
        if result.alloc_batches:
            self.state.upsert_alloc_blocks(self._index, result.alloc_batches)
        if result.stop_batches:
            self.state.apply_stop_batches(self._index, result.stop_batches)
        return result, None

    def update_eval(self, ev):
        self.last_status = ev.status

    def create_eval(self, ev):
        raise AssertionError("no follow-up evaluation expected")


def _cell(n_nodes=8):
    state = StateStore()
    for i in range(n_nodes):
        node = mock.node()
        node.id = f"cell-{i:02d}"
        state.upsert_node(i + 1, node)
    return state


def _sized_job(count):
    job = mock.job()
    job.type = structs.JOB_TYPE_BATCH
    tg = job.task_groups[0]
    tg.count = count
    tg.tasks[0].resources = Resources(cpu=20, memory_mb=16)
    return job


def _run(state, planner, job, trigger):
    ev = Evaluation(id=generate_uuid(), priority=job.priority, type=job.type,
                    triggered_by=trigger, job_id=job.id)
    sched = new_scheduler("tpu-batch", state.snapshot(), planner,
                          logging.getLogger("test.block_stop"))
    sched.process(ev)
    return ev


def _placed(count, n_nodes=8):
    state = _cell(n_nodes)
    planner = _Planner(state)
    job = _sized_job(count)
    state.upsert_job(500, job)
    _run(state, planner, job, structs.EVAL_TRIGGER_JOB_REGISTER)
    assert len(structs.filter_terminal_allocs(
        state.allocs_by_job(job.id))) == count
    return state, planner, job


def test_deregistered_block_job_stops_as_blocks_and_expands_nothing(
        monkeypatch):
    import nomad_tpu.state.blocks as blocks_mod

    state, planner, job = _placed(BIG)
    assert state.job_alloc_blocks(job.id)
    n_blocks = len(state.job_alloc_blocks(job.id))
    state.delete_job(600, job.id)
    expanded = []
    for name in ("materialize", "materialize_node", "materialize_pos",
                 "materialize_prefix"):
        orig = getattr(blocks_mod.StoredAllocBlock, name)
        monkeypatch.setattr(
            blocks_mod.StoredAllocBlock, name,
            lambda self, *a, _o=orig, _n=name: (
                expanded.append(_n), _o(self, *a))[1])
    ev = _run(state, planner, job, structs.EVAL_TRIGGER_JOB_DEREGISTER)
    plan = planner.plans[-1]
    assert stops_only(plan) and len(plan.stop_batches) == n_blocks
    assert sum(b.n for b in plan.stop_batches) == BIG
    assert {(b.eval_id, b.job_id, b.desired_status, b.desired_description)
            for b in plan.stop_batches} == {
        (ev.id, job.id, STOP, ALLOC_NOT_NEEDED)}
    assert expanded == []
    assert planner.last_status == structs.EVAL_STATUS_COMPLETE
    monkeypatch.undo()
    assert state.job_alloc_blocks(job.id) == []
    assert state.nodes_with_object_allocs() == set()
    rows = state.allocs_by_job(job.id)
    assert len(rows) == BIG and all(a.terminal_status() for a in rows)
    # Asked again, the stopped job has nothing left to stop.
    _run(state, planner, job, structs.EVAL_TRIGGER_JOB_DEREGISTER)
    assert len(planner.plans) == 2


def test_deregistered_job_of_object_rows_takes_the_reference_diff():
    state, planner, job = _placed(40)
    assert state.job_alloc_blocks(job.id) == []
    state.delete_job(600, job.id)
    _run(state, planner, job, structs.EVAL_TRIGGER_JOB_DEREGISTER)
    plan = planner.plans[-1]
    assert not plan.stop_batches
    assert sum(len(v) for v in plan.node_update.values()) == 40


def test_block_job_with_a_live_object_row_takes_the_reference_diff():
    state, planner, job = _placed(BIG)
    blk = state.job_alloc_blocks(job.id)[0]
    member = blk.materialize_pos(0)
    member.client_status = structs.ALLOC_CLIENT_STATUS_RUNNING
    state.update_allocs_from_client(550, [member])
    assert state.job_has_object_allocs(job.id)
    state.delete_job(600, job.id)
    _run(state, planner, job, structs.EVAL_TRIGGER_JOB_DEREGISTER)
    plan = planner.plans[-1]
    assert not plan.stop_batches
    assert sum(len(v) for v in plan.node_update.values()) == BIG
    assert not structs.filter_terminal_allocs(state.allocs_by_job(job.id))


def test_block_without_a_seed_takes_the_reference_diff():
    """A block cut to the nodes that fitted (filter_nodes) carries its
    ids spelled out; a stop batch could not name its members, so the
    scheduler does not write one."""
    state = _cell()
    job = _sized_job(BIG)
    state.upsert_job(500, job)
    ids = [n.id for n in state.nodes()]
    whole = AllocBatch(
        eval_id="ev-p", job=job, tg_name="web",
        resources=Resources(cpu=20, memory_mb=16),
        task_resources={"web": Resources(cpu=20, memory_mb=16)},
        node_ids=ids[:2], node_counts=[2, 2], name_idx=np.arange(4),
        ids_seed=77)
    cut = whole.filter_nodes({ids[0]: True, ids[1]: False})
    assert cut.ids_seed is None
    state.upsert_alloc_blocks(510, [cut])
    state.delete_job(600, job.id)
    planner = _Planner(state)
    _run(state, planner, job, structs.EVAL_TRIGGER_JOB_DEREGISTER)
    plan = planner.plans[-1]
    assert not plan.stop_batches
    assert sum(len(v) for v in plan.node_update.values()) == 2


def test_scale_down_of_a_block_job_still_stops_rows():
    state, planner, job = _placed(BIG)
    import copy

    smaller = copy.deepcopy(job)
    smaller.task_groups[0].count = BIG - 10
    state.upsert_job(600, smaller)
    _run(state, planner, smaller, structs.EVAL_TRIGGER_JOB_REGISTER)
    plan = planner.plans[-1]
    assert not plan.stop_batches
    assert sum(len(v) for v in plan.node_update.values()) == 10


def test_stop_frees_the_capacity_for_the_next_job():
    """A cell the first job fills: the second fits only once the first
    is stopped, and then fits whole (a stopped block's capacity is free,
    a live one's is not)."""
    state = _cell(n_nodes=2)
    planner = _Planner(state)
    first = _sized_job(2 * BIG)       # more than two nodes hold
    state.upsert_job(500, first)
    _run(state, planner, first, structs.EVAL_TRIGGER_JOB_REGISTER)

    def live(job):
        return len(structs.filter_terminal_allocs(
            state.allocs_by_job(job.id)))

    room = live(first)
    assert BIG <= room < 2 * BIG
    second = _sized_job(room)
    state.upsert_job(501, second)
    _run(state, planner, second, structs.EVAL_TRIGGER_JOB_REGISTER)
    assert live(second) == 0
    state.delete_job(600, first.id)
    _run(state, planner, first, structs.EVAL_TRIGGER_JOB_DEREGISTER)
    assert planner.plans[-1].stop_batches and live(first) == 0
    _run(state, planner, second, structs.EVAL_TRIGGER_JOB_REGISTER)
    assert live(second) == room


# -- the plan pipeline -------------------------------------------------------------


def test_stop_plan_is_neither_scalar_nor_fused_and_commits_whole():
    job, other = _job(), _job("other-job")
    store = _store(job, other)
    snap = store.snapshot()
    plan = Plan(eval_id="ev-stop", stop_batches=_stop_batches(snap, job.id))
    assert stops_only(plan) and not plan.is_noop()
    assert _plan_touched_nodes(plan) == {f"bs-node-{i}" for i in range(5)}
    totals = _PipelineTotals()
    seq = iter(range(21, 99))
    (result,) = evaluate_plans(snap, [plan], stamp_index=lambda: next(seq),
                               totals=totals)
    assert result.stop_batches == plan.stop_batches
    assert result.refresh_index == 0 and not result.is_noop()
    assert result.full_commit(plan) == (True, 17, 17)
    assert _plan_touched_nodes(result) == _plan_touched_nodes(plan)
    stats = totals.stats()
    assert (stats["stop_plans"], stats["scalar_plans"],
            stats["fused_plans"]) == (1, 0, 0)
    # The optimistic snapshot was rolled: the next plan of the batch
    # verifies against the freed capacity.
    assert snap.job_alloc_blocks(job.id) == []
    assert len(snap.stopped_alloc_blocks()) == 2 and snap.optimistic
    # The live store is untouched until the entry applies.
    assert len(store.job_alloc_blocks(job.id)) == 2


def test_stop_plan_between_placing_plans_keeps_them_fused():
    job, other = _job(), _job("other-job")
    store = _store(job, other)
    snap = store.snapshot()
    ids = _node_ids(store)

    def place(seed):
        return Plan(eval_id=f"ev-{seed}", alloc_batches=[
            _batch(_job(f"new-{seed}"), ids[:2], [1, 1], seed=seed,
                   eval_id=f"ev-{seed}")])

    plans = [place(201), place(202),
             Plan(eval_id="ev-stop",
                  stop_batches=_stop_batches(snap, job.id)),
             place(203), place(204)]
    totals = _PipelineTotals()
    seq = iter(range(21, 99))
    results = evaluate_plans(snap, plans, stamp_index=lambda: next(seq),
                             totals=totals)
    assert [len(r.alloc_batches) for r in results] == [1, 1, 0, 1, 1]
    assert len(results[2].stop_batches) == 2
    stats = totals.stats()
    assert (stats["stop_plans"], stats["fused_plans"],
            stats["scalar_plans"], stats["scalar_object_rows"]) == (
        1, 4, 0, 0)


def test_mixed_plan_takes_the_scalar_path_and_its_stops_commit():
    job, other = _job(), _job("other-job")
    store = _store(job, other)
    snap = store.snapshot()
    ids = _node_ids(store)
    mixed = Plan(eval_id="ev-mixed",
                 stop_batches=_stop_batches(snap, job.id),
                 alloc_batches=[_batch(_job("new"), ids[:1], [1], seed=300,
                                       eval_id="ev-mixed")])
    assert not stops_only(mixed)
    totals = _PipelineTotals()
    seq = iter(range(21, 99))
    results = evaluate_plans(
        snap, [mixed, Plan(eval_id="ev-2", alloc_batches=[
            _batch(_job("new2"), ids[:1], [1], seed=301, eval_id="ev-2")])],
        stamp_index=lambda: next(seq), totals=totals)
    assert len(results[0].stop_batches) == 2
    assert len(results[0].alloc_batches) == 1
    stats = totals.stats()
    assert stats["stop_plans"] == 0 and stats["scalar_ineligible"] == 1
