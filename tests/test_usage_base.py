"""The usage base (NodeMirror._base_usage_for): one production per alloc
generation, by what came and went.

Three things are held here:

- the alloc change log says, for each kind of write, which node ids may
  have changed usage, which object rows it replaced, and which block
  objects came and went (``StateStore.alloc_changes_since``);
- the base advanced through that log is, at every generation of a random
  history, bit for bit what a walk over every object row and every run
  of every block gives (``_walk_base_usage``, the Python walk that served
  before the advance and is kept here as the reference);
- a generation is produced once: threads that ask for it while it is
  produced wait and share the arrays, and a producer that raises leaves
  nobody waiting.
"""

import sys
import threading
import time

import numpy as np
import pytest

from nomad_tpu import structs
from nomad_tpu.structs import (
    AllocBatch,
    Allocation,
    AllocStopBatch,
    AllocUpdateBatch,
    Job,
    NetworkResource,
    Node,
    Plan,
    Resources,
    Task,
    TaskGroup,
    generate_uuid,
)

DCS = ["dc1"]


# -- a small cell --------------------------------------------------------------


def _node(i, reserved_cpu=0):
    node = Node(
        id=f"ub-{i:04d}", datacenter="dc1", name=f"ub-{i}",
        attributes={"kernel.name": "linux", "driver.exec": "1"},
        resources=Resources(
            cpu=32000, memory_mb=65536, disk_mb=100_000, iops=300,
            networks=[NetworkResource(device="eth0", cidr="10.0.0.0/8",
                                      ip=f"10.0.{i % 250}.1", mbits=10_000)]),
        status=structs.NODE_STATUS_READY,
    )
    if reserved_cpu:
        node.reserved = Resources(
            cpu=reserved_cpu, memory_mb=reserved_cpu // 2,
            networks=[NetworkResource(device="eth0", mbits=reserved_cpu % 7)])
    return node


def _job(name):
    return Job(
        region="global", id=name, name=name, type=structs.JOB_TYPE_BATCH,
        priority=50, datacenters=DCS,
        task_groups=[TaskGroup(
            name="web", count=8,
            tasks=[Task(name="t", driver="exec",
                        resources=Resources(cpu=50, memory_mb=64))])],
    )


def _cell(n_nodes):
    from nomad_tpu.state import StateStore

    store = StateStore()
    nodes = [_node(i, reserved_cpu=(100 + i if i % 5 == 0 else 0))
             for i in range(n_nodes)]
    store.upsert_nodes(1, nodes)
    return store, nodes


def _batch(job, node_ids, counts, cpu=20, mbits=0, seed=1):
    res = Resources(cpu=cpu, memory_mb=32)
    task_res = Resources(cpu=cpu, memory_mb=32)
    if mbits:
        task_res.networks = [NetworkResource(device="eth0", mbits=mbits)]
    return AllocBatch(
        eval_id=generate_uuid(), job=job, tg_name="web", resources=res,
        task_resources={"t": task_res}, metrics=None,
        node_ids=list(node_ids), node_counts=list(counts),
        name_idx=np.arange(sum(counts)), ids_seed=seed,
    )


def _alloc(job, node_id, cpu=30, mbits=0,
           status=structs.ALLOC_DESIRED_STATUS_RUN, alloc_id=None):
    task_res = Resources(cpu=cpu, memory_mb=16)
    if mbits:
        task_res.networks = [NetworkResource(device="eth0", mbits=mbits)]
    return Allocation(
        id=alloc_id or generate_uuid(), eval_id=generate_uuid(),
        name=f"{job.name}.web[0]", node_id=node_id, job_id=job.id, job=job,
        task_group="web", resources=Resources(cpu=cpu, memory_mb=16),
        task_resources={"t": task_res}, desired_status=status,
    )


def _walk_base_usage(mirror, state):
    """The reference: reserved + every live object row + every run of
    every block, one Python step each (what ``_compute_base_usage`` was
    before the advance; int32 like the mirror's arrays)."""
    from nomad_tpu.tpu.mirror import _res_vec, _task_bw

    used = mirror.reserved_np.copy()
    bw = mirror.bw_reserved.copy()
    index_get = mirror.index.get
    for a in state.allocs_objects():
        if a.terminal_status():
            continue
        i = index_get(a.node_id)
        if i is None:
            continue
        used[i] += _res_vec(a.resources)
        bw[i] += _task_bw(a.task_resources)
    for blk in state.alloc_blocks():
        vec = _res_vec(blk.resources)
        tbw = _task_bw(blk.task_resources)
        for nid, cnt in blk.live_node_counts():
            i = index_get(nid)
            if i is None:
                continue
            used[i] += vec * cnt
            if tbw:
                bw[i] += tbw * cnt
    return used, bw


def _usage_counts():
    from nomad_tpu.tpu.mirror import GLOBAL_MIRROR_CACHE

    s = GLOBAL_MIRROR_CACHE.stats()
    return {k: s[k] for k in ("usage_rolls", "usage_rebuilds",
                              "usage_shared")}


def _added(before):
    after = _usage_counts()
    return {k: after[k] - before[k] for k in after}


# -- the log: what each kind of write says came and went -----------------------


def _log_case_block_commit(store, nodes, job):
    store.upsert_alloc_blocks(10, [_batch(job, [nodes[0].id, nodes[1].id],
                                          [2, 1])])
    blk, = store.alloc_blocks()
    return ({nodes[0].id, nodes[1].id}, [], [blk], [])


def _log_case_object_upsert(store, nodes, job):
    a = _alloc(job, nodes[2].id)
    store.upsert_allocs(10, [a])
    moved = a.copy()
    moved.node_id = nodes[3].id
    store.upsert_allocs(11, [moved])
    return ({nodes[2].id, nodes[3].id},
            [(None, nodes[2].id), (nodes[2].id, nodes[3].id)], [], [])


def _log_case_stop_excludes_member(store, nodes, job):
    batch = _batch(job, [nodes[0].id, nodes[1].id], [4, 4])
    store.upsert_alloc_blocks(9, [batch])
    old, = store.alloc_blocks()
    stop = old.materialize_pos(0)
    stop.desired_status = structs.ALLOC_DESIRED_STATUS_STOP
    store.upsert_allocs(10, [stop])
    new, = store.alloc_blocks()
    assert new is not old and new.excluded == frozenset({0})
    return ({nodes[0].id}, [(None, nodes[0].id)], [new], [old])


def _log_case_exclusion_dissolves(store, nodes, job):
    batch = _batch(job, [nodes[0].id, nodes[1].id], [1, 1])
    store.upsert_alloc_blocks(9, [batch])
    old, = store.alloc_blocks()
    stop = old.materialize_pos(0)
    stop.desired_status = structs.ALLOC_DESIRED_STATUS_STOP
    store.upsert_allocs(10, [stop])
    assert store.alloc_blocks() == []   # half excluded: the rest are rows
    return ({nodes[0].id}, [(None, nodes[0].id), (None, nodes[1].id)], [],
            [old])


def _log_case_eval_reaped(store, nodes, job):
    batch = _batch(job, [nodes[4].id], [3])
    store.upsert_alloc_blocks(9, [batch])
    old, = store.alloc_blocks()
    obj = _alloc(job, nodes[5].id)
    store.upsert_allocs(9, [obj])
    store.delete_eval(10, [batch.eval_id], [obj.id])
    return ({nodes[4].id, nodes[5].id}, [(nodes[5].id, None)], [], [old])


def _log_case_whole_block_update(store, nodes, job):
    batch = _batch(job, [nodes[0].id], [3])
    store.upsert_alloc_blocks(9, [batch])
    old, = store.alloc_blocks()
    store.apply_update_batches(10, [AllocUpdateBatch(
        eval_id="ev-upd", job=job, tg_name="web",
        resources=Resources(cpu=70, memory_mb=32),
        alloc_ids=[batch.alloc_id(i) for i in range(batch.n)])])
    new, = store.alloc_blocks()
    assert new is not old and new.resources.cpu == 70
    return ({nodes[0].id}, [], [new], [old])


def _stop_batch(blk):
    return AllocStopBatch(
        eval_id="ev-stop", job_id=blk.job_id, block_id=blk.block_id,
        n_live=blk.n_live, n_total=blk.n, ids_seed=blk.ids_seed,
        desired_description="alloc not needed due to job update")


def _log_case_whole_block_stop(store, nodes, job):
    batch = _batch(job, [nodes[0].id, nodes[1].id], [4, 4])
    store.upsert_alloc_blocks(9, [batch])
    old, = store.alloc_blocks()
    assert store.apply_stop_batches(10, [_stop_batch(old)]) == [None]
    # The block went and nothing came: no row, no block in its place.
    assert store.alloc_blocks() == [] and store.allocs_objects() == []
    return ({nodes[0].id, nodes[1].id}, [], [], [old])


def _log_case_client_update_promotes(store, nodes, job):
    batch = _batch(job, [nodes[0].id, nodes[1].id], [4, 4])
    store.upsert_alloc_blocks(9, [batch])
    old, = store.alloc_blocks()
    member = old.materialize_pos(5)
    member.client_status = structs.ALLOC_CLIENT_STATUS_RUNNING
    store.update_allocs_from_client(10, [member])
    new, = store.alloc_blocks()
    # No node's usage moved, so the node feed stays empty; the member
    # moved from the block to the object table, and the log says so.
    return (set(), [(None, nodes[1].id)], [new], [old])


@pytest.mark.parametrize("case", [
    _log_case_block_commit, _log_case_object_upsert,
    _log_case_stop_excludes_member, _log_case_exclusion_dissolves,
    _log_case_eval_reaped, _log_case_whole_block_update,
    _log_case_whole_block_stop, _log_case_client_update_promotes,
], ids=lambda f: f.__name__[len("_log_case_"):])
def test_alloc_log_names_what_came_and_went(case):
    store, nodes = _cell(8)
    nodes_want, rows_want, added_want, removed_want = case(
        store, nodes, _job("log-job"))
    snap = store.snapshot()
    assert snap.alloc_node_changes_since(9) == nodes_want
    rows, added, removed = snap.alloc_changes_since(9)
    # Each replaced object row as (old row's node, new row's node).
    assert sorted(((old and old.node_id or ""), (new and new.node_id or ""))
                  for old, new in rows) == sorted(
        (o or "", n or "") for o, n in rows_want)
    assert [id(b) for b in added] == [id(b) for b in added_want]
    assert [id(b) for b in removed] == [id(b) for b in removed_want]
    # A reader at the newest index sees nothing; one behind a restore's
    # floor is told the log cannot say.
    assert snap.alloc_changes_since(snap.get_index("allocs")) == (
        [], [], [])
    restore = store.restore()
    for node in nodes:
        restore.node_restore(node)
    restore.index_restore("allocs", 20)
    restore.commit()
    assert store.alloc_changes_since(19) is None
    assert store.alloc_node_changes_since(19) is None


def test_a_row_upserted_as_the_object_the_table_holds_is_opaque():
    """A caller that changes a stored row in place and upserts it again
    has left nothing to tell what the row was: the log says it cannot
    say, and the base is recomputed."""
    from nomad_tpu.scheduler.util import ready_nodes_in_dcs
    from nomad_tpu.tpu.mirror import NodeMirror

    store, nodes = _cell(8)
    a = _alloc(_job("opaque"), nodes[2].id, cpu=40)
    store.upsert_allocs(9, [a])
    mirror = NodeMirror(ready_nodes_in_dcs(store.snapshot(), DCS))
    mirror._base_usage_for(store.snapshot())
    a.resources = Resources(cpu=75, memory_mb=16)
    store.upsert_allocs(10, [a])
    snap = store.snapshot()
    assert snap.alloc_changes_since(9) is None
    assert snap.alloc_node_changes_since(9) == {nodes[2].id}
    before = _usage_counts()
    used, _bw = mirror._base_usage_for(snap)
    np.testing.assert_array_equal(used, _walk_base_usage(mirror, snap)[0])
    assert _added(before) == {"usage_rolls": 0, "usage_rebuilds": 1,
                              "usage_shared": 0}


# -- differential: the advanced base against the walk, over a random history ---


N_HISTORY_SEEDS = 8


@pytest.mark.parametrize("seed", range(N_HISTORY_SEEDS))
def test_usage_base_advance_matches_full_walk(seed, monkeypatch):
    from nomad_tpu.scheduler.context import EvalContext
    from nomad_tpu.scheduler.util import ready_nodes_in_dcs
    from nomad_tpu.state import store as store_mod
    from nomad_tpu.tpu.mirror import MirrorCache, NodeMirror

    # A short horizon, so that a few writes between two solves run the
    # log out from under the cached generation.
    monkeypatch.setattr(store_mod, "ALLOC_LOG_HORIZON", 6)
    rng = np.random.default_rng(29_000 + seed)
    n = int(rng.integers(24, 64))
    store, nodes = _cell(n)
    ids = [nd.id for nd in nodes]
    jobs = [_job(f"h{seed}-{k}") for k in range(3)]
    idx = 1
    cache = MirrorCache()
    batches = []       # committed AllocBatches, for member addressing
    objects = []       # upserted object rows
    before = _usage_counts()

    def commit_block():
        # 1 node to all of them; every third over half the cell.
        k = (int(rng.integers(n // 2 + 1, n + 1)) if rng.random() < 0.35
             else int(rng.integers(1, 6)))
        picks = [str(x) for x in rng.choice(ids, size=k, replace=False)]
        if rng.random() < 0.2:
            picks.append(picks[0])       # a node with two runs
        counts = [int(rng.integers(1, 5)) for _ in picks]
        b = _batch(jobs[int(rng.integers(0, 3))], picks, counts,
                   cpu=int(rng.integers(5, 60)),
                   mbits=int(rng.choice([0, 0, 3])),
                   seed=int(rng.integers(1, 2**62)))
        store.upsert_alloc_blocks(idx, [b])
        batches.append(b)

    def upsert_objects():
        rows = []
        for _ in range(int(rng.integers(1, 5))):
            status = (structs.ALLOC_DESIRED_STATUS_RUN
                      if rng.random() < 0.8
                      else structs.ALLOC_DESIRED_STATUS_STOP)
            rows.append(_alloc(jobs[int(rng.integers(0, 3))],
                               str(rng.choice(ids)),
                               cpu=int(rng.integers(5, 90)),
                               mbits=int(rng.choice([0, 2])), status=status))
        if objects and rng.random() < 0.5:
            # An existing row again: other size, other node, or stopped;
            # now and then as the very object the table holds, changed
            # in place, which the log can only call opaque.
            again = objects[int(rng.integers(0, len(objects)))]
            if rng.random() < 0.8:
                again = again.copy()
                if rng.random() < 0.5:
                    again.node_id = str(rng.choice(ids))
            again.resources = Resources(cpu=int(rng.integers(5, 90)),
                                        memory_mb=16)
            if rng.random() < 0.3:
                again.desired_status = structs.ALLOC_DESIRED_STATUS_STOP
            rows.append(again)
        store.upsert_allocs(idx, rows)
        objects.extend(rows)

    def live_members(limit):
        out = []
        for blk in store.alloc_blocks():
            for pos in blk.live_positions()[:limit]:
                out.append(blk.materialize_pos(pos))
        return out

    def stop_members():
        members = live_members(3)
        if not members:
            return commit_block()
        picks = rng.choice(len(members), size=min(len(members),
                                                  int(rng.integers(1, 4))),
                           replace=False)
        stops = []
        for k in picks:
            m = members[int(k)]
            m.desired_status = structs.ALLOC_DESIRED_STATUS_STOP
            stops.append(m)
        store.upsert_allocs(idx, stops)
        objects.extend(stops)

    def reap_job_blocks():
        job = jobs[int(rng.integers(0, 3))]
        evals = sorted({b.eval_id for b in store.job_alloc_blocks(job.id)})
        dead = [a.id for a in objects[:2]
                if store.alloc_object_by_id(a.id) is not None]
        store.delete_eval(idx, evals, dead)

    def update_whole_block():
        blks = store.alloc_blocks()
        if not blks:
            return commit_block()
        blk = blks[int(rng.integers(0, len(blks)))]
        store.apply_update_batches(idx, [AllocUpdateBatch(
            eval_id=generate_uuid(), job=blk.job, tg_name="web",
            resources=Resources(cpu=int(rng.integers(5, 90)), memory_mb=32),
            alloc_ids=[blk.alloc_id(p) for p in blk.live_positions()])])

    def stop_whole_block():
        blks = store.alloc_blocks()
        if not blks:
            return commit_block()
        blk = blks[int(rng.integers(0, len(blks)))]
        store.apply_stop_batches(idx, [_stop_batch(blk)])

    def client_update():
        members = live_members(2)
        if not members:
            return commit_block()
        m = members[int(rng.integers(0, len(members)))]
        m.client_status = structs.ALLOC_CLIENT_STATUS_RUNNING
        store.update_allocs_from_client(idx, [m])

    def reregister_node():
        # Through apply_delta: the generations ride to the new mirror
        # with the reserved delta of the patched row.
        node = store.node_by_id(str(rng.choice(ids))).copy()
        node.reserved = Resources(cpu=int(rng.integers(0, 400)),
                                  memory_mb=int(rng.integers(0, 200)))
        store.upsert_node(idx, node)

    writes = [commit_block, commit_block, upsert_objects, stop_members,
              reap_job_blocks, update_whole_block, stop_whole_block,
              client_update, reregister_node]
    prev = None
    for step in range(14):
        # Mostly one to three writes between two solves; now and then
        # enough to trim the log past the cached generation.
        for _ in range(16 if rng.random() < 0.15
                       else int(rng.integers(1, 4))):
            idx += 1
            writes[int(rng.integers(0, len(writes)))]()
        snap = store.snapshot()
        _n, mirror = cache.get(snap, DCS)
        fresh = NodeMirror(ready_nodes_in_dcs(snap, DCS))
        where = f"seed {seed} step {step} index {idx}"
        got_used, got_bw = mirror._base_usage_for(snap)
        want_used, want_bw = _walk_base_usage(fresh, snap)
        np.testing.assert_array_equal(got_used, want_used, err_msg=where)
        np.testing.assert_array_equal(got_bw, want_bw, err_msg=where)
        assert got_used.dtype == want_used.dtype == np.int32
        # The full recompute is held to the same walk.
        for got, want in zip(mirror._compute_base_usage(snap)[:2],
                             (want_used, want_bw)):
            np.testing.assert_array_equal(got, want, err_msg=where)
        # And the whole per-eval usage to the original walk.
        plan = Plan(eval_id=generate_uuid())
        nid = str(rng.choice(ids))
        plan.node_allocation.setdefault(nid, []).append(
            _alloc(jobs[0], nid, cpu=11))
        ctx = EvalContext(snap, plan)
        for got, want, name in zip(
                mirror.build_usage(ctx, jobs[0].id, "web"),
                fresh._build_usage_walk(ctx, jobs[0].id, "web"),
                ("used", "job_count", "tg_count", "bw_used")):
            np.testing.assert_array_equal(
                np.asarray(got), np.asarray(want),
                err_msg=f"{where}: {name}")
        # The generation before this one is still served, unchanged.
        if prev is not None and prev[0] is mirror:
            _m, p_snap, p_used, p_bw = prev
            counts = _usage_counts()
            again_used, again_bw = mirror._base_usage_for(p_snap)
            assert again_used is p_used and again_bw is p_bw, where
            assert _usage_counts() == counts, where
        prev = (mirror, snap, got_used, got_bw)
    moved = _added(before)
    assert moved["usage_rolls"] >= 5, moved
    assert 1 <= moved["usage_rebuilds"] <= 8, moved


# -- single flight --------------------------------------------------------------


@pytest.fixture
def flight_cell():
    """A mirror with a first generation cached and one commit after it:
    (store, nodes, mirror, job, snapshot of the new generation)."""
    from nomad_tpu.scheduler.util import ready_nodes_in_dcs
    from nomad_tpu.tpu.mirror import NodeMirror

    store, nodes = _cell(48)
    job = _job("flight")
    store.upsert_alloc_blocks(2, [_batch(job, [nd.id for nd in nodes[:30]],
                                         [2] * 30)])
    mirror = NodeMirror(ready_nodes_in_dcs(store.snapshot(), DCS))
    mirror._base_usage_for(store.snapshot())       # first fill
    store.upsert_alloc_blocks(3, [_batch(job, [nd.id for nd in nodes[10:]],
                                         [1] * 38, seed=2)])
    return store, nodes, mirror, job, store.snapshot()


def _ask_from_threads(mirror, snap, n_threads):
    """``n_threads`` ask for ``snap``'s base at once; each one's result
    or exception, in thread order. Every join has its time limit."""
    out = [None] * n_threads
    barrier = threading.Barrier(n_threads)

    def ask(k):
        barrier.wait(timeout=10)
        try:
            out[k] = mirror._base_usage_for(snap)
        except Exception as e:   # handed to the test, which asserts on it
            out[k] = e

    threads = [threading.Thread(target=ask, args=(k,), daemon=True)
               for k in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=20)
    assert not any(t.is_alive() for t in threads), "a waiter hangs"
    return out


def test_eight_threads_one_generation_one_roll(flight_cell, monkeypatch):
    store, nodes, mirror, job, snap = flight_cell
    advance = mirror._advance_usage
    calls = []

    def slow_advance(*args):
        calls.append(threading.get_ident())
        time.sleep(0.3)     # the other seven arrive while this one works
        return advance(*args)

    monkeypatch.setattr(mirror, "_advance_usage", slow_advance)
    before = _usage_counts()
    out = _ask_from_threads(mirror, snap, 8)
    assert len(calls) == 1
    assert _added(before) == {"usage_rolls": 1, "usage_rebuilds": 0,
                              "usage_shared": 7}
    assert all(o[0] is out[0][0] and o[1] is out[0][1] for o in out)
    want_used, want_bw = _walk_base_usage(mirror, snap)
    np.testing.assert_array_equal(out[0][0], want_used)
    np.testing.assert_array_equal(out[0][1], want_bw)
    assert not mirror._usage_flights


def test_a_producer_that_raises_leaves_no_waiter_hanging(flight_cell,
                                                         monkeypatch):
    store, nodes, mirror, job, snap = flight_cell
    advance = mirror._advance_usage
    calls = []

    def first_raises(*args):
        calls.append(threading.get_ident())
        if len(calls) == 1:
            time.sleep(0.3)
            raise RuntimeError("producer fell over")
        return advance(*args)

    monkeypatch.setattr(mirror, "_advance_usage", first_raises)
    before = _usage_counts()
    out = _ask_from_threads(mirror, snap, 8)
    failed = [o for o in out if isinstance(o, Exception)]
    served = [o for o in out if not isinstance(o, Exception)]
    assert len(failed) == 1 and "fell over" in str(failed[0])
    # The next caller produced, once, and the other six shared it.
    assert len(calls) == 2 and len(served) == 7
    assert _added(before) == {"usage_rolls": 1, "usage_rebuilds": 0,
                              "usage_shared": 6}
    assert all(o[0] is served[0][0] for o in served)
    np.testing.assert_array_equal(served[0][0],
                                  _walk_base_usage(mirror, snap)[0])
    assert not mirror._usage_flights


def _staged_path(mirror, snap):
    """``path`` as the usage_base cut notes it for one build_usage."""
    from nomad_tpu import trace
    from nomad_tpu.scheduler.context import EvalContext
    from nomad_tpu.trace import StageTimer

    st = StageTimer()
    with trace.use_stages(st), st.stage("staging"):
        mirror.build_usage(EvalContext(snap, Plan(eval_id=generate_uuid())),
                           "flight", "web")
    return {c[0]: c for c in st.stages}["staging.usage_base"][5]["path"]


def test_older_generations_are_hits_or_rolls_never_rebuilds(flight_cell):
    store, nodes, mirror, job, snap3 = flight_cell
    mirror._base_usage_for(snap3)
    store.upsert_allocs(4, [_alloc(job, nodes[0].id)])
    snap4 = store.snapshot()
    store.upsert_alloc_blocks(5, [_batch(job, [nodes[1].id], [3], seed=3)])
    snap5 = store.snapshot()
    mirror._base_usage_for(snap5)       # the ring holds 2, 3 and 5
    before = _usage_counts()
    # One generation behind the newest: found, by the thread that made it.
    assert _staged_path(mirror, snap3) == "hit"
    assert _added(before) == {"usage_rolls": 0, "usage_rebuilds": 0,
                              "usage_shared": 0}
    # Between two cached generations: rolled from the nearest older one.
    assert _staged_path(mirror, snap4) == "roll"
    assert _added(before) == {"usage_rolls": 1, "usage_rebuilds": 0,
                              "usage_shared": 0}
    np.testing.assert_array_equal(mirror._base_usage_for(snap4)[0],
                                  _walk_base_usage(mirror, snap4)[0])
    # Another thread finds the newest generation ready: shared.
    out = _ask_from_threads(mirror, snap5, 1)
    assert out[0][0] is mirror._base_usage_for(snap5)[0]
    assert _added(before) == {"usage_rolls": 1, "usage_rebuilds": 0,
                              "usage_shared": 1}


def test_the_ring_is_short_and_an_optimistic_state_is_never_cached(
        flight_cell):
    from nomad_tpu.tpu.mirror import USAGE_RING

    store, nodes, mirror, job, snap = flight_cell
    for k in range(USAGE_RING + 3):
        store.upsert_allocs(10 + k, [_alloc(job, nodes[k].id)])
        mirror._base_usage_for(store.snapshot())
    ring = mirror._usage_ring
    assert len(ring) == USAGE_RING
    assert [g.aidx for g in ring] == sorted(g.aidx for g in ring)
    assert ring[-1].aidx == store.get_index("allocs")
    opt = store.snapshot()
    opt.upsert_allocs(99, [_alloc(job, nodes[0].id, cpu=77)])
    before = _usage_counts()
    used, _bw = mirror._base_usage_for(opt)
    np.testing.assert_array_equal(used, _walk_base_usage(mirror, opt)[0])
    assert [g.aidx for g in mirror._usage_ring] == [g.aidx for g in ring]
    assert not any(_added(before).values())


def test_usage_base_under_contending_threads(flight_cell):
    """More threads than cores, a short switch interval, a writer that
    commits while they read: every base served is the walk's, and no
    generation is produced twice."""
    store, nodes, mirror, job, _snap = flight_cell
    stop = time.monotonic() + 3.0
    errors = []
    snaps = [store.snapshot()]
    snaps_lock = threading.Lock()

    def writer():
        k = 0
        while time.monotonic() < stop and k < 40:
            k += 1
            if k % 3:
                store.upsert_alloc_blocks(100 + k, [_batch(
                    job, [nodes[(k + j) % 48].id for j in range(20)],
                    [1] * 20, seed=100 + k)])
            else:
                store.upsert_allocs(100 + k, [_alloc(job, nodes[k % 48].id)])
            with snaps_lock:
                snaps.append(store.snapshot())
            time.sleep(0.01)

    def reader(seed):
        rng = np.random.default_rng(seed)
        while time.monotonic() < stop:
            with snaps_lock:
                snap = snaps[-1 - int(rng.integers(0, min(3, len(snaps))))]
            try:
                used, bw = mirror._base_usage_for(snap)
                want_used, want_bw = _walk_base_usage(mirror, snap)
                if not (np.array_equal(used, want_used)
                        and np.array_equal(bw, want_bw)):
                    errors.append(snap.get_index("allocs"))
            except Exception as e:   # reported below, with the rest
                errors.append(e)

    before = _usage_counts()
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=writer, daemon=True)]
        threads += [threading.Thread(target=reader, args=(s,), daemon=True)
                    for s in range(24)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(interval)
    assert errors == []
    moved = _added(before)
    assert moved["usage_rebuilds"] == 0, moved
    assert moved["usage_rolls"] <= len(snaps), moved
    assert moved["usage_shared"] > 0, moved
    assert not mirror._usage_flights
