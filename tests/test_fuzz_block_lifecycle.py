"""Differential fuzz: block-columnar state vs object state over job
lifecycles.

The same scheduler logic runs against two state representations of
identical clusters — one committing plans columnar (StoredAllocBlock, the
FSM posture) and one materializing everything to object rows (the
reference posture). After every lifecycle step the two worlds must agree
on placement totals, per-node distribution, per-node resource usage, and
job version — proving the block-native reconcile/update paths
(tpu/solver.py _block_reconcile, AllocUpdateBatch src_* columns) are
semantically invisible. Reference oracle: the five-way diff + inplace
update semantics (util.go:54-131, 265-302, 316-398)."""

import copy
import logging
import os

import numpy as np
import pytest

from nomad_tpu import mock, structs
from nomad_tpu.scheduler import new_scheduler
from nomad_tpu.server.plan_apply import evaluate_plan
from nomad_tpu.state import StateStore
from nomad_tpu.structs import (
    Evaluation,
    Resources,
    allocs_fit,
    generate_uuid,
)

N_SEEDS = int(os.environ.get("NOMAD_TPU_FUZZ_SEEDS", 8))
BATCH = 300


class _Committer:
    """Applies evaluated plans to state; columnar or materializing."""

    def __init__(self, state, columnar: bool):
        self.state = state
        self.columnar = columnar
        self._index = 10_000

    def submit_plan(self, plan):
        self._index += 1
        result = evaluate_plan(self.state.snapshot(), plan)
        result.alloc_index = self._index
        allocs = []
        for lst in result.node_update.values():
            allocs.extend(lst)
        for lst in result.node_allocation.values():
            allocs.extend(lst)
        allocs.extend(result.failed_allocs)
        if self.columnar:
            if allocs:
                self.state.upsert_allocs(self._index, allocs)
            if result.alloc_batches:
                self.state.upsert_alloc_blocks(
                    self._index, result.alloc_batches
                )
            if result.update_batches:
                self.state.apply_update_batches(
                    self._index, result.update_batches
                )
            if result.stop_batches:
                self.state.apply_stop_batches(
                    self._index, result.stop_batches
                )
        else:
            for b in result.alloc_batches:
                allocs.extend(b.materialize())
            for b in result.update_batches:
                b.resolve(self.state.snapshot())
                allocs.extend(b.materialize())
            if allocs:
                self.state.upsert_allocs(self._index, allocs)
        return result, None

    def update_eval(self, ev):
        pass

    def create_eval(self, ev):
        pass


def _mk_world(n_nodes):
    state = StateStore()
    for i in range(n_nodes):
        node = mock.node()
        node.id = f"node-{i:03d}"
        state.upsert_node(i + 1, node)
    return state


def _process(state, planner, job,
             trigger=structs.EVAL_TRIGGER_JOB_REGISTER):
    ev = Evaluation(
        id=generate_uuid(), priority=job.priority, type=job.type,
        triggered_by=trigger, job_id=job.id,
    )
    sched = new_scheduler("tpu-batch", state.snapshot(), planner,
                         logging.getLogger("fuzz"))
    sched.process(ev)


def _world_view(state, job_id):
    """Comparable summary of a job's live allocations."""
    live = [a for a in state.allocs_by_job(job_id)
            if a.desired_status == structs.ALLOC_DESIRED_STATUS_RUN]
    per_node = {}
    usage = {}
    for a in live:
        per_node[a.node_id] = per_node.get(a.node_id, 0) + 1
        vec = np.asarray(a.resources.as_vector(), dtype=np.int64)
        usage[a.node_id] = usage.get(a.node_id, 0) + vec
    versions = {a.job.modify_index for a in live}
    return len(live), per_node, {k: tuple(int(x) for x in v)
                                 for k, v in usage.items()}, versions


@pytest.mark.parametrize("seed", range(N_SEEDS))
def test_block_vs_object_lifecycle(seed):
    rng = np.random.default_rng(31_000 + seed)
    n_nodes = int(rng.choice([6, 10, 16]))
    count = int(rng.choice([BATCH, BATCH + 50]))

    state_b = _mk_world(n_nodes)
    state_o = _mk_world(n_nodes)
    planner_b = _Committer(state_b, columnar=True)
    planner_o = _Committer(state_o, columnar=False)

    job = mock.job()
    job.type = structs.JOB_TYPE_BATCH
    tg = job.task_groups[0]
    tg.count = count
    tg.tasks[0].resources = Resources(
        cpu=int(rng.integers(20, 40)), memory_mb=int(rng.integers(32, 64))
    )
    tg.tasks[0].resources.networks = []

    idx = 5000
    state_b.upsert_job(idx, copy.deepcopy(job))
    state_o.upsert_job(idx, copy.deepcopy(job))
    _process(state_b, planner_b, job)
    _process(state_o, planner_o, job)

    steps = int(rng.integers(1, 4))
    for _ in range(steps):
        op = rng.choice(["grow", "shrink_res", "scale_up", "env"])
        job = copy.deepcopy(job)
        tg = job.task_groups[0]
        if op == "grow":
            tg.tasks[0].resources.memory_mb += int(rng.integers(1, 16))
        elif op == "shrink_res":
            tg.tasks[0].resources.cpu = max(
                1, tg.tasks[0].resources.cpu - int(rng.integers(1, 10))
            )
        elif op == "scale_up":
            tg.count += int(rng.integers(1, 40))
        else:  # destructive
            tg.tasks[0].env = {"V": str(int(rng.integers(0, 1000)))}
        idx += 1
        state_b.upsert_job(idx, copy.deepcopy(job))
        state_o.upsert_job(idx, copy.deepcopy(job))
        _process(state_b, planner_b, job)
        _process(state_o, planner_o, job)

        n_b, per_node_b, usage_b, ver_b = _world_view(state_b, job.id)
        n_o, per_node_o, usage_o, ver_o = _world_view(state_o, job.id)
        assert n_b == n_o, (seed, op, n_b, n_o)
        assert per_node_b == per_node_o, (seed, op)
        assert usage_b == usage_o, (seed, op)
        assert ver_b == ver_o, (seed, op, ver_b, ver_o)

        # Soundness in the columnar world: no node overcommitted.
        for node in state_b.nodes():
            live = [a for a in state_b.allocs_by_node(node.id)
                    if a.desired_status == structs.ALLOC_DESIRED_STATUS_RUN]
            fit, _dim, _u = allocs_fit(node, live)
            assert fit, (seed, op, node.id)

        # The O(1) live-object counter must equal a full scan at every
        # step (it gates the block-level reconcile).
        t = state_b._t
        scan = {}
        for a in t.allocs.values():
            if not a.terminal_status():
                scan[a.job_id] = scan.get(a.job_id, 0) + 1
        assert scan == t.live_objs_by_job, (seed, op)

    # The job ends: deregistered in both worlds. The columnar world names
    # whatever blocks it still holds whole and stops the rest row by row;
    # either way every allocation reads stopped, node for node as in the
    # object world, and holds nothing.
    idx += 1
    for state, planner in ((state_b, planner_b), (state_o, planner_o)):
        state.delete_job(idx, job.id)
        _process(state, planner, job, structs.EVAL_TRIGGER_JOB_DEREGISTER)

    def stopped_view(state):
        rows = state.allocs_by_job(job.id)
        assert all(a.terminal_status() for a in rows), seed
        per_node = {}
        for a in rows:
            if a.desired_status == structs.ALLOC_DESIRED_STATUS_STOP:
                per_node[a.node_id] = per_node.get(a.node_id, 0) + 1
        return len(rows), per_node

    assert stopped_view(state_b) == stopped_view(state_o), seed
    assert _world_view(state_b, job.id)[0] == 0
    assert state_b.job_alloc_blocks(job.id) == []
    assert state_b.alloc_blocks() == []


@pytest.mark.parametrize("seed", range(4))
def test_no_lost_wakeup_under_concurrent_bulk_commits(seed):
    """Stress the watch fast path's ordering contract: watcher threads
    continuously run the register -> re-check -> wait loop (the
    blocking-query pattern) against random nodes while a writer commits
    columnar blocks. Every watcher must observe the final allocs index
    promptly — a lost wakeup (member items skipped for a waiter that
    registered mid-commit without post-write visibility) would strand a
    watcher until its deadline."""
    import threading
    import time as _time

    from nomad_tpu.state.store import item_alloc_node
    from nomad_tpu.structs import AllocBatch, Resources, generate_uuid

    def _mk_batch(job, node_ids, counts, eval_id):
        n = sum(counts)
        return AllocBatch(
            eval_id=eval_id, job=job, tg_name=job.task_groups[0].name,
            resources=Resources(cpu=1, memory_mb=1),
            node_ids=list(node_ids), node_counts=list(counts),
            name_idx=list(range(n)),
            ids_hex="".join(
                generate_uuid().replace("-", "") for _ in range(n)
            ),
        )

    rng = np.random.default_rng(90_000 + seed)
    store = StateStore()
    nodes = [mock.node() for _ in range(12)]
    for i, n in enumerate(nodes):
        store.upsert_node(i + 1, n)
    job = mock.job()
    store.upsert_job(100, job)

    N_COMMITS = 30
    final_index = 100 + N_COMMITS
    errors = []
    observed = []

    def watcher(widx):
        # Per-thread RNG: np.random.Generator is not thread-safe, and a
        # shared one would make seeded failures unreproducible.
        wrng = np.random.default_rng(90_000 + seed * 100 + widx)
        node = nodes[int(wrng.integers(0, len(nodes)))]
        deadline = _time.monotonic() + 30.0
        last = 0
        while _time.monotonic() < deadline:
            ticket = store.watch.register([item_alloc_node(node.id)])
            try:
                idx = store.snapshot().get_index("allocs")
                if idx >= final_index:
                    observed.append((widx, idx))
                    return
                if idx == last:
                    # Park with a SHORT timeout: a lost wakeup shows up
                    # as systematically timing out instead of waking.
                    store.watch.wait(ticket, timeout=0.5)
                last = idx
            finally:
                store.watch.unregister(ticket)
        errors.append(f"watcher {widx} never saw index {final_index}")

    threads = [threading.Thread(target=watcher, args=(i,)) for i in range(6)]
    for t in threads:
        t.start()
    for c in range(N_COMMITS):
        k = int(rng.integers(1, len(nodes) + 1))
        sel = rng.choice(len(nodes), size=k, replace=False)
        batch = _mk_batch(
            job, [nodes[i].id for i in sel], [1] * k,
            eval_id=f"gen-{seed}-{c}",
        )
        store.upsert_alloc_blocks(101 + c, [batch])
        _time.sleep(0.002)
    for t in threads:
        t.join(35.0)
    assert not errors, errors
    assert len(observed) == 6
