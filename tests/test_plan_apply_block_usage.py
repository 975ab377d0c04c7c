"""The plan verifier's block usage rolls in both directions.

``plan_apply._existing_block_usage_rows`` keeps, per node table, the
usage of every live stored block and what each block contributed; a
call diffs the snapshot's blocks against it by identity, subtracts the
blocks gone and adds the new ones. Seeded random sequences on a real
``StateStore`` (block commits, whole-block stops, a member promoted out
of its block, blocks with network asks, blocks naming a node the table
does not know, an optimistic snapshot rolled by
``apply_result_to_snapshot`` and then replaced by the store's own) hold
the rolled usage equal, after every step, to a from-nothing
accumulation over the same snapshot and to the dict form
``_existing_block_usage``, which shares no code with either. A stop
subtracts its blocks and rebuilds nothing.
"""

import numpy as np
import pytest

from nomad_tpu import mock, structs
from nomad_tpu.server import plan_apply
from nomad_tpu.server.plan_apply import (
    _accumulate_block_usage,
    _existing_block_usage,
    _existing_block_usage_rows,
    _node_table,
    block_usage_stats,
)
from nomad_tpu.server.plan_pipeline import apply_result_to_snapshot
from nomad_tpu.state import StateStore
from nomad_tpu.structs import (
    AllocBatch,
    AllocStopBatch,
    NetworkResource,
    PlanResult,
    Resources,
)

N_SEEDS = 12
STOP = structs.ALLOC_DESIRED_STATUS_STOP


def _batch(rng, ids, job, serial, with_net=False, unknown=False):
    picks = [str(rng.choice(ids)) for _ in range(int(rng.integers(1, 6)))]
    if unknown:
        picks.append("no-such-node")
    counts = [int(rng.integers(1, 9)) for _ in picks]
    res = Resources(cpu=int(rng.integers(10, 400)),
                    memory_mb=int(rng.integers(16, 256)))
    if with_net:
        res.networks = [NetworkResource(device="eth0", mbits=10)]
    return AllocBatch(
        eval_id=f"ev-{serial}", job=job, tg_name="web", resources=res,
        task_resources={"t": res}, metrics=None, node_ids=picks,
        node_counts=counts, name_idx=np.arange(sum(counts)),
        ids_seed=int(rng.integers(1, 2**63)),
    )


def _stop_batch(blk):
    return AllocStopBatch(
        eval_id="ev-stop", job_id=blk.job_id, block_id=blk.block_id,
        n_live=blk.n_live, n_total=blk.n, ids_seed=blk.ids_seed,
        desired_status=STOP, desired_description="gone",
        node_ids=blk.node_ids)


def _check(snap, table):
    """The rolled usage equals the from-nothing one and the dict form."""
    usage, net_rows, _blocks = _existing_block_usage_rows(snap, table)
    assert table.block_usage_cache.contrib.keys() == {
        id(b) for b in snap.alloc_blocks()}
    want = _accumulate_block_usage(table, snap.alloc_blocks())
    want_usage, want_net = want.usage, want.net_rows
    zeros = np.zeros((table.n, 4), dtype=np.int64)
    no_net = np.zeros(table.n, dtype=bool)
    got_u = zeros if usage is None else usage
    got_n = no_net if net_rows is None else net_rows
    np.testing.assert_array_equal(
        got_u, zeros if want_usage is None else want_usage)
    np.testing.assert_array_equal(
        got_n, no_net if want_net is None else want_net)
    by_node, net_nodes, _ = _existing_block_usage(snap)
    for nid, row in table.rows.items():
        vec = by_node.get(nid)
        assert tuple(got_u[row]) == (
            (0, 0, 0, 0) if vec is None else tuple(int(x) for x in vec))
        assert bool(got_n[row]) == (nid in net_nodes)


@pytest.mark.parametrize("seed", range(N_SEEDS))
def test_block_usage_roll_equals_accumulation_from_nothing(seed):
    rng = np.random.default_rng(38_000 + seed)
    with plan_apply._NODE_TABLE_LOCK:
        plan_apply._NODE_TABLE_CACHE = None
    store = StateStore()
    nodes = []
    for i in range(int(rng.integers(4, 16))):
        node = mock.node()
        node.id = f"bu-node-{i}"
        node.reserved.networks = []
        nodes.append(node)
    store.upsert_nodes(1, nodes)
    ids = [n.id for n in nodes]
    jobs = [mock.job() for _ in range(3)]
    idx = 1
    table = _node_table(store.snapshot())
    _check(store.snapshot(), table)
    stopped = 0

    def new_batches(k):
        nonlocal idx
        out = []
        for _ in range(k):
            idx += 1
            out.append(_batch(rng, ids, jobs[int(rng.integers(0, 3))], idx,
                              with_net=rng.random() < 0.15,
                              unknown=rng.random() < 0.1))
        return out

    for _step in range(int(rng.integers(25, 45))):
        op = rng.random()
        live = store.snapshot().alloc_blocks()
        if op < 0.35 or not live:
            idx += 1
            store.upsert_alloc_blocks(idx, new_batches(
                int(rng.integers(1, 4))))
        elif op < 0.55:
            # Whole-block stops: subtracted, never a rebuild.
            picked = [live[int(i)] for i in rng.choice(
                len(live), size=min(len(live), int(rng.integers(1, 3))),
                replace=False)]
            before = block_usage_stats()
            idx += 1
            store.apply_stop_batches(idx, [_stop_batch(b) for b in picked])
            _check(store.snapshot(), table)
            after = block_usage_stats()
            assert after["block_usage_rebuilds"] == before[
                "block_usage_rebuilds"]
            assert after["block_usage_removals"] - before[
                "block_usage_removals"] == len(picked)
            stopped += len(picked)
        elif op < 0.75:
            # A member promoted to an object row: its block is replaced
            # by a copy that excludes it (or dissolves at half its size).
            blk = live[int(rng.integers(0, len(live)))]
            alloc = blk.materialize_pos(int(rng.choice(blk.live_positions())))
            alloc.desired_status = STOP
            idx += 1
            store.upsert_allocs(idx, [alloc])
        else:
            # The committer's sequence: an optimistic snapshot verified
            # with this wave's blocks and stops rolled in, then the store's
            # own snapshot once the entry applied (the blocks' twins).
            snap = store.snapshot()
            result = PlanResult(alloc_batches=new_batches(
                int(rng.integers(1, 3))))
            if rng.random() < 0.5:
                result.stop_batches = [_stop_batch(live[0])]
            idx += 1
            apply_result_to_snapshot(snap, result, idx)
            _check(snap, table)
            store.upsert_alloc_blocks(idx, result.alloc_batches)
            if result.stop_batches:
                store.apply_stop_batches(idx, result.stop_batches)
        _check(store.snapshot(), table)

    assert store.snapshot().get_index("nodes") == 1
    assert _node_table(store.snapshot()) is table
    if stopped:
        assert block_usage_stats()["block_usage_removals"] >= stopped


def test_concurrent_callers_each_get_their_snapshots_usage():
    """The committer and the schedulers' headroom base roll one table's
    accumulation from different snapshots at once: each caller reads the
    usage of its own snapshot, and whatever entry is published last is
    consistent with its own blocks."""
    import sys
    import threading

    rng = np.random.default_rng(38_999)
    with plan_apply._NODE_TABLE_LOCK:
        plan_apply._NODE_TABLE_CACHE = None
    store = StateStore()
    nodes = []
    for i in range(12):
        node = mock.node()
        node.id = f"cc-node-{i}"
        node.reserved.networks = []
        nodes.append(node)
    store.upsert_nodes(1, nodes)
    ids = [n.id for n in nodes]
    job = mock.job()
    snaps = []
    for i in range(16):
        store.upsert_alloc_blocks(i + 2, [_batch(
            rng, ids, job, i, with_net=rng.random() < 0.2)])
        live = store.snapshot().alloc_blocks()
        if i % 3 == 2:
            store.apply_stop_batches(100 + i, [_stop_batch(live[0])])
        snaps.append(store.snapshot())
    table = _node_table(snaps[-1])
    want = [_accumulate_block_usage(table, s.alloc_blocks()).usage
            for s in snaps]
    errors = []

    def worker(k):
        order = np.random.default_rng(k).permutation(len(snaps))
        for _ in range(20):
            for j in order:
                usage, _net, _ = _existing_block_usage_rows(snaps[j], table)
                if not np.array_equal(usage, want[j]):
                    errors.append((k, int(j)))

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=worker, args=(k,))
                   for k in range(16)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(old)
    assert errors == []
    acc = table.block_usage_cache
    held = [c[0] for c in acc.contrib.values()]
    np.testing.assert_array_equal(
        acc.usage, _accumulate_block_usage(table, held).usage)
