"""Native kernel parity + bulk plan verification equivalence.

The numpy fallbacks in nomad_tpu.native are the correctness oracle for the
C++ kernels; _prevaluate_nodes_bulk must agree with the scalar
evaluate_node_plan on every node it chooses to answer for.
"""

import random

import numpy as np
import pytest

from nomad_tpu import mock, native, structs
from nomad_tpu.server.plan_apply import (
    _prevaluate_nodes_bulk,
    evaluate_node_plan,
    evaluate_plan,
)
from nomad_tpu.state import StateStore
from nomad_tpu.structs import (
    Allocation,
    NetworkResource,
    Plan,
    Resources,
    generate_uuid,
)


def test_native_kernels_match_numpy_fallback():
    rng = np.random.default_rng(0)
    idx = rng.integers(0, 50, size=1000).astype(np.int32)
    vals = rng.integers(0, 100, size=(1000, 4)).astype(np.int32)

    got = native.scatter_add(idx, vals, 50)
    want = np.zeros((50, 4), dtype=np.int64)
    np.add.at(want, idx, vals)
    np.testing.assert_array_equal(got, want.astype(np.int32))

    used = rng.integers(0, 100, size=(200, 4)).astype(np.int32)
    total = rng.integers(0, 100, size=(200, 4)).astype(np.int32)
    fit, exhausted = native.fit_check(used, total)
    over = used > total
    np.testing.assert_array_equal(fit, ~over.any(axis=1))
    for i in range(200):
        if fit[i]:
            assert exhausted[i] == -1
        else:
            assert exhausted[i] == over[i].argmax()

    np.testing.assert_array_equal(
        native.bincount(idx, 50), np.bincount(idx, minlength=50)[:50]
    )


def _mk_alloc(node_id, cpu, mem, networks=None):
    res = Resources(cpu=cpu, memory_mb=mem)
    if networks:
        res.networks = networks
    return Allocation(
        id=generate_uuid(),
        node_id=node_id,
        job_id="j",
        task_group="tg",
        resources=res,
        desired_status=structs.ALLOC_DESIRED_STATUS_RUN,
    )


@pytest.mark.parametrize("seed", [0, 1, 2, 3, 4])
def test_bulk_verifier_matches_scalar(seed):
    """Random plans over a mixed cluster: every node the bulk verifier
    answers for must agree with evaluate_node_plan."""
    rng = random.Random(seed)
    state = StateStore()
    nodes = []
    for i in range(20):
        node = mock.node()
        node.id = f"n-{i:02d}"
        if rng.random() < 0.15:
            node.status = structs.NODE_STATUS_DOWN
        if rng.random() < 0.1:
            node.drain = True
        nodes.append(node)
        state.upsert_node(i + 1, node)

    # Seed some existing allocations (some with networks)
    idx = 100
    for node in nodes:
        for _ in range(rng.randrange(0, 3)):
            nets = None
            if rng.random() < 0.2:
                nets = [NetworkResource(device="eth0", ip="10.0.0.1", mbits=10)]
            state.upsert_allocs(
                idx, [_mk_alloc(node.id, rng.choice([200, 800]), 256, nets)]
            )
            idx += 1

    plan = Plan(eval_id=generate_uuid())
    shared = Resources(cpu=300, memory_mb=512)
    for node in nodes:
        n_place = rng.randrange(0, 12)
        for _ in range(n_place):
            alloc = Allocation(
                id=generate_uuid(), node_id=node.id, job_id="j2",
                task_group="tg2", resources=shared,
                desired_status=structs.ALLOC_DESIRED_STATUS_RUN,
            )
            plan.append_alloc(alloc)

    snap = state.snapshot()
    bulk = _prevaluate_nodes_bulk(snap, plan)
    assert bulk, "bulk verifier answered for no nodes"
    for node_id, fit in bulk.items():
        assert fit == evaluate_node_plan(snap, plan, node_id), node_id


def test_evaluate_plan_large_uses_bulk_and_matches():
    """A 500-placement plan through evaluate_plan: result identical to the
    scalar-only path (threshold forced high)."""
    from nomad_tpu.server import plan_apply

    state = StateStore()
    for i in range(10):
        node = mock.node()
        node.id = f"m-{i}"
        state.upsert_node(i + 1, node)

    plan = Plan(eval_id=generate_uuid())
    shared = Resources(cpu=100, memory_mb=128)
    for i in range(500):
        alloc = Allocation(
            id=generate_uuid(), node_id=f"m-{i % 10}", job_id="big",
            task_group="tg", resources=shared,
            desired_status=structs.ALLOC_DESIRED_STATUS_RUN,
        )
        plan.append_alloc(alloc)

    snap = state.snapshot()
    fast = evaluate_plan(snap, plan)

    orig = plan_apply.FAST_VERIFY_THRESHOLD
    plan_apply.FAST_VERIFY_THRESHOLD = 10**9
    try:
        slow = evaluate_plan(state.snapshot(), plan)
    finally:
        plan_apply.FAST_VERIFY_THRESHOLD = orig

    assert set(fast.node_allocation) == set(slow.node_allocation)
    for nid in fast.node_allocation:
        assert len(fast.node_allocation[nid]) == len(slow.node_allocation[nid])
    assert fast.refresh_index == slow.refresh_index


def test_bulk_verify_columnar_against_empty_node_table():
    """A large columnar plan verified against a snapshot whose nodes have
    ALL deregistered must answer fit=False for every node (stale data ->
    refresh), not crash indexing the empty table (the pure-columnar fast
    path's zero-row guard)."""
    from nomad_tpu.structs import AllocBatch

    state = StateStore()
    nodes = []
    for i in range(4):
        node = mock.node()
        node.id = f"gone-{i}"
        state.upsert_node(i + 1, node)
        nodes.append(node)
    job = mock.job()
    state.upsert_job(10, job)

    plan = Plan(eval_id=generate_uuid())
    batch = AllocBatch(
        eval_id=plan.eval_id, job=job, tg_name="web",
        resources=Resources(cpu=10, memory_mb=16),
        task_resources={},
        node_ids=[n.id for n in nodes],
        node_counts=[32, 32, 32, 32],  # past FAST_VERIFY_THRESHOLD
        name_idx=np.arange(128),
        ids_hex="ab" * (16 * 128),
    )
    plan.append_batch(batch)

    # Every node deregisters AFTER the plan was built.
    for i, n in enumerate(nodes):
        state.delete_node(20 + i, n.id)

    result = evaluate_plan(state.snapshot(), plan)
    assert not result.alloc_batches        # nothing committable
    assert result.refresh_index > 0        # stale-data refresh forced


# -- the loader builds from what git holds ----------------------------------


@pytest.fixture
def fresh_loader(monkeypatch):
    """nomad_tpu.native as a new process sees it: nothing loaded yet."""
    for name, value in (("_lib", None), ("_tried", False),
                        ("_built", False), ("_error", None)):
        monkeypatch.setattr(native, name, value)
    return native


def test_library_older_than_source_is_rebuilt(fresh_loader):
    import os

    if not native.available():
        pytest.skip("no toolchain: the numpy verifier is all there is")
    for name, value in (("_lib", None), ("_tried", False)):
        setattr(fresh_loader, name, value)
    src_mtime = os.path.getmtime(native._SRC)
    os.utime(native._SO, (src_mtime - 10, src_mtime - 10))
    assert native._stale()
    assert native.status() == {
        "verifier": "native", "built": True, "error": None}
    assert not native._stale()


def test_failed_make_is_logged_not_swallowed(fresh_loader, monkeypatch,
                                             caplog):
    import subprocess

    def failing_make(cmd, **kw):
        raise subprocess.CalledProcessError(
            2, cmd, stderr="nomad_native.cpp:1: error: expected ';'")

    monkeypatch.setattr(native, "_stale", lambda: True)
    monkeypatch.setattr(native.subprocess, "run", failing_make)
    with caplog.at_level("WARNING", logger="nomad_tpu.native"):
        status = native.status()
    assert status["verifier"] == "numpy" and not status["built"]
    assert "expected ';'" in status["error"]
    assert "numpy verifier runs instead" in caplog.text
    # the numpy path still answers
    fit, exhausted = native.fit_check(
        np.array([[1, 1]], np.int32), np.array([[2, 0]], np.int32))
    assert fit.tolist() == [False] and exhausted.tolist() == [1]
