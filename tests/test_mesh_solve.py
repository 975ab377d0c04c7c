"""Node-axis sharded production solve (parallel/mesh.py).

The water-fill kernels that carry the 10k-node x 100k-task load run SPMD
over the configured (evals x nodes) Mesh — the blueprint's scale axis
(SURVEY.md §7 "blockwise/sharded masking and top-k over the node axis";
the reference's analogous scale machinery is the candidate-scan bound,
/root/reference/scheduler/stack.go:94-121). These tests run the REAL
scheduler path end-to-end on the 8-virtual-device CPU mesh (conftest.py)
and assert sharded == single-device placements.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from nomad_tpu import mock, structs
from nomad_tpu.parallel import mesh as mesh_lib
from nomad_tpu.structs import Evaluation, generate_uuid

from sched_harness import Harness
from test_coalesce import _direct, _inputs, _submit

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 8, reason="needs the 8-device virtual mesh"
)


@pytest.fixture
def node_mesh():
    mesh = mesh_lib.configure_node_sharding(8)
    try:
        yield mesh
    finally:
        mesh_lib.clear_node_sharding()


def test_waterfill_sharded_matches_single_device(node_mesh):
    """The same closed-form water-fill, dispatched with node-axis
    shardings, must produce identical counts."""
    from nomad_tpu.ops.binpack import solve_waterfill

    rng = np.random.default_rng(7)
    for trial in range(5):
        n = 64
        total = np.zeros((n, 4), dtype=np.int32)
        total[:, 0] = rng.integers(500, 8000, n)
        total[:, 1] = rng.integers(512, 16384, n)
        total[:, 2] = 100 * 1024
        total[:, 3] = 150
        inp = dict(
            total=jnp.asarray(total),
            sched_cap=jnp.asarray(total[:, :2].astype(np.float32)),
            used0=jnp.zeros((n, 4), dtype=jnp.int32),
            job_count0=jnp.zeros((n,), dtype=jnp.int32),
            tg_count0=jnp.zeros((n,), dtype=jnp.int32),
            bw_avail=jnp.full((n,), 1000, dtype=jnp.int32),
            bw_used0=jnp.zeros((n,), dtype=jnp.int32),
            eligible=jnp.asarray(rng.random(n) > 0.2),
            ask=jnp.array([100 + 10 * trial, 128, 0, 0], dtype=jnp.int32),
            bw_ask=jnp.int32(0),
            count=int(rng.integers(100, 2000)),
            penalty=10.0,
        )
        # Single-device reference
        d_counts, d_unplaced = _direct(inp)
        # Sharded dispatch of the same args
        args10 = mesh_lib.shard_waterfill_args(node_mesh, (
            inp["total"], inp["sched_cap"], inp["used0"], inp["job_count0"],
            inp["tg_count0"], inp["bw_avail"], inp["bw_used0"],
            inp["eligible"], inp["ask"], inp["bw_ask"],
        ))
        count, penalty = mesh_lib.replicate_on_mesh(
            node_mesh, jnp.int32(inp["count"]), jnp.float32(inp["penalty"])
        )
        counts, remaining = solve_waterfill(
            *args10, count, penalty, False, False
        )
        np.testing.assert_array_equal(np.asarray(counts), d_counts,
                                      err_msg=f"trial {trial}")
        assert int(remaining) == d_unplaced


def test_coalesced_batch_dispatch_on_mesh(node_mesh):
    """The vmapped batched water-fill runs sharded too: concurrent entries
    through the coalescer on the mesh match their individual solves."""
    from nomad_tpu.ops.coalesce import CoalescingSolver

    engine = CoalescingSolver()
    inputs = [_inputs(50 + 10 * i, 200 + 37 * i) for i in range(4)]
    fetches = [_submit(engine, inp) for inp in inputs]
    for inp, fetch in zip(inputs, fetches):
        counts, unplaced = fetch()
        d_counts, d_unplaced = _direct(inp)
        np.testing.assert_array_equal(counts, d_counts)
        assert unplaced == d_unplaced


@pytest.mark.parametrize("family", ["wf", "exact"])
def test_stacked_dispatch_on_an_eval_by_node_mesh(family):
    """A (2 evals x 4 nodes) mesh: the rows are placed node-sharded in
    front of the same jitted entries, the stack inside the program rides
    the eval axis (mesh.constrain_eval_stack), and every rider's result
    is its lone solve's. The mesh branch is not a single-program
    dispatch: the counter stays where it was."""
    from nomad_tpu.ops.coalesce import CoalescingSolver
    from test_coalesce import (
        _assert_bit_equal,
        _family_entries,
        _panel_single,
    )

    mesh_lib.configure_node_sharding(8, eval_parallel=2)
    try:
        engine = CoalescingSolver()
        inputs, entries = _family_entries(family, 64, 4, salt=1)
        single0 = _panel_single()
        engine._dispatch(entries)
        assert (engine.dispatches, engine.batch_retries) == (1, 0)
        assert _panel_single() == single0
        a_dev = entries[0].group.counts_dev
        assert len(a_dev.sharding.device_set) == 8
        _assert_bit_equal(family, inputs, entries)
    finally:
        mesh_lib.clear_node_sharding()


def _run_big_service_eval(factory):
    """A 32-node cluster and a count=300 service job: count > the exact
    threshold, so the TPU path runs the water-fill production kernel."""
    h = Harness()
    for i in range(32):
        node = mock.node()
        node.resources.cpu = 14000
        node.resources.memory_mb = 28000
        h.state.upsert_node(h.next_index(), node)
    job = mock.job()
    job.task_groups[0].count = 300
    h.state.upsert_job(h.next_index(), job)
    ev = Evaluation(
        id=generate_uuid(),
        priority=job.priority,
        triggered_by=structs.EVAL_TRIGGER_JOB_REGISTER,
        job_id=job.id,
    )
    h.process(factory, ev)
    assert len(h.plans) == 1
    per_node = {}
    for node_id, allocs in h.plans[0].node_allocation.items():
        per_node[node_id] = per_node.get(node_id, 0) + len(allocs)
    for batch in h.plans[0].alloc_batches:
        for node_id, cnt in zip(batch.node_ids, batch.node_counts):
            per_node[node_id] = per_node.get(node_id, 0) + int(cnt)
    return h, per_node


def test_tpu_scheduler_end_to_end_sharded_matches_single_device():
    """TPUGenericScheduler end-to-end over the mesh: same eval, same
    placements as the single-device dispatch, and full placement count."""
    _h0, single = _run_big_service_eval("tpu-service")
    mesh = mesh_lib.configure_node_sharding(8)
    try:
        _h1, sharded = _run_big_service_eval("tpu-service")
    finally:
        mesh_lib.clear_node_sharding()
    assert sum(single.values()) == 300
    # Node identities differ between harnesses (fresh uuids); the placement
    # *distribution* must match exactly: same multiset of per-node counts.
    assert sorted(single.values()) == sorted(sharded.values())


def test_tpu_system_scheduler_on_mesh():
    """The system scheduler's one-dispatch fit check also runs sharded."""
    mesh = mesh_lib.configure_node_sharding(8)
    try:
        h = Harness()
        for i in range(16):
            h.state.upsert_node(h.next_index(), mock.node())
        job = mock.system_job()
        h.state.upsert_job(h.next_index(), job)
        ev = Evaluation(
            id=generate_uuid(),
            priority=job.priority,
            type=structs.JOB_TYPE_SYSTEM,
            triggered_by=structs.EVAL_TRIGGER_JOB_REGISTER,
            job_id=job.id,
        )
        h.process("tpu-system", ev)
        assert len(h.plans) == 1
        placed = sum(
            len(v) for v in h.plans[0].node_allocation.values()
        ) + sum(b.n for b in h.plans[0].alloc_batches)
        assert placed == 16
    finally:
        mesh_lib.clear_node_sharding()


def test_mesh_dispatch_guardrails(node_mesh):
    """Perf guardrails on the sharded production path: a warm eval issues
    exactly one coalesced dispatch, and NO node-axis tensor is resharded
    at dispatch (mirror tensors and usage are born sharded —
    put_node_sharded). A regression that reintroduces per-dispatch
    resharding fails here, not in a profile."""
    from nomad_tpu.ops.coalesce import GLOBAL_SOLVER
    from nomad_tpu.structs import Resources

    h = Harness()
    for i in range(64):
        node = mock.node()
        node.id = f"guard-{i:03d}"
        node.resources.cpu = 14000
        node.resources.memory_mb = 28000
        h.state.upsert_node(h.next_index(), node)
    job = mock.job()
    job.id = "guard-job"
    job.task_groups[0].count = 200  # > threshold: columnar water-fill path
    for t in job.task_groups[0].tasks:
        t.resources = Resources(cpu=50, memory_mb=64)
    h.state.upsert_job(h.next_index(), job)

    # Warm run: compiles, builds the mirror, fills mask caches.
    ev = Evaluation(
        id=generate_uuid(), priority=job.priority, type=job.type,
        triggered_by=structs.EVAL_TRIGGER_JOB_REGISTER, job_id=job.id,
    )
    h.process("tpu-batch", ev)

    # Measured run: same store generation (mirror cache hit), existing
    # allocs present (usage tensorization is NOT the clean fast path).
    mesh_lib.reset_stats()
    d0 = GLOBAL_SOLVER.dispatches
    ev2 = Evaluation(
        id=generate_uuid(), priority=job.priority, type=job.type,
        triggered_by=structs.EVAL_TRIGGER_JOB_REGISTER, job_id=job.id,
    )
    h.process("tpu-batch", ev2)

    assert GLOBAL_SOLVER.dispatches - d0 <= 1, (
        "warm eval issued multiple device dispatches"
    )
    assert mesh_lib.STATS["node_reshards"] == 0, mesh_lib.STATS
    # Usage tensors for this eval are born sharded: a handful of puts, not
    # one per dispatch arg; small-arg replication stays bounded.
    assert mesh_lib.STATS["node_puts"] <= 8, mesh_lib.STATS
    assert mesh_lib.STATS["replications"] <= 6, mesh_lib.STATS


def test_apply_solver_mesh_fallback_and_configure():
    """The server-config face: a mesh the local device set can't satisfy
    falls back transparently (None, solves stay single-device); a
    satisfiable one configures and is indistinguishable from the env/
    explicit path."""
    from nomad_tpu.parallel.mesh import SolverMeshConfig

    cfg = mesh_lib.SolverMeshConfig.parse({"node_shards": 1024})
    assert mesh_lib.apply_solver_mesh(cfg) is None
    assert mesh_lib.node_sharding_mesh() is None

    cfg = SolverMeshConfig.parse({"node_shards": 4, "eval_parallel": 2})
    mesh = mesh_lib.apply_solver_mesh(cfg)
    try:
        assert mesh is not None
        assert mesh.shape[mesh_lib.NODE_AXIS] == 4
        assert mesh.shape[mesh_lib.EVAL_AXIS] == 2
        assert mesh_lib.node_sharding_mesh() is mesh
    finally:
        mesh_lib.clear_node_sharding()

    # Disabled spec: no-op.
    assert mesh_lib.apply_solver_mesh(SolverMeshConfig.parse(None)) is None


def test_sharded_mirror_delta_roll_keeps_node_sharding(node_mesh):
    """The mesh-aware _rows_update: rolling a sharded mirror forward
    through a node write must leave the patched buffers NODE_AXIS-
    sharded (out_shardings-pinned scatter) — a roll that let the output
    sharding float would cost every later solve a full-axis reshard."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from nomad_tpu import mock
    from nomad_tpu.state import StateStore
    from nomad_tpu.tpu.mirror import MirrorCache

    store = StateStore()
    nodes = []
    for i in range(12):
        n = mock.node()
        n.id = f"roll-{i:02d}"
        store.upsert_node(i + 1, n)
        nodes.append(n)
    cache = MirrorCache()
    snap0 = store.snapshot()
    _n0, m0 = cache.get(snap0, ["dc1"])
    want = NamedSharding(node_mesh, P(mesh_lib.NODE_AXIS, None))
    assert m0.total.sharding == want

    # Resource-only rewrite of one resident node: the delta path.
    import copy

    n2 = copy.deepcopy(nodes[3])
    n2.resources.cpu += 111
    store.upsert_node(100, n2)
    rolls0 = cache.delta_rolls
    _n1, m1 = cache.get(store.snapshot(), ["dc1"])
    assert cache.delta_rolls == rolls0 + 1, "write did not take the roll"
    assert m1.total.sharding == want, "roll dropped the node sharding"
    assert m1.sched_cap.sharding == NamedSharding(
        node_mesh, P(mesh_lib.NODE_AXIS, None))
    assert m1.bw_avail.sharding == NamedSharding(
        node_mesh, P(mesh_lib.NODE_AXIS))
    # And the rolled row actually carries the write.
    row = m1.index["roll-03"]
    assert int(np.asarray(m1.total)[row, 0]) == n2.resources.cpu


def test_stacked_exact_dispatch_on_mesh_matches_single_device(node_mesh):
    """The cross-eval batched exact scan runs SPMD too: stacked entries
    through the coalescer on the mesh match their single-device solves
    bit-for-bit."""
    import test_coalesce as tc
    from nomad_tpu.ops.coalesce import CoalescingSolver

    engine = CoalescingSolver()
    inputs = [tc._inputs(50 + 10 * i, 20 + 7 * i) for i in range(4)]
    expected = [tc._direct_exact(inp) for inp in inputs]
    fetches = [tc._submit_exact(engine, inp) for inp in inputs]
    for (idxs, oks), (e_idxs, e_oks) in zip(
        [f() for f in fetches], expected
    ):
        np.testing.assert_array_equal(idxs, e_idxs)
        np.testing.assert_array_equal(oks, e_oks)


def test_mesh_dispatch_count_bounded_for_concurrent_evals(node_mesh):
    """Concurrent solves on the mesh stay correct and bounded: K submits
    cost at most K dispatches (coalescing may merge them into fewer), each
    matching its individual single-device solve."""
    from nomad_tpu.ops.coalesce import CoalescingSolver

    engine = CoalescingSolver()
    inputs = [_inputs(60, 200), _inputs(80, 260), _inputs(40, 120)]
    expected = [_direct(inp) for inp in inputs]
    d0 = engine.dispatches
    fetches = [_submit(engine, inp) for inp in inputs]
    got = [f() for f in fetches]
    for (counts, unplaced), (ecounts, eunplaced) in zip(got, expected):
        np.testing.assert_array_equal(counts, ecounts)
        assert unplaced == eunplaced
    assert engine.dispatches - d0 <= len(inputs)
