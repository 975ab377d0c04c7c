"""The candidate rule (nomad_tpu/scheduler/candidates.py, ISSUE 37): what
each evaluation's solve may choose from.

- the keys: the same evaluation draws the same candidates twice, two
  evaluations draw different ones, neighbours in the log never share a
  class, an attempt after a refused plan draws afresh, and every node is
  some evaluation's candidate;
- the device against the host oracle, decision for decision: the
  dispatch's program under a key places exactly what the standing solve
  places on the oracle's mask, for both program families, stacked;
- widening: a group that its class cannot hold is placed all the same,
  inside the same dispatch, and a cell filled to its last slot still
  places (the ``cell_full`` case);
- the counters the benchmark reads.
"""

import os

import jax.numpy as jnp
import numpy as np
import pytest

from nomad_tpu import structs
from nomad_tpu.ops import binpack, coalesce
from nomad_tpu.scheduler import candidates
from nomad_tpu.scheduler.context import EvalContext
from nomad_tpu.structs import (
    Evaluation,
    Job,
    Node,
    Plan,
    Resources,
    Task,
    TaskGroup,
    generate_uuid,
)

from sched_harness import Harness

N_SEEDS = int(os.environ.get("NOMAD_TPU_FUZZ_SEEDS", 40))


# -- the keys ------------------------------------------------------------------


def _ctx(eval_id="e1", attempt=0, eval_index=0):
    return EvalContext(None, Plan(eval_id=eval_id), attempt=attempt,
                       eval_index=eval_index)


def test_the_same_evaluation_draws_the_same_candidates_twice():
    for attempt, index in ((0, 0), (0, 4711), (1, 4711), (3, 0)):
        a = candidates.draw_key(_ctx("ev-a", attempt, index))
        b = candidates.draw_key(_ctx("ev-a", attempt, index))
        assert a == b and 0 <= a < 2 * candidates.RETRY
        # A drawn key says so: no class of it is the evaluation's own.
        assert (a >= candidates.RETRY) == (attempt > 0 or index == 0)


def test_neighbours_in_the_log_never_share_a_class():
    # One to one on any 256 consecutive indexes: the evaluations in
    # flight at one time are neighbours in the broker's queue.
    for start in (1, 97, 12_345_678):
        keys = [candidates.index_key(i) for i in range(start, start + 256)]
        assert sorted(keys) == list(range(256))
    # And far apart at the coarser levels too, whatever the stride the
    # log's other entries give them (a job, its evaluation, its plan and
    # its evaluation's end: a stride of 2 to 4): sixteen in flight fall
    # into 13 or more of level 4's sixteen classes.
    for stride in (1, 2, 3, 4, 5, 6):
        for start in range(64):
            keys = [candidates.index_key(start + stride * i)
                    for i in range(16)]
            assert len({k >> 4 for k in keys}) >= 13, (stride, start)


def test_two_evaluations_draw_different_candidates():
    # With no index the draw is the evaluation's own seeded stream.
    drawn = {candidates.draw_key(_ctx(f"ev-{i}")) for i in range(64)}
    assert len(drawn) > 48 and min(drawn) >= candidates.RETRY
    # An attempt after a refused plan draws afresh, whatever the index.
    ctx = lambda attempt: _ctx("ev-x", attempt, eval_index=77)  # noqa: E731
    first = candidates.draw_key(ctx(0))
    assert first == candidates.index_key(77)
    later = [candidates.draw_key(ctx(a)) for a in range(1, 5)]
    assert len(set(later)) == 4 and first not in later


@pytest.mark.parametrize("n_padded", [16, 32, 256, 1024, 8192, 16384])
def test_every_node_is_some_evaluations_candidate(n_padded):
    bits = candidates.class_bits(n_padded)
    assert bits == max(0, min(8, n_padded.bit_length() - 5))
    keys = candidates.node_keys(n_padded)
    width = candidates.level_widths(bits)[bits]
    cap = np.ones(n_padded, dtype=np.int64)
    seen = np.zeros(n_padded, dtype=bool)
    sizes = []
    for key in range(0, 2 ** candidates.KEY_BITS, width):
        mask = candidates.oracle_mask(cap, key, 1, spread=False)
        assert not (seen & mask).any()      # classes do not overlap
        seen |= mask
        sizes.append(int(mask.sum()))
        # The class of a level is the union of two of the next.
        if bits:
            assert mask.sum() == ((keys ^ key) < width).sum()
    assert seen.all()
    # The golden-ratio hash deals the rows evenly: no class is empty or
    # twice its share.
    share = n_padded / len(sizes)
    assert min(sizes) >= share / 2 and max(sizes) <= 2 * share


# -- the device against the oracle, decision for decision ----------------------


def _cap(s, count, room=candidates.HEADROOM):
    """Per-node capacity in copies, as the rule counts it, on the host."""
    avail = (s["total"] - s["used"]).astype(np.int64)
    ok = (avail >= 0).all(axis=1) & (s["bw_used"] <= s["bw_avail"])
    with np.errstate(divide="ignore"):
        per_dim = np.where(s["ask"] > 0,
                           avail // np.maximum(s["ask"], 1), 2 ** 30)
    cap = per_dim.min(axis=1)
    if s["bw_ask"] > 0:
        cap = np.minimum(cap, (s["bw_avail"] - s["bw_used"]) // s["bw_ask"])
    if s["jd"]:
        cap = np.minimum(cap, s["job_count"] == 0)
    if s["td"]:
        cap = np.minimum(cap, s["tg_count"] == 0)
    return np.where(s["eligible"] & ok, np.clip(cap, 0, room * count), 0)


def _inputs(rng, n, jd, td):
    total = np.zeros((n, 4), dtype=np.int32)
    total[:, 0] = rng.choice([16000, 32000, 64000], n)
    total[:, 1] = rng.choice([3932, 32768, 65536, 131072], n)
    total[:, 2] = 102400
    total[:, 3] = 150
    frac = rng.random((n, 1)) * rng.choice([0.0, 0.6, 0.98])
    used = (total * frac).astype(np.int32)
    job_count = (rng.integers(0, 3, n) * (rng.random() < 0.4)).astype(np.int32)
    return dict(
        total=total, used=used, job_count=job_count,
        tg_count=np.minimum(job_count, rng.integers(0, 2, n)).astype(np.int32),
        bw_avail=rng.integers(100, 2000, n).astype(np.int32),
        bw_used=np.zeros(n, dtype=np.int32),
        eligible=rng.random(n) > rng.choice([0.0, 0.06, 0.9]),
        ask=np.array([int(rng.choice([400, 800, 1600, 4000, 8000])),
                      int(rng.choice([1024, 2048, 4096, 8192, 24576])),
                      0, 0], dtype=np.int32),
        bw_ask=int(rng.integers(0, 200)) if rng.random() < 0.3 else 0,
        jd=jd, td=td,
    )


def _row(s, eligible=None):
    sched = (s["total"][:, :2]).astype(np.float32)
    return (jnp.asarray(s["total"]), jnp.asarray(sched),
            jnp.asarray(s["used"]), jnp.asarray(s["job_count"]),
            jnp.asarray(s["tg_count"]), jnp.asarray(s["bw_avail"]),
            jnp.asarray(s["bw_used"]),
            jnp.asarray(s["eligible"] if eligible is None else eligible),
            jnp.asarray(s["ask"]), jnp.int32(s["bw_ask"]))


@pytest.mark.parametrize("seed", range(N_SEEDS))
def test_stacked_dispatch_under_keys_matches_the_oracle(seed):
    """B evaluations stacked in ONE program, each under its own key: every
    row places exactly what the standing solve places where eligibility
    is narrowed on the host by the oracle's mask. The exact scan's node
    for each copy in turn; the water-fill's count for each node."""
    rng = np.random.default_rng(370_000 + seed)
    n = int(rng.choice([64, 256, 1024]))
    width = int(rng.choice([1, 2, 4]))
    jd = bool(rng.random() < 0.15)
    td = bool(rng.random() < 0.15 and not jd)
    base = _inputs(rng, n, jd, td)       # one mirror: shared node tensors
    rows = []
    for _ in range(width):
        s = dict(base)
        s["eligible"] = base["eligible"] & (rng.random(n) > 0.1)
        s["ask"] = _inputs(rng, n, jd, td)["ask"]
        rows.append(s)
    keys = [int(rng.integers(-1, 2 * candidates.RETRY)) for _ in rows]
    pens = np.array([float(rng.choice([5.0, 10.0]))] * width, np.float32)

    # The exact scan.
    counts = np.array([int(rng.integers(1, 129)) for _ in rows], np.int32)
    k = binpack.bucket(int(counts.max()))
    masks = [candidates.oracle_mask(_cap(s, int(c)), key, int(c),
                                    spread=False)
             for s, c, key in zip(rows, counts, keys)]
    shared = tuple(_row(base)[i] for i in coalesce._SHARED_COLS)

    def eval_cols(row):
        return tuple(row[i] for i in coalesce._EVAL_COLS)

    got_i, got_ok = coalesce.solve_greedy_rows(
        shared, tuple(eval_cols(_row(s)) for s in rows), counts, pens, k,
        jd, td, None, np.asarray(keys, np.int32))
    want_i, want_ok = coalesce.solve_greedy_rows(
        shared, tuple(eval_cols(_row(s, s["eligible"] & m))
                      for s, m in zip(rows, masks)),
        counts, pens, k, jd, td)
    np.testing.assert_array_equal(np.asarray(got_ok), np.asarray(want_ok))
    np.testing.assert_array_equal(
        np.where(got_ok, got_i, -1), np.where(want_ok, want_i, -1))

    # The water-fill.
    counts = np.array([int(rng.integers(129, 3000)) for _ in rows], np.int32)
    masks = [candidates.oracle_mask(_cap(s, int(c)), key, int(c),
                                    spread=True)
        for s, c, key in zip(rows, counts, keys)]
    got_c, got_left = coalesce.solve_waterfill_rows(
        tuple(_row(s) for s in rows), counts, pens, jd, td, "jnp", None,
        np.asarray(keys, np.int32))
    want_c, want_left = coalesce.solve_waterfill_rows(
        tuple(_row(s, s["eligible"] & m) for s, m in zip(rows, masks)),
        counts, pens, jd, td)
    np.testing.assert_array_equal(np.asarray(got_c), np.asarray(want_c))
    np.testing.assert_array_equal(np.asarray(got_left), np.asarray(want_left))
    # Never fewer than the whole cell holds: the rule narrows where to
    # place, not how many.
    full_c, full_left = coalesce.solve_waterfill_rows(
        tuple(_row(s) for s in rows), counts, pens, jd, td)
    np.testing.assert_array_equal(np.asarray(got_left), np.asarray(full_left))


def test_floor_div_is_the_integer_division():
    rng = np.random.default_rng(37)
    a = rng.integers(0, 2 ** 24, 100_000).astype(np.int32)
    b = rng.integers(1, 40_000, 100_000).astype(np.int32)
    a[:1000] = b[:1000] * rng.integers(0, 400, 1000)   # exact multiples
    got = np.asarray(binpack._floor_div(jnp.asarray(a), jnp.asarray(b)))
    np.testing.assert_array_equal(got, a // b)
    assert int(binpack._floor_div(jnp.int32(-5), jnp.int32(3))) == 0


# -- widening, through the scheduler -------------------------------------------


def _cell(h, n, cpu=4000, memory_mb=8192):
    nodes = []
    for i in range(n):
        node = Node(
            id=f"cand-{i:04d}", datacenter="dc1", name=f"n{i}",
            attributes={"kernel.name": "linux", "driver.exec": "1"},
            resources=Resources(cpu=cpu, memory_mb=memory_mb,
                                disk_mb=100_000, iops=1000),
            status=structs.NODE_STATUS_READY)
        h.state.upsert_node(h.next_index(), node)
        nodes.append(node)
    return nodes


def _job(count, cpu=1000, memory_mb=1024, jtype=structs.JOB_TYPE_BATCH):
    return Job(
        region="global", id=generate_uuid(), name="cand", type=jtype,
        priority=50, datacenters=["dc1"],
        task_groups=[TaskGroup(name="g", count=count, tasks=[Task(
            name="t", driver="exec",
            resources=Resources(cpu=cpu, memory_mb=memory_mb))])])


def _run(h, job, factory="tpu-batch"):
    h.state.upsert_job(h.next_index(), job)
    ev = Evaluation(
        id=generate_uuid(), priority=job.priority, type=job.type,
        triggered_by=structs.EVAL_TRIGGER_JOB_REGISTER, job_id=job.id,
        job_modify_index=h.state.job_by_id(job.id).modify_index,
        status=structs.EVAL_STATUS_PENDING)
    h.process(factory, ev)
    return [a for a in h.state.allocs_by_job(job.id)
            if a.desired_status == structs.ALLOC_DESIRED_STATUS_RUN]


def _panel():
    from nomad_tpu.tpu.solver import SOLVER_PANEL

    snap = SOLVER_PANEL.snapshot()
    return {k: snap[k] for k in ("solves", "sampled_solves",
                                 "widened_solves", "schedule_attempts")}


def test_a_small_job_stays_in_its_class_and_is_counted():
    h = Harness()
    nodes = _cell(h, 200)           # bucket 256: sixteen classes
    before = _panel()
    placed = _run(h, _job(8))
    after = _panel()
    assert len(placed) == 8
    rows = {n.id: i for i, n in enumerate(nodes)}
    keys = candidates.node_keys(256)[[rows[a.node_id] for a in placed]]
    assert len(set((keys >> 4).tolist())) == 1      # one class of sixteen
    assert after["sampled_solves"] - before["sampled_solves"] == 1
    assert after["widened_solves"] == before["widened_solves"]
    assert after["schedule_attempts"] - before["schedule_attempts"] == 1


def test_a_group_its_class_cannot_hold_widens_and_is_placed():
    h = Harness()
    nodes = _cell(h, 200, cpu=8000)     # eight copies a node, ~12 a class
    before = _panel()
    placed = _run(h, _job(120))     # one exact scan; a class holds ~100
    after = _panel()
    assert len(placed) == 120
    assert after["solves"] - before["solves"] == 1      # the same dispatch
    assert after["widened_solves"] - before["widened_solves"] == 1
    assert after["sampled_solves"] - before["sampled_solves"] == 1
    # Half the cell: the finest level whose roomy nodes hold the group
    # (two copies a node where a node has room for eight).
    rows = {n.id: i for i, n in enumerate(nodes)}
    keys = candidates.node_keys(256)[[rows[a.node_id] for a in placed]]
    assert len(set((keys >> 7).tolist())) == 1
    assert len({a.node_id for a in placed}) >= 60


def test_a_cell_filled_to_its_last_slot_still_places():
    """The ``cell_full`` case: whatever classes and roomy nodes are left,
    the last slots of the cell are found, by the exact scan and by the
    water-fill, and one copy more is the only one left out."""
    h = Harness()
    _cell(h, 60)                    # 240 slots of 1000 MHz
    assert len(_run(h, _job(200))) == 200       # water-fill, roomy nodes
    assert len(_run(h, _job(30))) == 30         # exact scan, its class
    assert len(_run(h, _job(9))) == 9
    before = _panel()
    last = _run(h, _job(2))                     # 1 slot left in the cell
    assert len(last) == 1
    assert _panel()["widened_solves"] - before["widened_solves"] == 1
    assert len(_run(h, _job(150))) == 0         # and the water-fill: none
    by_node = {}
    for a in h.state.allocs():
        if a.desired_status == structs.ALLOC_DESIRED_STATUS_RUN:
            by_node[a.node_id] = by_node.get(a.node_id, 0) + 1
    assert sum(by_node.values()) == 240 and max(by_node.values()) == 4


def test_the_host_scheduler_places_as_many():
    """The reference side of the differential: on the same cell the host
    stack (a shuffled sample of two nodes) and the dense stack (a class
    of nodes) place the same number, class or no class."""
    for count in (8, 120, 239):
        got = {}
        for factory in ("batch", "tpu-batch"):
            h = Harness()
            _cell(h, 60)
            got[factory] = len(_run(h, _job(count), factory))
        assert got["batch"] == got["tpu-batch"] == count


def test_a_remainder_rides_the_program_of_its_whole_group():
    """What is left of a group after a refused plan is a count of its
    own, and would bring a count bucket of its own to compile inside a
    run: it is solved by the program its group solved with, the exact
    scan of the group's bucket or the water-fill."""
    from nomad_tpu.ops.coalesce import GLOBAL_SOLVER
    from nomad_tpu.tpu.solver import SOLVER_PANEL, TPUStack

    h = Harness()
    nodes = _cell(h, 200, cpu=8000)
    job = _job(100)
    ctx = EvalContext(h.state.snapshot(), Plan(eval_id=generate_uuid()),
                      eval_index=7)
    stack = TPUStack(ctx, batch=True)
    stack.set_nodes(nodes)
    stack.set_job(job)
    tg = job.task_groups[0]

    def buckets():
        return {b["bucket"]: b["solves"]
                for b in SOLVER_PANEL.snapshot()["count_buckets"]}

    before, paths = buckets(), dict(GLOBAL_SOLVER.paths)
    idxs, oks, _ = stack.solve_group(tg, 3, group_count=100)
    assert oks.all() and len(idxs) == 3
    after = buckets()
    assert after.get(128, 0) - before.get(128, 0) == 1     # not bucket 8
    assert after.get(8, 0) == before.get(8, 0)
    # Of a group the water-fill places, the water-fill places the rest.
    idxs, oks, _ = stack.solve_group(tg, 3, group_count=300)
    assert oks.all() and len(set(idxs.tolist())) == 3
    assert buckets() == after
    now = dict(GLOBAL_SOLVER.paths)
    assert now.get("exact", 0) - paths.get("exact", 0) == 1
    assert sum(now.values()) - sum(paths.values()) == 2
