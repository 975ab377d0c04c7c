"""Randomized differential fuzz: the TPU dense solve vs the host oracle.

The highest-value test for a solver with interchangeable kernels whose
equivalence is otherwise argued in comments (ops/binpack.py): hundreds of
random clusters/jobs/existing-alloc states, asserting

1. kernel agreement — ``solve_rounds_fused`` (direct round simulation) and
   ``solve_waterfill`` (closed form) produce identical per-node counts, and
   ``solve_greedy`` places the same total;
2. scheduler agreement — the ``tpu-*`` factories place exactly as many
   allocations as the host oracle (the ported iterator chain, the
   reference's correctness contract: /root/reference/scheduler/
   generic_sched_test.go, rank_test.go, feasible_test.go);
3. plan soundness — every committed placement lands on an eligible node
   and no node exceeds capacity (structs.allocs_fit, funcs.go:44-87).

Seed count tunable via NOMAD_TPU_FUZZ_SEEDS (default keeps the suite
fast; failures print the seed for replay).
"""

import os

import jax.numpy as jnp
import numpy as np
import pytest

from nomad_tpu import mock, structs
from nomad_tpu.network import NetworkIndex
from nomad_tpu.structs import (
    Constraint,
    Evaluation,
    Job,
    NetworkResource,
    Node,
    Resources,
    RestartPolicy,
    Task,
    TaskGroup,
    generate_uuid,
)

from sched_harness import Harness

N_KERNEL_SEEDS = int(os.environ.get("NOMAD_TPU_FUZZ_SEEDS", 60))
N_SCHED_SEEDS = int(os.environ.get("NOMAD_TPU_FUZZ_SEEDS", 60))


# ---------------------------------------------------------------------------
# 1. Kernel-level agreement


def _random_solve_inputs(rng):
    n = int(rng.choice([8, 16, 32, 64, 128]))
    total = np.zeros((n, 4), dtype=np.int32)
    total[:, 0] = rng.integers(200, 8000, n)      # cpu: some nodes tiny
    total[:, 1] = rng.integers(128, 16384, n)     # mem
    total[:, 2] = rng.integers(1024, 200_000, n)  # disk
    total[:, 3] = rng.integers(10, 300, n)        # iops
    used = np.zeros((n, 4), dtype=np.int32)
    if rng.random() < 0.5:  # existing utilization, possibly near-full
        frac = rng.random((n, 1)) * rng.choice([0.5, 0.95])
        used = (total * frac).astype(np.int32)
    job_count = rng.integers(0, 3, n).astype(np.int32) * (rng.random() < 0.4)
    tg_count = np.minimum(job_count, rng.integers(0, 2, n)).astype(np.int32)
    bw_avail = rng.integers(100, 2000, n).astype(np.int32)
    bw_used = (bw_avail * rng.random(n) * 0.8).astype(np.int32) * (
        rng.random() < 0.5
    )
    eligible = rng.random(n) > rng.choice([0.0, 0.3, 0.9])
    ask = np.array([
        int(rng.integers(1, 1500)), int(rng.integers(1, 2048)),
        int(rng.integers(0, 2000)), int(rng.integers(0, 50)),
    ], dtype=np.int32)
    bw_ask = int(rng.integers(0, 200)) if rng.random() < 0.5 else 0
    count = int(rng.integers(1, 800))
    penalty = float(rng.choice([5.0, 10.0]))
    jd = bool(rng.random() < 0.15)
    td = bool(rng.random() < 0.15 and not jd)
    return dict(
        total=total, used=used, job_count=job_count, tg_count=tg_count,
        bw_avail=bw_avail, bw_used=bw_used, eligible=eligible, ask=ask,
        bw_ask=bw_ask, count=count, penalty=penalty, jd=jd, td=td,
    )


@pytest.mark.parametrize("seed", range(N_KERNEL_SEEDS))
def test_kernel_threeway_agreement(seed):
    """waterfill == rounds_fused exactly; greedy places the same total and
    respects the same per-node capacity."""
    from nomad_tpu.ops.binpack import (
        bucket,
        solve_greedy,
        solve_rounds_fused,
        solve_waterfill,
    )

    rng = np.random.default_rng(10_000 + seed)
    s = _random_solve_inputs(rng)
    sched_cap = s["total"][:, :2].astype(np.float32)
    args = (
        jnp.asarray(s["total"]), jnp.asarray(sched_cap),
        jnp.asarray(s["used"]), jnp.asarray(s["job_count"]),
        jnp.asarray(s["tg_count"]), jnp.asarray(s["bw_avail"]),
        jnp.asarray(s["bw_used"]), jnp.asarray(s["eligible"]),
        jnp.asarray(s["ask"]), jnp.int32(s["bw_ask"]),
    )
    wf_counts, wf_left = solve_waterfill(
        *args, jnp.int32(s["count"]), jnp.float32(s["penalty"]),
        s["jd"], s["td"],
    )
    rf_counts, rf_left = solve_rounds_fused(
        *args, jnp.int32(s["count"]), jnp.float32(s["penalty"]),
        s["jd"], s["td"],
    )
    wf_counts = np.asarray(wf_counts)
    np.testing.assert_array_equal(
        wf_counts, np.asarray(rf_counts),
        err_msg=f"waterfill != rounds_fused (seed {seed})",
    )
    assert int(wf_left) == int(rf_left), seed

    # Greedy scan (capped k for runtime): same placement total over the
    # same prefix.
    k_cap = min(s["count"], 64)
    k = bucket(k_cap)
    active = jnp.arange(k) < k_cap
    _idxs, oks, _ = solve_greedy(
        *args, active, jnp.float32(s["penalty"]), k, s["jd"], s["td"],
    )
    greedy_placed = int(np.asarray(oks).sum())
    # Both must saturate: greedy places min(k_cap, capacity); water-fill's
    # total is min(count, capacity) with k_cap <= count.
    capacity_reached = int(wf_counts.sum())
    assert greedy_placed == min(k_cap, capacity_reached), (
        seed, greedy_placed, capacity_reached,
    )

    # Soundness: counts never exceed per-ask capacity on any node.
    avail = s["total"] - s["used"]
    for i in range(len(wf_counts)):
        c = int(wf_counts[i])
        if c == 0:
            continue
        assert s["eligible"][i], (seed, i)
        assert np.all(s["ask"] * c <= avail[i]), (seed, i)
        if s["bw_ask"] > 0:
            assert s["bw_used"][i] + c * s["bw_ask"] <= s["bw_avail"][i]
        if s["jd"]:
            assert c <= 1 and s["job_count"][i] == 0
        if s["td"]:
            assert c <= 1 and s["tg_count"][i] == 0


# ---------------------------------------------------------------------------
# 2. Scheduler-level differential: tpu-* vs host oracle


def _random_cluster(rng, n):
    nodes = []
    for i in range(n):
        res = Resources(
            cpu=int(rng.integers(500, 8000)),
            memory_mb=int(rng.integers(512, 16384)),
            disk_mb=int(rng.integers(10_000, 200_000)),
            iops=int(rng.integers(50, 300)),
            networks=[NetworkResource(
                device="eth0", cidr="192.168.0.0/16", ip=f"192.168.{i%250}.1",
                mbits=int(rng.integers(100, 1001)),
            )],
        )
        node = Node(
            id=f"{seeded_hex(rng)}",
            datacenter="dc1" if rng.random() < 0.7 else "dc2",
            name=f"node-{i}",
            attributes={
                "kernel.name": "linux" if rng.random() < 0.8 else "darwin",
                "arch": "amd64",
                "driver.exec": "1",
                "driver.docker": "1" if rng.random() < 0.6 else "0",
            },
            resources=res,
            status=structs.NODE_STATUS_READY,
        )
        nodes.append(node)
    return nodes


def seeded_hex(rng):
    return "".join(rng.choice(list("0123456789abcdef"), 32))


def _random_job(rng):
    jtype = str(rng.choice([structs.JOB_TYPE_SERVICE, structs.JOB_TYPE_BATCH]))
    constraints = []
    if rng.random() < 0.5:
        constraints.append(Constraint(
            l_target="$attr.kernel.name", r_target="linux", operand="=",
        ))
    if rng.random() < 0.2:
        constraints.append(Constraint(operand="distinct_hosts"))
    task_res = Resources(
        cpu=int(rng.integers(20, 1200)),
        memory_mb=int(rng.integers(16, 2048)),
    )
    if rng.random() < 0.4:
        task_res.networks = [
            NetworkResource(mbits=int(rng.integers(1, 120)))
        ]
    count = int(rng.choice([1, 3, 17, 60, 140, 300]))
    job = Job(
        region="global",
        id=generate_uuid(),
        name="fuzz",
        type=jtype,
        priority=50,
        datacenters=["dc1"] if rng.random() < 0.5 else ["dc1", "dc2"],
        constraints=constraints,
        task_groups=[TaskGroup(
            name="tg",
            count=count,
            restart_policy=RestartPolicy(
                attempts=1, interval=600.0, delay=5.0
            ),
            tasks=[Task(name="t", driver="exec", resources=task_res)],
        )],
    )
    return job


def _run_eval(factory, nodes, job, trigger=structs.EVAL_TRIGGER_JOB_REGISTER,
              harness=None):
    h = harness or Harness()
    if harness is None:
        for node in nodes:
            h.state.upsert_node(h.next_index(), node)
        h.state.upsert_job(h.next_index(), job)
    ev = Evaluation(
        id=generate_uuid(), priority=job.priority, type=job.type,
        triggered_by=trigger, job_id=job.id,
    )
    h.process(factory, ev)
    return h


def _placed_and_failed(h):
    placed = 0
    for plan in h.plans:
        placed += sum(len(v) for v in plan.node_allocation.values())
        placed += sum(b.n for b in plan.alloc_batches)
    failed = sum(
        (a.metrics.coalesced_failures + 1 if a.metrics else 1)
        for plan in h.plans for a in plan.failed_allocs
    )
    return placed, failed


def _check_capacity(h, nodes):
    """No committed plan may overcommit any node (funcs.go:44-87)."""
    by_id = {n.id: n for n in nodes}
    for node in nodes:
        allocs = [
            a for a in h.state.allocs_by_node(node.id)
            if a.desired_status == structs.ALLOC_DESIRED_STATUS_RUN
        ]
        if not allocs:
            continue
        idx = NetworkIndex()
        idx.set_node(node)
        fit, dim, _used = structs.allocs_fit(node, allocs, idx)
        assert fit, (node.id, dim, len(allocs))
    # And every placement names a real node
    for plan in h.plans:
        for nid in plan.node_allocation:
            assert nid in by_id
        for b in plan.alloc_batches:
            for nid in b.node_ids:
                assert nid in by_id


@pytest.mark.parametrize("seed", range(N_SCHED_SEEDS))
def test_scheduler_differential_fresh_registration(seed):
    """Fresh job registration on a random cluster: the dense solve places
    exactly as many as the host oracle, soundly."""
    master = np.random.default_rng(20_000 + seed)
    n = int(master.integers(1, 60)) if seed % 10 else int(
        master.integers(100, 201)
    )
    results = {}
    for factory_kind in ("host", "tpu"):
        rng = np.random.default_rng(20_000 + seed)  # identical stream
        _ = rng.integers(1, 60) if seed % 10 else rng.integers(100, 201)
        nodes = _random_cluster(rng, n)
        job = _random_job(rng)
        factory = job.type if factory_kind == "host" else f"tpu-{job.type}"
        h = _run_eval(factory, nodes, job)
        placed, failed = _placed_and_failed(h)
        _check_capacity(h, nodes)
        results[factory_kind] = (placed, failed, job.task_groups[0].count)

    (hp, hf, count) = results["host"]
    (tp, tf, _) = results["tpu"]
    assert hp + hf == count
    assert tp + tf == count
    assert tp == hp, (
        f"seed {seed}: tpu placed {tp}, host placed {hp} (count {count})"
    )


@pytest.mark.parametrize("seed", range(0, N_SCHED_SEEDS, 3))
def test_scheduler_differential_rolling_update(seed):
    """Phase 2: mutate the job (resources bump -> destructive update) and
    re-evaluate against existing allocs; the dense solve matches the host
    oracle's placement count through the diff/evict path."""
    results = {}
    for factory_kind in ("host", "tpu"):
        rng = np.random.default_rng(30_000 + seed)
        n = int(rng.integers(2, 40))
        nodes = _random_cluster(rng, n)
        job = _random_job(rng)
        job.task_groups[0].count = min(job.task_groups[0].count, 60)
        factory = job.type if factory_kind == "host" else f"tpu-{job.type}"
        h = _run_eval(factory, nodes, job)

        # Mutate: resource bump forces destructive updates; count change
        # exercises place/stop.
        job2 = job  # same object graph is fine: store holds it by id
        if rng.random() < 0.5:
            job2.task_groups[0].tasks[0].resources.cpu += 17
        else:
            job2.task_groups[0].count = max(
                1, job2.task_groups[0].count + int(rng.integers(-20, 21))
            )
        h.state.upsert_job(h.next_index(), job2)
        ev = Evaluation(
            id=generate_uuid(), priority=job2.priority, type=job2.type,
            triggered_by=structs.EVAL_TRIGGER_JOB_REGISTER, job_id=job2.id,
        )
        h.process(factory, ev)
        _check_capacity(h, nodes)
        final = [
            a for a in h.state.allocs_by_job(job2.id)
            if a.desired_status == structs.ALLOC_DESIRED_STATUS_RUN
        ]
        results[factory_kind] = len(final)

    assert results["tpu"] == results["host"], f"seed {seed}: {results}"


# ---------------------------------------------------------------------------
# 2b. Placement QUALITY: dense global argmax vs power-of-two-choices
# ---------------------------------------------------------------------------


N_QUALITY_SEEDS = int(os.environ.get("NOMAD_TPU_QUALITY_SEEDS", 110))


def _aggregate_fitness(h, nodes):
    """Aggregate BestFit-v3 quality of a committed placement: each RUN
    alloc scores its node's FINAL utilization (structs.score_fit, the
    same kernel the device solve maximizes — funcs.go:89-124), weighted
    by the allocs packed there. Higher = tighter packing."""
    from nomad_tpu.structs import score_fit

    total = 0.0
    for node in nodes:
        live = [
            a for a in h.state.allocs_by_node(node.id)
            if a.desired_status == structs.ALLOC_DESIRED_STATUS_RUN
        ]
        if not live:
            continue
        util = Resources(
            cpu=sum(a.resources.cpu for a in live),
            memory_mb=sum(a.resources.memory_mb for a in live),
        )
        total += len(live) * score_fit(node, util)
    return total


def test_scheduler_quality_tpu_at_least_host():
    """The tpu/solver.py design claim, asserted instead of argued: the
    host GenericStack ranks only a random ~log2(n) subset of feasible
    nodes (power-of-two-choices, stack.go:94-121) while the dense solve
    scores every node, "so placement quality is >= host". Aggregated
    across >= 100 seeded random clusters on identical state, the TPU
    factories' aggregate score_fit must be at least the host oracle's
    (both greedy, so any single seed can wobble either way — the
    aggregate is the claim; gross per-seed regressions are also caught).
    """
    totals = {"host": 0.0, "tpu": 0.0}
    per_seed = []
    for seed in range(N_QUALITY_SEEDS):
        scores = {}
        for factory_kind in ("host", "tpu"):
            rng = np.random.default_rng(80_000 + seed)  # identical stream
            n = int(rng.integers(4, 40))
            nodes = _random_cluster(rng, n)
            job = _random_job(rng)
            # Network-free: port assignment is a host post-pass on BOTH
            # paths and only adds runtime, not quality signal.
            job.task_groups[0].tasks[0].resources.networks = []
            job.task_groups[0].count = min(job.task_groups[0].count, 80)
            factory = job.type if factory_kind == "host" else f"tpu-{job.type}"
            h = _run_eval(factory, nodes, job)
            placed, _ = _placed_and_failed(h)
            scores[factory_kind] = (_aggregate_fitness(h, nodes), placed)
        # Quality is only comparable on equal placement counts (count
        # parity is its own differential above).
        assert scores["tpu"][1] == scores["host"][1], (seed, scores)
        totals["host"] += scores["host"][0]
        totals["tpu"] += scores["tpu"][0]
        per_seed.append((seed, scores["tpu"][0], scores["host"][0]))

    assert totals["tpu"] >= totals["host"] * (1.0 - 1e-9), (
        f"aggregate quality regression: tpu {totals['tpu']:.1f} < "
        f"host {totals['host']:.1f} over {N_QUALITY_SEEDS} seeds; worst "
        f"seeds: {sorted(per_seed, key=lambda s: s[1] - s[2])[:5]}"
    )
    # No catastrophic single-seed loss hiding inside a winning aggregate:
    # flag any seed where tpu scores under half the host packing.
    bad = [s for s in per_seed if s[1] < 0.5 * s[2] - 1e-9]
    assert not bad, f"gross per-seed quality loss: {bad[:5]}"


# ---------------------------------------------------------------------------
# 3. System-scheduler differential: tpu-system vs host oracle


def _random_system_job(rng):
    constraints = []
    if rng.random() < 0.6:
        constraints.append(Constraint(
            l_target="$attr.kernel.name", r_target="linux", operand="=",
        ))
    if rng.random() < 0.3:
        constraints.append(Constraint(
            l_target="$attr.driver.docker", r_target="1", operand="=",
        ))
    task_res = Resources(
        cpu=int(rng.integers(20, 1500)),
        memory_mb=int(rng.integers(16, 4096)),
    )
    if rng.random() < 0.3:
        task_res.networks = [NetworkResource(mbits=int(rng.integers(1, 200)))]
    return Job(
        region="global",
        id=generate_uuid(),
        name="fuzz-sys",
        type=structs.JOB_TYPE_SYSTEM,
        priority=50,
        datacenters=["dc1"] if rng.random() < 0.5 else ["dc1", "dc2"],
        constraints=constraints,
        task_groups=[TaskGroup(
            name="sys",
            count=1,
            restart_policy=RestartPolicy(attempts=1, interval=600.0, delay=5.0),
            tasks=[Task(name="t", driver="exec", resources=task_res)],
        )],
    )


@pytest.mark.parametrize("seed", range(0, N_SCHED_SEEDS, 2))
def test_scheduler_differential_system(seed):
    """System (one-alloc-per-node) jobs: tpu-system must place on exactly
    the same number of nodes as the host oracle, never more than one per
    node (reference oracle: scheduler/system_sched_test.go)."""
    results = {}
    for factory_kind in ("host", "tpu"):
        rng = np.random.default_rng(40_000 + seed)
        n = int(rng.integers(1, 80))
        nodes = _random_cluster(rng, n)
        job = _random_system_job(rng)
        factory = "system" if factory_kind == "host" else "tpu-system"
        h = _run_eval(factory, nodes, job)
        placed, _failed = _placed_and_failed(h)
        _check_capacity(h, nodes)
        # One-per-node invariant.
        per_node = {}
        for node in nodes:
            live = [
                a for a in h.state.allocs_by_node(node.id)
                if a.desired_status == structs.ALLOC_DESIRED_STATUS_RUN
            ]
            assert len(live) <= 1, (seed, node.id, len(live))
            per_node[node.id] = len(live)
        results[factory_kind] = (placed, sum(per_node.values()))

    assert results["tpu"] == results["host"], f"seed {seed}: {results}"


# ---------------------------------------------------------------------------
# 4. Port-bearing groups at scale (the small-path routing's parity contract)


def _random_port_job(rng, count):
    """Network asks with reserved AND dynamic ports — the inherently
    sequential assignment the device path routes host-side
    (network.go:136-194); parity must survive count > BATCH threshold."""
    net = NetworkResource(mbits=int(rng.integers(1, 80)))
    if rng.random() < 0.6:
        net.reserved_ports = [int(rng.integers(20000, 20004))]
    if rng.random() < 0.6:
        net.dynamic_ports = ["http"]
    if not net.reserved_ports and not net.dynamic_ports:
        net.reserved_ports = [20001]
    task_res = Resources(
        cpu=int(rng.integers(20, 400)),
        memory_mb=int(rng.integers(16, 512)),
        networks=[net],
    )
    return Job(
        region="global",
        id=generate_uuid(),
        name="fuzz-ports",
        type=str(rng.choice([structs.JOB_TYPE_SERVICE, structs.JOB_TYPE_BATCH])),
        priority=50,
        datacenters=["dc1", "dc2"],
        task_groups=[TaskGroup(
            name="web",
            count=count,
            restart_policy=RestartPolicy(attempts=1, interval=600.0, delay=5.0),
            tasks=[Task(name="t", driver="exec", resources=task_res)],
        )],
    )


@pytest.mark.parametrize("seed", range(0, N_SCHED_SEEDS, 4))
def test_scheduler_differential_ports_at_scale(seed):
    """count > 128 (the batched-path threshold) with reserved/dynamic port
    asks: the device factories route these through the sequential network
    offer, and the placement count must still match the host oracle —
    with no port collisions in committed state (allocs_fit port check)."""
    results = {}
    for factory_kind in ("host", "tpu"):
        rng = np.random.default_rng(50_000 + seed)
        n = int(rng.integers(40, 140))
        count = int(rng.integers(129, 300))
        nodes = _random_cluster(rng, n)
        job = _random_port_job(rng, count)
        factory = job.type if factory_kind == "host" else f"tpu-{job.type}"
        h = _run_eval(factory, nodes, job)
        placed, failed = _placed_and_failed(h)
        _check_capacity(h, nodes)  # includes NetworkIndex port collisions
        assert placed + failed == count, (seed, placed, failed, count)
        # Offered networks must never reuse a (ip, reserved port) pair on a
        # node — the same port on DIFFERENT IPs of the CIDR is legal
        # (AssignNetwork yields per-IP, network.go:136-194).
        for node in nodes:
            seen = set()
            for a in h.state.allocs_by_node(node.id):
                if a.desired_status != structs.ALLOC_DESIRED_STATUS_RUN:
                    continue
                for tr in a.task_resources.values():
                    for net in tr.networks:
                        for port in net.reserved_ports:
                            key = (net.ip, port)
                            assert key not in seen, (seed, node.id, key)
                            seen.add(key)
        results[factory_kind] = placed

    assert results["tpu"] == results["host"], f"seed {seed}: {results}"


# ---------------------------------------------------------------------------
# 5. Concurrent coalesced evals racing plan-apply (optimistic concurrency)


@pytest.mark.parametrize("seed", range(4))
def test_concurrent_coalesced_race_no_overcommit(seed):
    """Several jobs whose combined ask EXCEEDS cluster capacity are solved
    concurrently (broker batch -> coalesced dispatch) against the same
    snapshot; plan-apply's serialized verification must reject the
    overflow: post-commit, no node is overcommitted and total placements
    never exceed capacity (nomad/plan_apply.go:167-277 posture)."""
    import time as _time

    from nomad_tpu.server import Server, ServerConfig

    rng = np.random.default_rng(60_000 + seed)
    n_nodes = 8
    per_node_cap = 4  # 4 tasks of 1000cpu on a 4000cpu node
    capacity = n_nodes * per_node_cap
    n_jobs = 4
    # Each job alone fits; together they ask for 2x capacity.
    per_job = capacity * 2 // n_jobs

    srv = Server(ServerConfig(
        scheduler_backend="tpu", num_schedulers=2, eval_batch_size=n_jobs,
        periodic_dispatch=False, prewarm_shapes=False,
    ))
    try:
        nodes = []
        for i in range(n_nodes):
            node = Node(
                id=f"race-{seed}-{i}",
                datacenter="dc1",
                name=f"n{i}",
                attributes={"kernel.name": "linux", "driver.exec": "1"},
                resources=Resources(
                    cpu=4000, memory_mb=16384, disk_mb=100_000, iops=1000,
                ),
                status=structs.NODE_STATUS_READY,
            )
            srv.raft.apply("node_register", {"node": node})
            nodes.append(node)
        evals = []
        for j in range(n_jobs):
            job = Job(
                region="global", id=generate_uuid(), name=f"race-{j}",
                type=structs.JOB_TYPE_BATCH, priority=50,
                datacenters=["dc1"],
                task_groups=[TaskGroup(
                    name="work", count=per_job,
                    restart_policy=RestartPolicy(
                        attempts=0, interval=600.0, delay=1.0,
                    ),
                    tasks=[Task(name="t", driver="exec",
                                resources=Resources(cpu=1000, memory_mb=64))],
                )],
            )
            srv.raft.apply("job_register", {"job": job})
            evals.append(Evaluation(
                id=generate_uuid(), priority=50, type=job.type,
                triggered_by=structs.EVAL_TRIGGER_JOB_REGISTER,
                job_id=job.id, status=structs.EVAL_STATUS_PENDING,
            ))
        srv.start()
        # One batch: all evals land at once and race through plan-apply.
        srv.raft.apply("eval_update", {"evals": evals})
        deadline = _time.monotonic() + 90
        while _time.monotonic() < deadline:
            done = [srv.state_store.eval_by_id(e.id) for e in evals]
            if all(d is not None
                   and d.status != structs.EVAL_STATUS_PENDING for d in done):
                break
            _time.sleep(0.02)
        else:
            raise AssertionError("evals did not finish")

        total_live = 0
        for node in nodes:
            live = [
                a for a in srv.state_store.allocs_by_node(node.id)
                if a.desired_status == structs.ALLOC_DESIRED_STATUS_RUN
            ]
            total_live += len(live)
            fit, dim, _ = structs.allocs_fit(node, live)
            assert fit, (seed, node.id, dim, len(live))
            assert len(live) <= per_node_cap
        assert total_live <= capacity
        # The winners actually landed: the race must not starve everyone.
        assert total_live > 0
    finally:
        srv.shutdown()


# ---------------------------------------------------------------------------
# 2c. Anti-affinity penalty accounting parity (VERDICT r5 item 5b)
# ---------------------------------------------------------------------------


def _collision_penalty(h, nodes, job):
    """Total anti-affinity penalty the committed placement incurred:
    placing the k-th alloc of a job on a node already holding j of them
    costs j*p (rank.go:240-302), so a node ending with k allocs paid
    p * k*(k-1)/2."""
    from nomad_tpu.scheduler.stack import (
        BATCH_JOB_ANTI_AFFINITY_PENALTY,
        SERVICE_JOB_ANTI_AFFINITY_PENALTY,
    )

    p = (BATCH_JOB_ANTI_AFFINITY_PENALTY
         if job.type == structs.JOB_TYPE_BATCH
         else SERVICE_JOB_ANTI_AFFINITY_PENALTY)
    total = 0.0
    for node in nodes:
        k = sum(
            1 for a in h.state.allocs_by_node(node.id)
            if a.desired_status == structs.ALLOC_DESIRED_STATUS_RUN
            and a.job_id == job.id
        )
        total += p * k * (k - 1) / 2.0
    return total


def _roomy_nodes(n):
    """Identical, roomy nodes: collisions are capacity-feasible, so the
    only force spreading placements is the anti-affinity penalty — a path
    that ignored it would BestFit-stack onto few nodes."""
    from nomad_tpu.structs import Node, Resources

    return [
        Node(
            id=f"aff-{i:03d}", datacenter="dc1", name=f"n{i}",
            attributes={"kernel.name": "linux", "driver.exec": "1"},
            resources=Resources(cpu=16000, memory_mb=32768,
                                disk_mb=500_000, iops=10_000),
            status=structs.NODE_STATUS_READY,
        )
        for i in range(n)
    ]


@pytest.mark.parametrize("seed", range(0, N_SCHED_SEEDS, 2))
def test_scheduler_differential_anti_affinity_penalty(seed):
    """Forced co-placement (count a multiple of the node count, ample
    capacity): the device solve's in-kernel penalty term must account
    collisions like the host's JobAntiAffinityIterator. Asserted on the
    committed state: equal placement counts, TPU total collision penalty
    <= host's (the dense solve scores every node; the host samples
    ~log2(n)), and — on identical nodes, where even spread is the unique
    penalty-optimal shape — a perfectly balanced per-node distribution."""
    results = {}
    for factory_kind in ("host", "tpu"):
        rng = np.random.default_rng(90_000 + seed)
        n = int(rng.integers(3, 12))
        per_node = int(rng.integers(2, 5))
        count = n * per_node
        jtype = str(rng.choice(
            [structs.JOB_TYPE_SERVICE, structs.JOB_TYPE_BATCH]
        ))
        nodes = _roomy_nodes(n)
        job = Job(
            region="global", id=generate_uuid(), name="fuzz-aff",
            type=jtype, priority=50, datacenters=["dc1"],
            task_groups=[TaskGroup(
                name="tg", count=count,
                restart_policy=RestartPolicy(
                    attempts=1, interval=600.0, delay=5.0,
                ),
                tasks=[Task(name="t", driver="exec",
                            resources=Resources(cpu=100, memory_mb=64))],
            )],
        )
        factory = job.type if factory_kind == "host" else f"tpu-{job.type}"
        h = _run_eval(factory, nodes, job)
        placed, failed = _placed_and_failed(h)
        assert placed == count and failed == 0, (seed, factory_kind, placed)
        _check_capacity(h, nodes)
        per_node_counts = sorted(
            sum(1 for a in h.state.allocs_by_node(node.id)
                if a.desired_status == structs.ALLOC_DESIRED_STATUS_RUN)
            for node in nodes
        )
        results[factory_kind] = (
            _collision_penalty(h, nodes, job), per_node_counts,
        )

    host_pen, host_dist = results["host"]
    tpu_pen, tpu_dist = results["tpu"]
    # Identical nodes: even spread is penalty-optimal and both greedy
    # paths must find it — any stacking means the penalty was dropped.
    assert tpu_dist[0] == tpu_dist[-1] == per_node, (seed, tpu_dist)
    assert host_dist[0] == host_dist[-1] == per_node, (seed, host_dist)
    assert tpu_pen <= host_pen + 1e-9, (seed, tpu_pen, host_pen)


# ---------------------------------------------------------------------------
# 2d. Rolling-update / in-place identity parity (VERDICT r5 item 5c)
# ---------------------------------------------------------------------------


def _run_ids(h, job):
    return sorted(
        a.id for a in h.state.allocs_by_job(job.id)
        if a.desired_status == structs.ALLOC_DESIRED_STATUS_RUN
    )


def _identity_phases(factory_kind, seed, count):
    """Place -> resource-only bump (in-place) -> env change (destructive);
    returns the three RUN-alloc id sets plus the job modify indexes the
    final allocs carry."""
    import copy

    rng = np.random.default_rng(95_000 + seed)
    n = max(4, count // 2)
    nodes = _roomy_nodes(n)
    job = Job(
        region="global", id=generate_uuid(), name="fuzz-ident",
        type=structs.JOB_TYPE_SERVICE, priority=50, datacenters=["dc1"],
        task_groups=[TaskGroup(
            name="web", count=count,
            restart_policy=RestartPolicy(
                attempts=1, interval=600.0, delay=5.0,
            ),
            tasks=[Task(name="t", driver="exec",
                        resources=Resources(
                            cpu=int(rng.integers(50, 200)),
                            memory_mb=64,
                        ))],
        )],
    )
    factory = job.type if factory_kind == "host" else f"tpu-{job.type}"
    h = _run_eval(factory, nodes, job)
    ids0 = _run_ids(h, job)
    assert len(ids0) == count, (seed, factory_kind, len(ids0))

    # Phase 2: cpu+1 — tasks_updated() false, every node has headroom:
    # the in-place path MUST keep every alloc id (util.go:316-398; the
    # block path commits a field swap, state/blocks.py with_update).
    # Deep copy: existing allocs embed the job object, and mutating it in
    # place would make the diff see no modify_index change at all.
    job2 = copy.deepcopy(job)
    job2.task_groups[0].tasks[0].resources.cpu += 1
    h.state.upsert_job(h.next_index(), job2)
    ev = Evaluation(
        id=generate_uuid(), priority=job2.priority, type=job2.type,
        triggered_by=structs.EVAL_TRIGGER_JOB_REGISTER, job_id=job2.id,
    )
    h.process(factory, ev)
    ids1 = _run_ids(h, job2)
    inplace_mod = {
        a.job.modify_index
        for a in h.state.allocs_by_job(job2.id)
        if a.desired_status == structs.ALLOC_DESIRED_STATUS_RUN
    }

    # Phase 3: env change — destructive; every alloc must be REPLACED.
    job3 = copy.deepcopy(job2)
    job3.task_groups[0].tasks[0].env = {"V": "2"}
    h.state.upsert_job(h.next_index(), job3)
    ev = Evaluation(
        id=generate_uuid(), priority=job3.priority, type=job3.type,
        triggered_by=structs.EVAL_TRIGGER_JOB_REGISTER, job_id=job3.id,
    )
    h.process(factory, ev)
    ids2 = _run_ids(h, job3)
    _check_capacity(h, nodes)
    return ids0, ids1, ids2, inplace_mod, job2.modify_index


@pytest.mark.parametrize("seed", range(0, N_SCHED_SEEDS, 3))
def test_scheduler_differential_inplace_identity(seed):
    """Resource-only bump with guaranteed headroom: BOTH factories must
    update the same allocs in place (identical id sets before/after, job
    version advanced) — and an env change must replace every id. The
    object-diff path (count < 256)."""
    for factory_kind in ("host", "tpu"):
        ids0, ids1, ids2, mods, job2_idx = _identity_phases(
            factory_kind, seed, count=int(
                np.random.default_rng(95_000 + seed).integers(5, 40)
            ),
        )
        assert ids1 == ids0, (seed, factory_kind, "in-place changed ids")
        assert mods == {job2_idx}, (seed, factory_kind, mods)
        assert len(ids2) == len(ids0), (seed, factory_kind)
        assert not set(ids2) & set(ids0), (
            seed, factory_kind, "destructive update kept old ids"
        )


def test_scheduler_inplace_identity_block_native():
    """Same contract at columnar scale (count >= 256): the TPU path's
    block-native in-place machinery (whole-block field swap, no member
    materialization) must preserve the seed-derived id column exactly,
    and the host oracle agrees on every phase's cardinality."""
    out = {}
    for factory_kind in ("host", "tpu"):
        ids0, ids1, ids2, mods, job2_idx = _identity_phases(
            factory_kind, seed=1, count=300,
        )
        assert ids1 == ids0, (factory_kind, "in-place changed ids")
        assert mods == {job2_idx}, (factory_kind, mods)
        assert not set(ids2) & set(ids0), (factory_kind,)
        out[factory_kind] = (len(ids0), len(ids1), len(ids2))
    assert out["tpu"] == out["host"] == (300, 300, 300)


@pytest.mark.parametrize(
    "seed", range(int(os.environ.get("NOMAD_TPU_BURST_SEEDS", "6")))
)
def test_burst_mix_matches_serial(seed):
    """Differential for the announced-burst machinery (enqueue_many +
    hint_burst + generation-scoped accounting): a random mix of jobs —
    columnar-scale counts, exact-path small counts, and system jobs —
    lands once as ONE broker burst and once serially. Both modes must
    complete every eval, place every asked task (total ask fits), and
    leave every node within capacity; burst members that never reach the
    coalescer (exact path) must resolve the hold, not stall it."""
    import time as _time

    from nomad_tpu.server import Server, ServerConfig

    rng = np.random.default_rng(70_000 + seed)
    n_nodes = 16
    asks = []
    for _ in range(int(rng.integers(3, 7))):
        kind = rng.choice(["columnar", "exact", "system", "equiv"])
        if kind == "columnar":
            count = int(rng.integers(129, 400))
        elif kind == "exact":
            count = int(rng.integers(1, 129))
        elif kind == "equiv":
            # 2-3 identical columnar task groups in ONE job: the
            # equivalence-class collapse rides the burst too.
            count = int(rng.integers(2, 4)) * 256
        else:
            count = None  # one per node
        asks.append((kind, count))
    # Small per-task ask so the whole mix always fits: worst case
    # 6 jobs * max(399, 768) tasks * 10cpu <= 16 nodes * 4000 cpu.
    expected = sum(
        (n_nodes if kind == "system" else count) for kind, count in asks
    )

    def run_mode(batch_size):
        srv = Server(ServerConfig(
            scheduler_backend="tpu", num_schedulers=2,
            eval_batch_size=batch_size, periodic_dispatch=False,
            prewarm_shapes=False,
        ))
        try:
            nodes = []
            for i in range(n_nodes):
                node = Node(
                    id=f"bm-{seed}-{i}", datacenter="dc1", name=f"n{i}",
                    attributes={"kernel.name": "linux", "driver.exec": "1"},
                    resources=Resources(cpu=4000, memory_mb=16384,
                                        disk_mb=100_000, iops=1000),
                    status=structs.NODE_STATUS_READY,
                )
                srv.raft.apply("node_register", {"node": node})
                nodes.append(node)
            jobs, evals = [], []
            for j, (kind, count) in enumerate(asks):
                if kind == "equiv":
                    tgs = [
                        TaskGroup(
                            name=f"work{m}", count=256,
                            restart_policy=RestartPolicy(
                                attempts=0, interval=600.0, delay=1.0,
                            ),
                            tasks=[Task(
                                name="t", driver="exec",
                                resources=Resources(cpu=10,
                                                    memory_mb=16))],
                        )
                        for m in range(count // 256)
                    ]
                else:
                    tgs = [TaskGroup(
                        name="work", count=1 if kind == "system" else count,
                        restart_policy=RestartPolicy(
                            attempts=0, interval=600.0, delay=1.0,
                        ),
                        tasks=[Task(
                            name="t", driver="exec",
                            resources=Resources(cpu=10, memory_mb=16))],
                    )]
                job = Job(
                    region="global", id=generate_uuid(),
                    name=f"bm-{j}-{kind}",
                    type=(structs.JOB_TYPE_SYSTEM if kind == "system"
                          else structs.JOB_TYPE_BATCH),
                    priority=50, datacenters=["dc1"], task_groups=tgs,
                )
                srv.raft.apply("job_register", {"job": job})
                jobs.append(job)
                evals.append(Evaluation(
                    id=generate_uuid(), priority=50, type=job.type,
                    triggered_by=structs.EVAL_TRIGGER_JOB_REGISTER,
                    job_id=job.id, status=structs.EVAL_STATUS_PENDING,
                ))
            srv.start()
            if batch_size > 1:
                srv.raft.apply("eval_update", {"evals": evals})
            else:
                for ev in evals:
                    srv.raft.apply("eval_update", {"evals": [ev]})
            deadline = _time.monotonic() + 120
            while _time.monotonic() < deadline:
                done = [srv.state_store.eval_by_id(e.id) for e in evals]
                if all(d is not None and d.status not in
                       (structs.EVAL_STATUS_PENDING,) for d in done):
                    break
                _time.sleep(0.02)
            else:
                raise AssertionError((seed, batch_size, "evals stuck"))
            statuses = {srv.state_store.eval_by_id(e.id).status
                        for e in evals}
            assert statuses == {structs.EVAL_STATUS_COMPLETE}, (
                seed, batch_size, statuses)
            placed = {}
            for job in jobs:
                placed[job.name] = sum(
                    1 for a in srv.state_store.allocs_by_job(job.id)
                    if a.desired_status == structs.ALLOC_DESIRED_STATUS_RUN
                )
            for node in nodes:
                live = [
                    a for a in srv.state_store.allocs_by_node(node.id)
                    if a.desired_status == structs.ALLOC_DESIRED_STATUS_RUN
                ]
                fit, dim, _ = structs.allocs_fit(node, live)
                assert fit, (seed, batch_size, node.id, dim)
            return placed
        finally:
            srv.shutdown()

    burst = run_mode(len(asks))
    serial = run_mode(1)
    assert burst == serial, (seed, burst, serial)
    assert sum(burst.values()) == expected, (seed, burst, expected)


# ---------------------------------------------------------------------------
# 2f. Cross-eval batched exact solve: stacked dispatch ≡ individual solves
# ---------------------------------------------------------------------------


def _exact_cluster(rng, n):
    """Shared node tensors for a stacked exact dispatch — one mirror's
    (total, sched_cap, bw_avail), the identity the coalescer groups on."""
    total = np.zeros((n, 4), dtype=np.int32)
    total[:, 0] = rng.integers(200, 8000, n)
    total[:, 1] = rng.integers(128, 16384, n)
    total[:, 2] = rng.integers(1024, 200_000, n)
    total[:, 3] = rng.integers(10, 300, n)
    return (
        jnp.asarray(total), jnp.asarray(total[:, :2].astype(np.float32)),
        jnp.asarray(rng.integers(100, 2000, n).astype(np.int32)),
        total,
    )


def _exact_entry_args(rng, n, cluster):
    """One random exact-solve input set over the shared cluster: the
    per-eval tensors (usage, eligibility, ask) vary, the node tensors
    are the mirror's (shared objects, like burst members of one state
    generation)."""
    total_dev, sched_cap_dev, bw_avail_dev, total = cluster
    used = (total * (rng.random((n, 1)) * 0.6)).astype(np.int32)
    ask = np.array([
        int(rng.integers(1, 1500)), int(rng.integers(1, 2048)),
        int(rng.integers(0, 2000)), int(rng.integers(0, 50)),
    ], dtype=np.int32)
    count = int(rng.integers(1, 129))
    return (
        total_dev, sched_cap_dev,
        jnp.asarray(used), jnp.zeros((n,), jnp.int32),
        jnp.zeros((n,), jnp.int32),
        bw_avail_dev,
        jnp.zeros((n,), jnp.int32),
        jnp.asarray(rng.random(n) > 0.2),
        jnp.asarray(ask), jnp.int32(int(rng.integers(0, 100))),
        count, float(rng.choice([5.0, 10.0])), False, False,
    )


@pytest.mark.parametrize("seed", range(0, N_KERNEL_SEEDS, 4))
def test_stacked_exact_dispatch_matches_individual(seed):
    """The cross-eval batched exact scan (solve_greedy_batched through
    the coalescer's stacked dispatch) must return BIT-IDENTICAL
    (idxs, oks) to each entry's lone solve_greedy dispatch — the
    decision-identity contract of ISSUE 14's batching. Heterogeneous
    counts within one count bucket, heterogeneous asks/usage, padded
    eval rows; mesh=1 (the default single-device fallback path)."""
    from nomad_tpu.ops.binpack import bucket, solve_greedy
    from nomad_tpu.ops.coalesce import CoalescingSolver, _Entry

    rng = np.random.default_rng(130_000 + seed)
    n = int(rng.choice([32, 64]))
    cluster = _exact_cluster(rng, n)
    k_target = None
    entries = []
    raw = []
    # 2-7 entries of ONE count bucket (the dispatcher's grouping key),
    # counts heterogeneous inside it.
    width = int(rng.integers(2, 8))
    while len(entries) < width:
        args = _exact_entry_args(rng, n, cluster)
        k = bucket(args[10])
        if k_target is None:
            k_target = k
        elif k != k_target:
            continue
        raw.append(args)
        entries.append(_Entry(args, kind="exact", k=k))
    engine = CoalescingSolver()
    d0 = engine.dispatches
    engine._dispatch(list(entries))
    assert engine.dispatches == d0 + 1, "one stacked dispatch expected"
    for e, args in zip(entries, raw):
        count = args[10]
        idxs, oks = e.result()
        active = jnp.arange(e.k) < count
        ref_idxs, ref_oks, _ = solve_greedy(
            *args[:10], active, jnp.float32(args[11]), e.k,
            args[12], args[13],
        )
        np.testing.assert_array_equal(
            np.asarray(idxs), np.asarray(ref_idxs),
            err_msg=f"seed {seed} idxs diverge",
        )
        np.testing.assert_array_equal(
            np.asarray(oks), np.asarray(ref_oks),
            err_msg=f"seed {seed} oks diverge",
        )


@pytest.mark.parametrize("seed", range(0, N_SCHED_SEEDS, 6))
def test_equiv_class_collapse_matches_combined(seed):
    """Equivalence classes (Borg): a job of M identical columnar task
    groups must (a) dispatch ONE counts-solve, (b) produce the same
    per-node placement distribution as the single combined-count group
    solved alone, (c) place every copy within capacity, and (d) leave
    the per-member batches carrying the right name-index shares."""
    from nomad_tpu.tpu.solver import SOLVER_PANEL

    rng = np.random.default_rng(140_000 + seed)
    n_nodes = int(rng.integers(8, 24))
    members = int(rng.integers(2, 5))
    count = int(rng.integers(256, 400))
    cpu = int(rng.integers(4, 10))

    def mk_nodes():
        nodes = []
        for i in range(n_nodes):
            node = Node(
                id=f"eq-{seed}-{i}", datacenter="dc1", name=f"n{i}",
                attributes={"kernel.name": "linux", "driver.exec": "1"},
                resources=Resources(cpu=14000, memory_mb=28000,
                                    disk_mb=100_000, iops=1000),
                status=structs.NODE_STATUS_READY,
            )
            nodes.append(node)
        return nodes

    def run(tg_counts):
        h = Harness()
        for node in mk_nodes():
            h.state.upsert_node(h.next_index(), node)
        tgs = [
            TaskGroup(
                name=f"g{j}", count=c,
                restart_policy=RestartPolicy(attempts=0, interval=600.0,
                                             delay=1.0),
                tasks=[Task(name="t", driver="exec",
                            resources=Resources(cpu=cpu, memory_mb=16))],
            )
            for j, c in enumerate(tg_counts)
        ]
        job = Job(
            region="global", id=generate_uuid(), name=f"eqf-{seed}",
            type=structs.JOB_TYPE_BATCH, priority=50,
            datacenters=["dc1"], task_groups=tgs,
        )
        h.state.upsert_job(h.next_index(), job)
        ev = Evaluation(
            id=generate_uuid(), priority=50, type=job.type,
            triggered_by=structs.EVAL_TRIGGER_JOB_REGISTER, job_id=job.id,
        )
        h.process("tpu-batch", ev)
        assert len(h.plans) == 1
        per_node: dict = {}
        per_tg: dict = {}
        for b in h.plans[0].alloc_batches:
            per_tg[b.tg_name] = per_tg.get(b.tg_name, 0) + b.n
            for nid, cnt in zip(b.node_ids, b.node_counts):
                per_node[nid] = per_node.get(nid, 0) + int(cnt)
        return h, per_node, per_tg

    e0 = SOLVER_PANEL.equiv_classes
    s0 = SOLVER_PANEL.solves
    _h, per_node, per_tg = run([count] * members)
    assert SOLVER_PANEL.equiv_classes == e0 + 1, "class did not collapse"
    assert SOLVER_PANEL.solves == s0 + 1, "expected exactly one solve"
    total = members * count
    assert sum(per_tg.values()) == total, (seed, per_tg)
    assert all(per_tg[f"g{j}"] == count for j in range(members)), per_tg
    # The combined-count reference: one group of members*count copies.
    _h2, per_node_ref, _ = run([total])
    assert per_node == per_node_ref, (
        seed, "class expansion changed the placement distribution",
    )


def test_equiv_class_interleaved_groups_do_not_collapse():
    """Only CONSECUTIVE equivalent groups collapse: [A, B, A'] with
    A ≡ A' but B different must solve as three rows — folding A' past B
    would let A''s placements into the plan before B solves, changing
    the usage view (anti-affinity job_count, plan deltas) the sequential
    loop gives B. [A, A', B] collapses the adjacent pair."""
    from nomad_tpu.tpu.solver import SOLVER_PANEL

    def run(order):
        h = Harness()
        for i in range(16):
            node = Node(
                id=f"il-{i}", datacenter="dc1", name=f"n{i}",
                attributes={"kernel.name": "linux", "driver.exec": "1"},
                resources=Resources(cpu=14000, memory_mb=28000,
                                    disk_mb=100_000, iops=1000),
                status=structs.NODE_STATUS_READY,
            )
            h.state.upsert_node(h.next_index(), node)
        tgs = []
        for j, kind in enumerate(order):
            cpu = 5 if kind == "A" else 9
            tgs.append(TaskGroup(
                name=f"g{j}", count=300,
                restart_policy=RestartPolicy(attempts=0, interval=600.0,
                                             delay=1.0),
                tasks=[Task(name="t", driver="exec",
                            resources=Resources(cpu=cpu, memory_mb=16))],
            ))
        job = Job(
            region="global", id=generate_uuid(), name="il",
            type=structs.JOB_TYPE_BATCH, priority=50,
            datacenters=["dc1"], task_groups=tgs,
        )
        h.state.upsert_job(h.next_index(), job)
        ev = Evaluation(
            id=generate_uuid(), priority=50, type=job.type,
            triggered_by=structs.EVAL_TRIGGER_JOB_REGISTER, job_id=job.id,
        )
        s0 = SOLVER_PANEL.solves
        c0 = SOLVER_PANEL.equiv_classes
        h.process("tpu-batch", ev)
        placed = sum(b.n for b in h.plans[0].alloc_batches)
        return placed, SOLVER_PANEL.solves - s0, \
            SOLVER_PANEL.equiv_classes - c0

    placed, solves, classes = run(["A", "B", "A"])
    assert placed == 900
    assert solves == 3 and classes == 0, (solves, classes)

    placed, solves, classes = run(["A", "A", "B"])
    assert placed == 900
    assert solves == 2 and classes == 1, (solves, classes)


# ---------------------------------------------------------------------------
# 5. Delta-rolled device mirror ≡ fresh build (bit-identical)
#
# MirrorCache no longer rebuilds the whole NodeMirror on every node write:
# it rolls the resident mirror forward through the state store's node
# change log (NodeMirror.apply_delta), patching only dirty rows and
# invalidating only affected mask columns. The contract is BIT-IDENTITY:
# after any seeded sequence of upserts/removals/drain flips — including
# the repadding boundary and the log-horizon fallback — the rolled mirror
# must equal a mirror freshly built from the same snapshot, array for
# array, mask for mask, id for id.

N_MIRROR_SEEDS = int(os.environ.get("NOMAD_TPU_FUZZ_SEEDS", 60)) // 2


def _mirror_rand_node(rng, i):
    from nomad_tpu.structs import NODE_STATUS_INIT, NODE_STATUS_READY

    res = Resources(
        cpu=int(rng.integers(500, 8000)),
        memory_mb=int(rng.integers(256, 16384)),
        disk_mb=int(rng.integers(1024, 100_000)),
        iops=int(rng.integers(10, 300)),
    )
    if rng.random() < 0.3:
        res.networks = [NetworkResource(
            device="eth0", cidr="10.0.0.0/8", ip=f"10.0.{i % 250}.1",
            mbits=int(rng.integers(100, 2000)),
        )]
    node = Node(
        id=f"fz-{i:04d}",
        datacenter=str(rng.choice(["dc1", "dc2", "dc3"])),
        name=f"fz-{i}",
        attributes={
            "kernel.name": "linux",
            "driver.exec": str(rng.choice(["1", "0"])),
            "rack": f"r{int(rng.integers(0, 4))}",
        },
        meta={"tier": str(rng.choice(["a", "b"]))},
        status=str(rng.choice(
            [NODE_STATUS_READY] * 4 + [NODE_STATUS_INIT])),
        drain=bool(rng.random() < 0.08),
        resources=res,
    )
    if rng.random() < 0.2:
        node.reserved = Resources(
            cpu=int(rng.integers(0, 200)),
            memory_mb=int(rng.integers(0, 256)),
        )
    return node


_MIRROR_FUZZ_CONSTRAINTS = [
    Constraint(l_target="$attr.kernel.name", r_target="linux", operand="="),
    Constraint(l_target="$attr.rack", r_target="r1", operand="!="),
    Constraint(l_target="$meta.tier", r_target="a", operand="="),
    Constraint(l_target="$node.datacenter", r_target="dc1", operand="="),
]


def _assert_mirror_bit_identical(rolled, fresh, where):
    """Every array + mask + id order must match a fresh build exactly."""
    assert rolled.n == fresh.n, where
    assert rolled.padded == fresh.padded, where
    assert [n.id for n in rolled.nodes] == [n.id for n in fresh.nodes], where
    for attr in ("reserved_np", "bw_reserved", "base_mask"):
        np.testing.assert_array_equal(
            getattr(rolled, attr), getattr(fresh, attr),
            err_msg=f"{where}: {attr}")
    for attr in ("total", "sched_cap", "bw_avail"):
        np.testing.assert_array_equal(
            np.asarray(getattr(rolled, attr)),
            np.asarray(getattr(fresh, attr)),
            err_msg=f"{where}: {attr}")
    np.testing.assert_array_equal(
        rolled.id_array(), fresh.id_array(), err_msg=f"{where}: ids")
    np.testing.assert_array_equal(
        rolled.driver_mask({"exec"}), fresh.driver_mask({"exec"}),
        err_msg=f"{where}: driver_mask")
    for c in _MIRROR_FUZZ_CONSTRAINTS:
        np.testing.assert_array_equal(
            rolled.constraint_mask(None, [c]),
            fresh.constraint_mask(None, [c]),
            err_msg=f"{where}: constraint {c.l_target} {c.operand}")
    got_dev, got_n = rolled.device_mask(
        None, {"exec"}, None, _MIRROR_FUZZ_CONSTRAINTS[:2])
    want_dev, want_n = fresh.device_mask(
        None, {"exec"}, None, _MIRROR_FUZZ_CONSTRAINTS[:2])
    assert got_n == want_n, where
    np.testing.assert_array_equal(
        np.asarray(got_dev), np.asarray(want_dev),
        err_msg=f"{where}: device_mask")
    for got, want, name in zip(rolled.clean_usage(), fresh.clean_usage(),
                               ("used", "job", "tg", "bw")):
        np.testing.assert_array_equal(
            np.asarray(got), np.asarray(want),
            err_msg=f"{where}: clean_usage {name}")


def _mirror_mutate(rng, store, idx, next_id):
    """One random node-table write against the live store. Returns
    (next index, next fresh id)."""
    from nomad_tpu import structs as st

    ids = [n.id for n in store.nodes()]
    op = rng.random()
    idx += 1
    if not ids or op < 0.22:
        store.upsert_node(idx, _mirror_rand_node(rng, next_id))
        return idx, next_id + 1
    nid = str(rng.choice(ids))
    if op < 0.50:
        # In-place rewrite: resource drift and/or mask-surface change.
        node = store.node_by_id(nid).copy()
        which = rng.random()
        if which < 0.5:
            node.resources = node.resources.copy()
            node.resources.cpu = int(rng.integers(500, 8000))
        elif which < 0.7:
            node.attributes["rack"] = f"r{int(rng.integers(0, 4))}"
        elif which < 0.85:
            node.meta["tier"] = str(rng.choice(["a", "b"]))
        else:
            node.reserved = Resources(cpu=int(rng.integers(0, 300)))
        store.upsert_node(idx, node)
    elif op < 0.65:
        store.update_node_drain(
            idx, nid, not store.node_by_id(nid).drain)
    elif op < 0.85:
        store.update_node_status(idx, nid, str(rng.choice(
            [st.NODE_STATUS_READY, st.NODE_STATUS_READY,
             st.NODE_STATUS_DOWN, st.NODE_STATUS_INIT])))
    else:
        store.delete_node(idx, nid)
    return idx, next_id


@pytest.mark.parametrize("seed", range(N_MIRROR_SEEDS))
def test_mirror_delta_roll_bit_identical(seed):
    """Seeded churn (upserts, removals, drain/status flips, fresh
    registrations) rolled through MirrorCache must yield a mirror
    bit-identical to a fresh build at every checkpoint."""
    from nomad_tpu.scheduler.util import ready_nodes_in_dcs
    from nomad_tpu.state import StateStore
    from nomad_tpu.tpu.mirror import MirrorCache, NodeMirror

    rng = np.random.default_rng(40_000 + seed)
    store = StateStore()
    idx = 0
    next_id = 0
    for _ in range(int(rng.integers(6, 70))):
        idx += 1
        store.upsert_node(idx, _mirror_rand_node(rng, next_id))
        next_id += 1
    dcs = ["dc1", "dc2"]
    cache = MirrorCache()
    _n, warm = cache.get(store.snapshot(), dcs)
    # Populate the caches the roll must selectively invalidate.
    warm.driver_mask({"exec"})
    warm.device_mask(None, {"exec"}, None, _MIRROR_FUZZ_CONSTRAINTS[:2])
    warm.clean_usage()
    for step in range(int(rng.integers(3, 9))):
        for _ in range(int(rng.integers(1, 5))):
            idx, next_id = _mirror_mutate(rng, store, idx, next_id)
        snap = store.snapshot()
        _n, rolled = cache.get(snap, dcs)
        fresh = NodeMirror(ready_nodes_in_dcs(snap, dcs))
        _assert_mirror_bit_identical(
            rolled, fresh, where=(seed, step, idx))
    stats = cache.stats()
    assert stats["delta_rolls"] + stats["full_rebuilds"] >= 1, (seed, stats)


def test_mirror_delta_repadding_boundary():
    """Appends inside the padding bucket roll; crossing the power-of-two
    boundary forces (and correctly executes) a full rebuild."""
    from nomad_tpu import structs as st
    from nomad_tpu.scheduler.util import ready_nodes_in_dcs
    from nomad_tpu.state import StateStore
    from nomad_tpu.tpu.mirror import MirrorCache, NodeMirror

    def mk(i):
        return Node(
            id=f"pad-{i:03d}", datacenter="dc1", name=f"pad-{i}",
            attributes={"kernel.name": "linux", "driver.exec": "1"},
            resources=Resources(cpu=1000, memory_mb=1024),
            status=st.NODE_STATUS_READY,
        )

    store = StateStore()
    idx = 0
    for i in range(63):
        idx += 1
        store.upsert_node(idx, mk(i))
    cache = MirrorCache()
    _n, m0 = cache.get(store.snapshot(), ["dc1"])
    assert m0.padded == 64
    # 63 -> 64: same bucket, append roll.
    idx += 1
    store.upsert_node(idx, mk(63))
    snap = store.snapshot()
    _n, m1 = cache.get(snap, ["dc1"])
    _assert_mirror_bit_identical(
        m1, NodeMirror(ready_nodes_in_dcs(snap, ["dc1"])), "64")
    assert cache.stats()["delta_rolls"] == 1
    assert cache.stats()["full_rebuilds"] == 1  # the initial build
    # 64 -> 65: crosses to the 128 bucket, must fully rebuild.
    idx += 1
    store.upsert_node(idx, mk(64))
    snap = store.snapshot()
    _n, m2 = cache.get(snap, ["dc1"])
    assert m2.padded == 128
    _assert_mirror_bit_identical(
        m2, NodeMirror(ready_nodes_in_dcs(snap, ["dc1"])), "65")
    assert cache.stats()["delta_rolls"] == 1
    assert cache.stats()["full_rebuilds"] == 2


def test_mirror_delta_log_horizon_fallback(monkeypatch):
    """Writes past the bounded change log's horizon make
    node_changes_since return None and the cache fall back to one full
    rebuild — never a wrong delta."""
    from nomad_tpu import structs as st
    from nomad_tpu.scheduler.util import ready_nodes_in_dcs
    from nomad_tpu.state import StateStore
    from nomad_tpu.state import store as store_mod
    from nomad_tpu.tpu.mirror import MirrorCache, NodeMirror

    monkeypatch.setattr(store_mod, "NODE_LOG_HORIZON", 4)
    store = StateStore()
    idx = 0
    for i in range(12):
        idx += 1
        store.upsert_node(idx, Node(
            id=f"hz-{i:03d}", datacenter="dc1", name=f"hz-{i}",
            attributes={"kernel.name": "linux"},
            resources=Resources(cpu=1000, memory_mb=1024),
            status=st.NODE_STATUS_READY,
        ))
    cache = MirrorCache()
    _n, _m = cache.get(store.snapshot(), ["dc1"])
    base_index = store.get_index("nodes")
    # > 2 * horizon single-node writes: the log trims past base_index.
    for i in range(10):
        node = store.node_by_id(f"hz-{i % 12:03d}").copy()
        node.resources = node.resources.copy()
        node.resources.cpu += 1
        idx += 1
        store.upsert_node(idx, node)
    snap = store.snapshot()
    assert snap.node_changes_since(base_index) is None
    _n, rolled = cache.get(snap, ["dc1"])
    _assert_mirror_bit_identical(
        rolled, NodeMirror(ready_nodes_in_dcs(snap, ["dc1"])), "horizon")
    stats = cache.stats()
    assert stats["delta_rolls"] == 0
    assert stats["full_rebuilds"] == 2, stats


# ---------------------------------------------------------------------------
# 6. Delta-maintained usage tensors ≡ the full proposed-alloc walk
#
# build_usage now copies a cached, change-log-rolled base and touches only
# the plan's in-flight rows; _build_usage_walk is the original O(cluster)
# reference implementation. They must agree exactly — across alloc-table
# generations (object rows, columnar blocks, evictions) and arbitrary
# plans (placements, evictions of object rows, block members, stale ids).


def _usage_quad(out):
    return [np.asarray(x) for x in out]


@pytest.mark.parametrize("seed", range(N_MIRROR_SEEDS))
def test_usage_delta_matches_full_walk(seed):
    from nomad_tpu import structs as st
    from nomad_tpu.scheduler.context import EvalContext
    from nomad_tpu.scheduler.util import ready_nodes_in_dcs
    from nomad_tpu.state import StateStore
    from nomad_tpu.structs import AllocBatch, Allocation, Plan
    from nomad_tpu.tpu.mirror import MirrorCache, NodeMirror

    rng = np.random.default_rng(50_000 + seed)
    store = StateStore()
    idx = 0
    n0 = int(rng.integers(8, 40))
    for i in range(n0):
        idx += 1
        store.upsert_node(idx, _mirror_rand_node(rng, i))
    dcs = ["dc1", "dc2"]
    cache = MirrorCache()
    cache.get(store.snapshot(), dcs)

    job = Job(
        region="global", id=f"uj-{seed}", name=f"uj-{seed}",
        type=structs.JOB_TYPE_SERVICE, priority=50, datacenters=dcs,
        task_groups=[TaskGroup(
            name="web", count=64,
            tasks=[Task(name="t", driver="exec",
                        resources=Resources(cpu=50, memory_mb=64))],
        )],
    )
    other_job = Job(
        region="global", id=f"uo-{seed}", name=f"uo-{seed}",
        type=structs.JOB_TYPE_SERVICE, priority=50, datacenters=dcs,
        task_groups=job.task_groups,
    )

    def rand_alloc(nid, j, serial, status=st.ALLOC_DESIRED_STATUS_RUN):
        return Allocation(
            id=generate_uuid(), eval_id=generate_uuid(),
            name=f"{j.name}.web[{serial}]", node_id=nid, job_id=j.id,
            job=j, task_group="web",
            resources=Resources(cpu=int(rng.integers(10, 200)),
                                memory_mb=int(rng.integers(16, 256))),
            desired_status=status,
        )

    object_allocs = []
    blocks_batches = []
    for generation in range(int(rng.integers(2, 5))):
        ids = [n.id for n in store.nodes()]
        # Alloc-table churn: object rows (some terminal), plus a columnar
        # block for a random job.
        new_allocs = []
        for s in range(int(rng.integers(1, 6))):
            j = job if rng.random() < 0.6 else other_job
            status = (st.ALLOC_DESIRED_STATUS_RUN
                      if rng.random() < 0.8
                      else st.ALLOC_DESIRED_STATUS_STOP)
            new_allocs.append(
                rand_alloc(str(rng.choice(ids)), j, s, status))
        idx += 1
        store.upsert_allocs(idx, new_allocs)
        object_allocs.extend(new_allocs)
        if rng.random() < 0.6:
            j = job if rng.random() < 0.5 else other_job
            picks = [str(rng.choice(ids))
                     for _ in range(int(rng.integers(1, 4)))]
            counts = [int(rng.integers(1, 5)) for _ in picks]
            batch = AllocBatch(
                eval_id=generate_uuid(), job=j, tg_name="web",
                resources=Resources(cpu=20, memory_mb=32),
                task_resources={"t": Resources(cpu=20, memory_mb=32)},
                metrics=None,
                node_ids=picks,
                node_counts=counts,
                name_idx=np.arange(sum(counts)),
                ids_seed=int(rng.integers(1, 2**63)),
            )
            idx += 1
            store.upsert_alloc_blocks(idx, [batch])
            blocks_batches.append(batch)
        # Cross-node supersede: restamp a live block member onto a
        # DIFFERENT node via an object-row upsert — the member's OLD
        # node silently loses its block usage, and the alloc log must
        # dirty both ends or the rolled base over-counts it.
        if rng.random() < 0.5:
            for blk in store.alloc_blocks():
                if blk.n_live:
                    pos = blk.live_positions()[0]
                    member = blk.materialize_pos(pos)
                    member.node_id = str(rng.choice(ids))
                    member.resources = Resources(
                        cpu=int(rng.integers(10, 100)), memory_mb=32)
                    idx += 1
                    store.upsert_allocs(idx, [member])
                    object_allocs.append(member)
                    break
        # A couple of node writes too: the mirror must roll while the
        # usage base rolls independently through the alloc log.
        for _ in range(int(rng.integers(0, 3))):
            idx, n0 = _mirror_mutate(rng, store, idx, n0 + 1000)

        snap = store.snapshot()
        _n, rolled = cache.get(snap, dcs)
        fresh = NodeMirror(ready_nodes_in_dcs(snap, dcs))

        # Random plan: placements + evictions (object rows, live block
        # members, stale ids).
        plan = Plan(eval_id=generate_uuid())
        mirror_ids = [n.id for n in fresh.nodes]
        if mirror_ids:
            for s in range(int(rng.integers(0, 4))):
                nid = str(rng.choice(mirror_ids))
                plan.node_allocation.setdefault(nid, []).append(
                    rand_alloc(nid, job, 100 + s))
        live_objects = [a for a in object_allocs
                        if store.alloc_object_by_id(a.id) is not None]
        for a in (rng.choice(live_objects, size=min(2, len(live_objects)),
                             replace=False) if live_objects else []):
            plan.node_update.setdefault(a.node_id, []).append(a.copy())
        for blk in snap.alloc_blocks():
            if rng.random() < 0.4 and blk.n_live:
                pos = blk.live_positions()[0]
                member = blk.materialize_pos(pos)
                plan.node_update.setdefault(
                    member.node_id, []).append(member)
                break
        if mirror_ids and rng.random() < 0.5:
            stale = rand_alloc(str(rng.choice(mirror_ids)), job, 999)
            plan.node_update.setdefault(stale.node_id, []).append(stale)

        ctx = EvalContext(snap, plan)
        got = _usage_quad(rolled.build_usage(ctx, job.id, "web"))
        want = _usage_quad(fresh._build_usage_walk(ctx, job.id, "web"))
        for g, w, name in zip(got, want,
                              ("used", "job_count", "tg_count", "bw_used")):
            np.testing.assert_array_equal(
                g, w, err_msg=f"seed {seed} gen {generation}: {name}")


@pytest.mark.parametrize("seed", range(N_MIRROR_SEEDS))
def test_node_table_delta_matches_fresh(seed):
    """The plan applier's columnar node table rolls through the same
    change log (plan_apply._NodeTable.apply_delta); a rolled table must
    equal a fresh build — rows map, columns, liveness — across the same
    churn the mirror fuzz applies."""
    from nomad_tpu.server import plan_apply
    from nomad_tpu.state import StateStore

    rng = np.random.default_rng(60_000 + seed)
    with plan_apply._NODE_TABLE_LOCK:
        plan_apply._NODE_TABLE_CACHE = None
    store = StateStore()
    idx = 0
    next_id = 0
    for _ in range(int(rng.integers(5, 50))):
        idx += 1
        store.upsert_node(idx, _mirror_rand_node(rng, next_id))
        next_id += 1
    plan_apply._node_table(store.snapshot())
    for step in range(int(rng.integers(3, 8))):
        for _ in range(int(rng.integers(1, 4))):
            idx, next_id = _mirror_mutate(rng, store, idx, next_id)
        snap = store.snapshot()
        rolled = plan_apply._node_table(snap)
        fresh = plan_apply._NodeTable(snap)
        where = (seed, step, idx)
        assert rolled.n == fresh.n, where
        assert rolled.rows == fresh.rows, where
        for attr in ("totals", "reserved", "dead", "scalar_only"):
            np.testing.assert_array_equal(
                getattr(rolled, attr), getattr(fresh, attr),
                err_msg=f"{where}: {attr}")


# ---------------------------------------------------------------------------
# 7. Batched plan verification parity (plan_pipeline.evaluate_plans)
# ---------------------------------------------------------------------------

N_BATCH_VERIFY_SEEDS = int(os.environ.get("NOMAD_TPU_FUZZ_SEEDS", 40))


def _pv_alloc(rng, nid, serial, cpu=None):
    return structs.Allocation(
        id=generate_uuid(), eval_id=generate_uuid(),
        name=f"pv.web[{serial}]", node_id=nid, job_id="pv-job",
        task_group="web",
        resources=Resources(
            cpu=int(cpu if cpu is not None else rng.integers(50, 900)),
            memory_mb=int(rng.integers(16, 512)),
        ),
        desired_status=structs.ALLOC_DESIRED_STATUS_RUN,
    )


def _pv_batch(rng, ids, with_net=False):
    """One columnar placement batch over a random node subset — counts
    sized so stacked overlapping batches overflow small nodes."""
    from nomad_tpu.structs import AllocBatch

    picks = [str(rng.choice(ids))
             for _ in range(int(rng.integers(1, 5)))]
    counts = [int(rng.integers(1, 40)) for _ in picks]
    res = Resources(cpu=int(rng.integers(30, 600)),
                    memory_mb=int(rng.integers(16, 256)))
    if with_net:
        res.networks = [NetworkResource(device="eth0", mbits=10)]
    return AllocBatch(
        eval_id=generate_uuid(), job=None, tg_name="web",
        resources=res,
        task_resources={"t": res},
        metrics=None,
        node_ids=picks, node_counts=counts,
        name_idx=np.arange(sum(counts)),
        ids_seed=int(rng.integers(1, 2**63)),
    )


def _pv_decisions(result):
    """The decision content of one PlanResult, in comparable form."""
    return {
        "refresh_index": result.refresh_index,
        "node_allocation": {
            nid: sorted(a.id for a in allocs)
            for nid, allocs in result.node_allocation.items() if allocs
        },
        "node_update": {
            nid: sorted(a.id for a in allocs)
            for nid, allocs in result.node_update.items() if allocs
        },
        "alloc_batches": sorted(
            (tuple(b.node_ids), tuple(int(c) for c in b.node_counts))
            for b in result.alloc_batches
        ),
        "update_batches": len(result.update_batches),
    }


@pytest.mark.parametrize("seed", range(N_BATCH_VERIFY_SEEDS))
def test_batched_plan_verify_matches_sequential(seed):
    """The plan pipeline's K-plan fused tensor verify is DECISION-
    IDENTICAL to K sequential evaluate_plan calls with each committed
    subset rolled into the snapshot between calls — across seeded
    overlapping/disjoint plan sets, block-native existing allocs,
    dead/drained/reserved-network nodes, object-row placements forcing
    the scalar path mid-batch, and delta-rolled node tables."""
    import copy as _copy
    import itertools

    from nomad_tpu.server import plan_apply
    from nomad_tpu.server.plan_apply import evaluate_plan
    from nomad_tpu.server.plan_pipeline import (
        apply_result_to_snapshot,
        evaluate_plans,
    )
    from nomad_tpu.state import StateStore
    from nomad_tpu.structs import Plan

    rng = np.random.default_rng(70_000 + seed)
    with plan_apply._NODE_TABLE_LOCK:
        plan_apply._NODE_TABLE_CACHE = None
    store = StateStore()
    idx = 0
    next_id = 0
    for _ in range(int(rng.integers(6, 30))):
        idx += 1
        store.upsert_node(idx, _mirror_rand_node(rng, next_id))
        next_id += 1
    # Seed a table ancestor so later verifies exercise the delta roll.
    plan_apply._node_table(store.snapshot())

    # Pre-existing columnar blocks (block-native allocs) and sometimes
    # object rows (which force the whole batch down the scalar path).
    ids = [n.id for n in store.nodes()]
    for _ in range(int(rng.integers(0, 4))):
        idx += 1
        store.upsert_alloc_blocks(
            idx, [_pv_batch(rng, ids, with_net=rng.random() < 0.15)])
    if rng.random() < 0.35:
        idx += 1
        store.upsert_allocs(idx, [
            _pv_alloc(rng, str(rng.choice(ids)), s)
            for s in range(int(rng.integers(1, 4)))
        ])
    # Node-table churn after the ancestor build: the rolled-table path.
    for _ in range(int(rng.integers(0, 4))):
        idx, next_id = _mirror_mutate(rng, store, idx, next_id)
    ids = [n.id for n in store.nodes()]
    if not ids:
        return

    k = int(rng.integers(2, 7))
    plans = []
    for p in range(k):
        plan = Plan(eval_id=f"pv-{seed}-{p}", priority=50)
        shape = rng.random()
        if shape < 0.6:
            # Pure columnar: the fused path's home turf. Overlap is the
            # point — batches draw from the same node pool.
            for _ in range(int(rng.integers(1, 3))):
                plan.append_batch(
                    _pv_batch(rng, ids, with_net=rng.random() < 0.1))
        elif shape < 0.85:
            # Object placements (scalar path mid-batch).
            for s in range(int(rng.integers(1, 4))):
                nid = str(rng.choice(ids))
                plan.node_allocation.setdefault(nid, []).append(
                    _pv_alloc(rng, nid, s))
        else:
            # Mixed: a batch plus an eviction of a stale id.
            plan.append_batch(_pv_batch(rng, ids))
            stale = _pv_alloc(rng, str(rng.choice(ids)), 999)
            plan.node_update.setdefault(stale.node_id, []).append(stale)
        plans.append(plan)

    plans_seq = _copy.deepcopy(plans)
    plans_fused = _copy.deepcopy(plans)
    snap_seq = store.snapshot()
    snap_fused = store.snapshot()

    stamp_seq = itertools.count(100_000)
    stamp_fused = itertools.count(100_000)

    want = []
    for plan in plans_seq:
        res = evaluate_plan(snap_seq, plan)
        if not res.is_noop():
            apply_result_to_snapshot(snap_seq, res, next(stamp_seq))
        want.append(_pv_decisions(res))

    got_results = evaluate_plans(
        snap_fused, plans_fused, stamp_index=lambda: next(stamp_fused))
    got = [_pv_decisions(r) for r in got_results]

    assert got == want, f"seed {seed}: fused verify diverged"
    # The rolled stores must agree too: same committed blocks, same
    # object rows.
    def _store_shape(snap):
        return (
            sorted((tuple(b.node_ids), tuple(int(c) for c in b.node_counts))
                   for b in snap.alloc_blocks()),
            sorted(a.id for nid in ids for a in snap.allocs_by_node(nid)),
        )
    assert _store_shape(snap_fused) == _store_shape(snap_seq), (
        f"seed {seed}: rolled snapshots diverged"
    )


@pytest.mark.parametrize("seed", range(N_BATCH_VERIFY_SEEDS))
def test_batched_plan_verify_fused_engagement_parity(seed):
    """Same parity contract on the fused pass's home distribution — all
    nodes live, pure columnar overlapping batches sized so the stacked
    asks overflow small nodes mid-batch (prefix commit + scalar
    resolution of the overflowing plan + re-fuse of the tail). Asserts
    the fused pass actually engaged: a regression that silently sends
    everything down the scalar path fails here, not just in benchmarks."""
    import copy as _copy
    import itertools

    from nomad_tpu.server import plan_apply
    from nomad_tpu.server.plan_apply import evaluate_plan
    from nomad_tpu.server.plan_pipeline import (
        _PipelineTotals,
        apply_result_to_snapshot,
        evaluate_plans,
    )
    from nomad_tpu.state import StateStore
    from nomad_tpu.structs import Node, Plan

    rng = np.random.default_rng(80_000 + seed)
    with plan_apply._NODE_TABLE_LOCK:
        plan_apply._NODE_TABLE_CACHE = None
    store = StateStore()
    idx = 0
    n_nodes = int(rng.integers(5, 25))
    for i in range(n_nodes):
        idx += 1
        store.upsert_node(idx, Node(
            id=f"fp-{i:03d}", datacenter="dc1", name=f"fp{i}",
            status="ready",
            resources=Resources(
                cpu=int(rng.integers(1000, 6000)),
                memory_mb=int(rng.integers(2048, 16384)),
                disk_mb=100_000, iops=10_000,
            ),
        ))
    plan_apply._node_table(store.snapshot())
    ids = [n.id for n in store.nodes()]

    def _mk_batch(hog=False):
        from nomad_tpu.structs import AllocBatch

        picks = [str(rng.choice(ids))
                 for _ in range(int(rng.integers(1, 5)))]
        counts = [int(rng.integers(1, 6)) for _ in picks]
        res = Resources(
            cpu=int(rng.integers(2000, 4000) if hog
                    else rng.integers(10, 80)),
            memory_mb=int(rng.integers(16, 128)),
        )
        return AllocBatch(
            eval_id=generate_uuid(), job=None, tg_name="web",
            resources=res, task_resources={"t": res}, metrics=None,
            node_ids=picks, node_counts=counts,
            name_idx=np.arange(sum(counts)),
            ids_seed=int(rng.integers(1, 2**63)),
        )

    # Existing block pressure so the base usage term is non-trivial.
    for _ in range(int(rng.integers(0, 3))):
        idx += 1
        store.upsert_alloc_blocks(idx, [_mk_batch()])

    k = int(rng.integers(3, 8))
    plans = []
    for p in range(k):
        plan = Plan(eval_id=f"fp-{seed}-{p}", priority=50)
        for _ in range(int(rng.integers(1, 3))):
            # Mostly modest asks that stack and fit (the fused whole-
            # commit run); ~15% hogs that overflow mid-batch and force
            # the prefix break + scalar resolution + tail re-fuse.
            plan.append_batch(_mk_batch(hog=rng.random() < 0.15))
        plans.append(plan)

    plans_seq = _copy.deepcopy(plans)
    plans_fused = _copy.deepcopy(plans)
    snap_seq = store.snapshot()
    snap_fused = store.snapshot()
    stamp_seq = itertools.count(100_000)
    stamp_fused = itertools.count(100_000)

    want = []
    for plan in plans_seq:
        res = evaluate_plan(snap_seq, plan)
        if not res.is_noop():
            apply_result_to_snapshot(snap_seq, res, next(stamp_seq))
        want.append(_pv_decisions(res))

    totals = _PipelineTotals()
    got_results = evaluate_plans(
        snap_fused, plans_fused,
        stamp_index=lambda: next(stamp_fused), totals=totals)
    got = [_pv_decisions(r) for r in got_results]

    assert got == want, f"seed {seed}: fused verify diverged"
    assert totals.fused_plans > 0, (
        f"seed {seed}: fused pass never engaged on its home distribution"
    )


@pytest.mark.parametrize("seed", range(0, N_BATCH_VERIFY_SEEDS, 2))
def test_batched_plan_verify_with_stops_matches_sequential(seed):
    """Same parity contract with whole-block stops inside the batch:
    stop-only plans interleaved with fused columnar plans and scalar ones
    (network asks, object rows, asks that overflow) over a cell that the
    stored blocks nearly fill, so what a stop frees decides what later
    plans commit. The node table's block usage is rolled by both
    snapshots in turn (the sequential one and the batched one share the
    table), and each snapshot's usage must still equal a from-nothing
    accumulation at the end."""
    import copy as _copy
    import itertools

    from nomad_tpu.server import plan_apply
    from nomad_tpu.server.plan_apply import (
        _accumulate_block_usage,
        _existing_block_usage_rows,
        evaluate_plan,
    )
    from nomad_tpu.server.plan_pipeline import (
        _PipelineTotals,
        apply_result_to_snapshot,
        evaluate_plans,
    )
    from nomad_tpu.state import StateStore
    from nomad_tpu.structs import AllocBatch, AllocStopBatch, Plan

    rng = np.random.default_rng(90_000 + seed)
    with plan_apply._NODE_TABLE_LOCK:
        plan_apply._NODE_TABLE_CACHE = None
    store = StateStore()
    n_nodes = int(rng.integers(4, 16))
    for i in range(n_nodes):
        store.upsert_node(i + 1, Node(
            id=f"sp-{i:03d}", datacenter="dc1", name=f"sp{i}",
            status="ready",
            resources=Resources(cpu=4000, memory_mb=8192,
                                disk_mb=100_000, iops=10_000),
        ))
    idx = n_nodes
    ids = [n.id for n in store.nodes()]

    def _mk_batch(cpu, net=False):
        picks = [str(rng.choice(ids))
                 for _ in range(int(rng.integers(1, 5)))]
        counts = [int(rng.integers(4, 30)) for _ in picks]
        res = Resources(cpu=cpu, memory_mb=int(rng.integers(8, 32)))
        if net:
            res.networks = [NetworkResource(device="eth0", mbits=1)]
        return AllocBatch(
            eval_id=generate_uuid(), job=None, tg_name="web",
            resources=res, task_resources={"t": res}, metrics=None,
            node_ids=picks, node_counts=counts,
            name_idx=np.arange(sum(counts)),
            ids_seed=int(rng.integers(1, 2**63)),
        )

    # Stored blocks that leave little room: a placement after a stop
    # fits where it would not have before it.
    for _ in range(int(rng.integers(4, 10))):
        idx += 1
        store.upsert_alloc_blocks(
            idx, [_mk_batch(int(rng.integers(20, 60)))])
    live = list(store.snapshot().alloc_blocks())
    rng.shuffle(live)

    k = int(rng.integers(4, 10))
    plans = []
    for p in range(k):
        plan = Plan(eval_id=f"sp-{seed}-{p}", priority=50)
        shape = rng.random()
        if (shape < 0.3 or p == 0) and live:
            blks = [live.pop() for _ in range(min(len(live),
                                                  int(rng.integers(1, 3))))]
            plan.stop_batches = [AllocStopBatch(
                eval_id=plan.eval_id, job_id=b.job_id, block_id=b.block_id,
                n_live=b.n_live, n_total=b.n, ids_seed=b.ids_seed,
                desired_status=structs.ALLOC_DESIRED_STATUS_STOP,
                desired_description="gone", node_ids=b.node_ids)
                for b in blks]
        elif shape < 0.75:
            for _ in range(int(rng.integers(1, 3))):
                plan.append_batch(_mk_batch(int(rng.integers(10, 60))))
        elif shape < 0.9:
            plan.append_batch(_mk_batch(int(rng.integers(10, 40)),
                                        net=True))
        else:
            for s in range(int(rng.integers(1, 4))):
                nid = str(rng.choice(ids))
                plan.node_allocation.setdefault(nid, []).append(
                    _pv_alloc(rng, nid, s, cpu=int(rng.integers(50, 900))))
        plans.append(plan)

    plans_seq = _copy.deepcopy(plans)
    plans_fused = _copy.deepcopy(plans)
    snap_seq = store.snapshot()
    snap_fused = store.snapshot()
    stamp_seq = itertools.count(100_000)
    stamp_fused = itertools.count(100_000)

    want = []
    for plan in plans_seq:
        res = evaluate_plan(snap_seq, plan)
        if not res.is_noop():
            apply_result_to_snapshot(snap_seq, res, next(stamp_seq))
        want.append((_pv_decisions(res),
                     [b.block_id for b in res.stop_batches]))

    totals = _PipelineTotals()
    got_results = evaluate_plans(
        snap_fused, plans_fused,
        stamp_index=lambda: next(stamp_fused), totals=totals)
    got = [(_pv_decisions(r), [b.block_id for b in r.stop_batches])
           for r in got_results]

    assert got == want, f"seed {seed}: verify with stops diverged"
    assert totals.stats()["stop_plans"] == sum(
        1 for p in plans if p.stop_batches)

    def _blocks(snap):
        return sorted(
            (tuple(b.node_ids), tuple(int(c) for c in b.node_counts),
             tuple(sorted(b.excluded)))
            for b in snap.alloc_blocks())
    assert _blocks(snap_fused) == _blocks(snap_seq)
    assert sorted(b.block_id for b in snap_fused.stopped_alloc_blocks()) \
        == sorted(b.block_id for b in snap_seq.stopped_alloc_blocks())
    table = plan_apply._node_table(snap_fused)
    for snap in (snap_seq, snap_fused, store.snapshot()):
        usage, net_rows, _ = _existing_block_usage_rows(snap, table)
        want = _accumulate_block_usage(table, snap.alloc_blocks())
        want_u, want_n = want.usage, want.net_rows
        np.testing.assert_array_equal(
            np.zeros((table.n, 4), dtype=np.int64) if usage is None
            else usage,
            np.zeros((table.n, 4), dtype=np.int64) if want_u is None
            else want_u)
        assert (net_rows is None or not net_rows.any()) == (
            want_n is None or not want_n.any())
        if want_n is not None and net_rows is not None:
            np.testing.assert_array_equal(net_rows, want_n)
