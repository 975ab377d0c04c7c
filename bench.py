"""Benchmark: the BASELINE.json north-star configuration.

Config 3 of BASELINE.md: a 10k-node cluster and a single batch job with
100k task groups (driver + datacenter constraints), placed by the TPU
dense-solve scheduler. The reference publishes no numbers (BASELINE.md);
the driver-defined target is p50 < 200ms for the placement solve, i.e.
500k placements/sec.

Measured phases per evaluation:
- solve: TPUStack.select_many end-to-end — eligibility masks, usage
  tensorization, the device round-solve, and placement extraction. This is
  the reformulated Stack.Select loop (the north-star metric).
- e2e:   the full TPUGenericScheduler.process, including Python-side diff,
  100k Allocation-object materialization and plan/state apply (the part a
  native runtime will take over in later rounds).

Prints ONE JSON line: {"metric", "unit", "backend", "device", ...}. ``value``
and ``vs_baseline`` appear only when the platform is a TPU; on any other
platform the bench exits != 0 (with NOMAD_TPU_BENCH_ALLOW_CPU=1 it runs,
labeled ``backend: "cpu"``, its figures under ``cpu_run``). A phase that
raises is reported in the JSON and fails the exit code.
"""

from __future__ import annotations

import contextlib
import gc
import json
import os
import statistics
import sys
import threading
import time
import traceback

N_NODES = int(os.environ.get("NOMAD_TPU_BENCH_NODES", 10_000))
N_TASKS = int(os.environ.get("NOMAD_TPU_BENCH_TASKS", 100_000))
RUNS = int(os.environ.get("NOMAD_TPU_BENCH_RUNS", 9))
TARGET_PLACEMENTS_PER_SEC = N_TASKS / 0.2  # the north star: tasks in 200ms p50

# The bench measures the device. On any platform but a TPU it refuses to
# run (exit != 0, no ``value``); this knob lets a local run proceed on the
# CPU, labeled ``backend: "cpu"``, with its figures under ``cpu_run`` and
# nothing under the device metric's name.
ALLOW_CPU = os.environ.get("NOMAD_TPU_BENCH_ALLOW_CPU", "") == "1"

METRIC = "placements_per_sec@10k_nodes_x_100k_tasks"

_EMITTED = threading.Event()

# A device op that hangs mid-run would otherwise produce NO output at all —
# the except-path only covers failures that raise. The watchdog guarantees
# the one-line contract regardless.
WATCHDOG_S = float(os.environ.get("NOMAD_TPU_BENCH_WATCHDOG", "2400"))


def emit(payload: dict) -> None:
    """The one-line JSON contract: always printed, even on failure.
    The flag is set BEFORE printing so a watchdog expiring mid-emit can
    never add a second line."""
    _EMITTED.set()
    print(json.dumps(payload), flush=True)


def _dist(times: list, warmup: int) -> dict:
    """Dispersion summary for a list of wall times (seconds): p10/p50/p90
    in ms plus run count and warmup policy. Same-box captures have been
    observed to swing ~2x between single samples (GC, dispatcher timing),
    so every published number carries its spread instead of a bare p50."""
    ts = sorted(times)
    if len(ts) >= 3:
        qs = statistics.quantiles(ts, n=10, method="inclusive")
        p10, p90 = qs[0], qs[8]
    else:
        p10, p90 = ts[0], ts[-1]
    return {
        "p10_ms": round(p10 * 1000, 3),
        "p50_ms": round(statistics.median(ts) * 1000, 3),
        "p90_ms": round(p90 * 1000, 3),
        "runs": len(ts),
        "warmup_runs": warmup,
    }


@contextlib.contextmanager
def _quiesced():
    """Timed-region hygiene: collect pending garbage BEFORE the clock
    starts, then keep the collector off so a generation-2 pass (the
    multi-ms stalls behind the observed 41M->68M placements/s swings)
    cannot land inside a measured run."""
    gc.collect()
    was_enabled = gc.isenabled()
    gc.disable()
    try:
        yield
    finally:
        if was_enabled:
            gc.enable()


def _start_watchdog() -> None:
    def run():
        if _EMITTED.wait(WATCHDOG_S):
            return
        emit({
            "metric": METRIC,
            "unit": "placements/s",
            "backend": "unknown",
            "error": (
                f"bench watchdog: no result after {WATCHDOG_S:.0f}s — a "
                "device op hung mid-run or the run overran its budget"
            ),
        })
        sys.stdout.flush()
        sys.stderr.flush()
        os._exit(1)

    threading.Thread(target=run, daemon=True, name="bench-watchdog").start()


def build_cluster():
    from nomad_tpu import structs
    from nomad_tpu.structs import (
        Constraint,
        Job,
        Node,
        Resources,
        RestartPolicy,
        Task,
        TaskGroup,
        generate_uuid,
    )

    nodes = []
    for i in range(N_NODES):
        nodes.append(
            Node(
                id=f"node-{i:05d}",
                datacenter="dc1" if i % 2 == 0 else "dc2",
                name=f"n{i}",
                attributes={"kernel.name": "linux", "driver.exec": "1"},
                resources=Resources(
                    cpu=4000, memory_mb=8192, disk_mb=100 * 1024, iops=150
                ),
                status=structs.NODE_STATUS_READY,
            )
        )

    job = Job(
        region="global",
        id=generate_uuid(),
        name="bench-batch",
        type=structs.JOB_TYPE_BATCH,
        priority=50,
        datacenters=["dc1"],  # datacenter constraint: half the cluster
        constraints=[
            Constraint(l_target="$attr.kernel.name", r_target="linux", operand="=")
        ],
        task_groups=[
            TaskGroup(
                name="work",
                count=N_TASKS,
                restart_policy=RestartPolicy(attempts=1, interval=600.0, delay=5.0),
                tasks=[
                    Task(
                        name="work",
                        driver="exec",
                        resources=Resources(cpu=100, memory_mb=128),
                    )
                ],
            )
        ],
    )
    return nodes, job


class _TimingStack:
    """Wraps TPUStack.solve_group to capture the solve wall time: masks +
    usage tensorization + device dispatch + readback (+ any host work the
    scheduler overlaps with the transfer)."""

    solve_times = []

    @classmethod
    def install(cls):
        from nomad_tpu.tpu.solver import TPUStack

        def wrap(orig):
            def timed(self, tg, count, overlap=None):
                start = time.perf_counter()
                out = orig(self, tg, count, overlap=overlap)
                cls.solve_times.append(time.perf_counter() - start)
                return out

            return timed

        TPUStack.solve_group = wrap(TPUStack.solve_group)
        TPUStack.solve_group_counts = wrap(TPUStack.solve_group_counts)


def build_state(nodes, job):
    """One live store, as on a real server: every eval snapshots it and the
    device mirror stays warm across evals (nomad_tpu.tpu.mirror.MirrorCache)."""
    from nomad_tpu.state import StateStore

    state = StateStore()
    for i, node in enumerate(nodes):
        state.upsert_node(i + 1, node)
    state.upsert_job(N_NODES + 1, job)
    return state


def run_once(state, job, trace_ids=None):
    """One scheduler pass. When ``trace_ids`` is a list, the eval runs
    under a root trace span (the worker posture) so the solver records
    its per-stage spans, and the eval id is appended for later span
    retrieval — the tracing-overhead arm of the headline."""
    import logging

    from nomad_tpu import structs, trace
    from nomad_tpu.scheduler import new_scheduler
    from nomad_tpu.structs import Evaluation, PlanResult, generate_uuid

    class _Planner:
        plan = None

        def submit_plan(self, plan):
            # Real leader-side verification (plan_apply.go evaluatePlan via
            # the native bulk verifier); the raft commit itself is elided.
            from nomad_tpu.server.plan_apply import evaluate_plan

            _Planner.plan = plan
            result = evaluate_plan(state.snapshot(), plan)
            result.alloc_index = N_NODES + 2
            return result, None

        def update_eval(self, ev):
            pass

        def create_eval(self, ev):
            pass

    ev = Evaluation(
        id=generate_uuid(),
        priority=job.priority,
        type=job.type,
        triggered_by=structs.EVAL_TRIGGER_JOB_REGISTER,
        job_id=job.id,
    )
    sched = new_scheduler(
        "tpu-batch", state.snapshot(), _Planner(), logging.getLogger("bench")
    )
    start = time.perf_counter()
    if trace_ids is not None:
        span = trace.get_tracer().start_span(ev.id, "eval", root=True)
        with trace.use_span(span):
            sched.process(ev)
        span.finish()
        trace_ids.append(ev.id)
    else:
        sched.process(ev)
    e2e = time.perf_counter() - start

    plan = _Planner.plan
    placed = sum(len(v) for v in plan.node_allocation.values())
    placed += sum(b.n for b in plan.alloc_batches)
    return e2e, placed


COALESCE_EVALS = 8


def run_coalesced(nodes):
    """Aux phase through the REAL server pipeline: COALESCE_EVALS jobs
    enqueued at the broker, drained by batched workers
    (eval_batch_size, server/worker.py), their device solves stacking into
    vmapped dispatches (ops/coalesce.py), plans through the plan queue and
    applier. The broker-path analog of the reference's optimistic worker
    concurrency (nomad/worker.go:45-125 + eval_broker.go:215-246).
    Returns (wall_seconds, total_placed, dispatches)."""
    from nomad_tpu import structs
    from nomad_tpu.ops.coalesce import GLOBAL_SOLVER
    from nomad_tpu.server import Server, ServerConfig
    from nomad_tpu.structs import Evaluation, generate_uuid

    srv = Server(ServerConfig(
        scheduler_backend="tpu",
        num_schedulers=2,
        eval_batch_size=COALESCE_EVALS,
        periodic_dispatch=False,
    ))
    try:
        for node in nodes:
            srv.raft.apply("node_register", {"node": node})
        jobs = []
        for i in range(2 * COALESCE_EVALS):  # half warmup, half timed
            _nodes, job = build_cluster()
            # Warm jobs use a tiny count on the SAME columnar path (>128
            # rides the water-fill; compile shapes key on node bucket and
            # batch size, not the count value), so warmup doesn't consume
            # the capacity the timed batch is measured against.
            job.task_groups[0].count = (
                129 if i < COALESCE_EVALS else N_TASKS // COALESCE_EVALS
            )
            srv.raft.apply("job_register", {"job": job})
            jobs.append(job)

        # Warmup batch: the SAME concurrent shape as the timed batch, so
        # the vmapped coalesced-dispatch programs (batch-size buckets)
        # compile before timing — steady-state throughput is the metric;
        # cold-compile behavior is covered by the prewarm/nack-touch tests.
        warm_jobs, jobs = jobs[:COALESCE_EVALS], jobs[COALESCE_EVALS:]
        warm_evals = [
            Evaluation(
                id=generate_uuid(), priority=job.priority, type=job.type,
                triggered_by=structs.EVAL_TRIGGER_JOB_REGISTER,
                job_id=job.id, status=structs.EVAL_STATUS_PENDING,
            )
            for job in warm_jobs
        ]
        srv.start()
        srv.raft.apply("eval_update", {"evals": warm_evals})
        _wait_evals_complete(srv, [ev.id for ev in warm_evals], timeout=300.0)
        # Worker drain timing decides which eval-axis batch buckets the
        # warm batch hit; compile the rest deterministically.
        from nomad_tpu.ops.binpack import bucket
        from nomad_tpu.ops.coalesce import warm_batch_shapes

        dc1_nodes = sum(1 for n in nodes if n.datacenter == "dc1")
        warm_batch_shapes(bucket(dc1_nodes))

        evals = [
            Evaluation(
                id=generate_uuid(), priority=job.priority, type=job.type,
                triggered_by=structs.EVAL_TRIGGER_JOB_REGISTER,
                job_id=job.id, status=structs.EVAL_STATUS_PENDING,
            )
            for job in jobs
        ]
        dispatches0 = GLOBAL_SOLVER.dispatches
        start = time.perf_counter()
        srv.raft.apply("eval_update", {"evals": evals})
        _wait_evals_complete(srv, [ev.id for ev in evals], timeout=300.0)
        wall = time.perf_counter() - start

        placed = 0
        for job in jobs:
            placed += sum(
                1 for a in srv.state_store.allocs_by_job(job.id)
                if a.desired_status == structs.ALLOC_DESIRED_STATUS_RUN
            )
        return wall, placed, GLOBAL_SOLVER.dispatches - dispatches0
    finally:
        srv.shutdown()


def run_simload():
    """Control-plane arm: placements/s and plan latency through the FULL
    register→heartbeat→eval→broker→worker→solver→plan_apply→raft path —
    a simcluster scenario against a real ClusterServer over real RPC
    (nomad_tpu/simcluster). The headline above measures the solver in
    isolation; this number is the same metric with the whole control
    plane in the loop, so the two together bound where the pipeline (not
    the kernel) is the ceiling. Scenario via NOMAD_TPU_BENCH_SIMLOAD
    (default steady-1k: cheap enough to ride every capture; the 10k-node
    artifacts are banked by tools/simload.py runs)."""
    from nomad_tpu.simcluster import run_scenario

    name = os.environ.get("NOMAD_TPU_BENCH_SIMLOAD", "steady-1k")
    art = run_scenario(name, seed=42)
    return {
        "scenario": name,
        "n_nodes": art["n_nodes"],
        "placed": art["placements"]["placed"],
        "placements_per_sec": art["placements"]["placements_per_sec"],
        "plan_latency_ms_p50": art["plan_latency_ms"].get("p50_ms"),
        "plan_latency_ms_p95": art["plan_latency_ms"].get("p95_ms"),
        "device_dispatches": art["placements"]["device_dispatches"],
        "broker_ready_peak": art["peaks"]["broker_ready"],
        "plan_queue_depth_peak": art["peaks"]["plan_queue_depth"],
        "heartbeat_timers": art["heartbeat"]["timers"],
        "registration_nodes_per_sec": art["registration"]["nodes_per_sec"],
    }


def _wait_evals_complete(srv, eval_ids, timeout):
    from nomad_tpu import structs

    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        done = [srv.state_store.eval_by_id(i) for i in eval_ids]
        if all(
            d is not None and d.status != structs.EVAL_STATUS_PENDING
            for d in done
        ):
            # A failed/canceled eval must surface as a bench error, not a
            # silently-low placement count.
            bad = {
                d.id: d.status for d in done
                if d.status != structs.EVAL_STATUS_COMPLETE
            }
            if bad:
                raise RuntimeError(f"evals did not complete: {bad}")
            return
        time.sleep(0.02)
    raise TimeoutError(f"evals not complete after {timeout}s")


def _mk_nodes(n, cpu=4000, mem=8192, with_net=True):
    from nomad_tpu import structs
    from nomad_tpu.structs import NetworkResource, Node, Resources

    nodes = []
    for i in range(n):
        res = Resources(cpu=cpu, memory_mb=mem, disk_mb=100 * 1024, iops=150)
        if with_net:
            res.networks = [NetworkResource(
                device="eth0", cidr="192.168.0.0/16",
                ip=f"192.168.{i % 250}.1", mbits=1000,
            )]
        nodes.append(Node(
            id=f"bench-{i:06d}",
            datacenter="dc1",
            name=f"n{i}",
            attributes={"kernel.name": "linux", "driver.exec": "1"},
            resources=res,
            status=structs.NODE_STATUS_READY,
        ))
    return nodes


def _eval_once(state, job, factory, alloc_index):
    """One scheduler pass against a live store; plans verified and applied
    to state (the Harness posture). Returns (e2e_seconds, placed)."""
    import logging

    from nomad_tpu import structs
    from nomad_tpu.scheduler import new_scheduler
    from nomad_tpu.server.plan_apply import evaluate_plan
    from nomad_tpu.structs import Evaluation, generate_uuid

    applied = {"placed": 0}

    class _P:
        def submit_plan(self, plan):
            result = evaluate_plan(state.snapshot(), plan)
            result.alloc_index = alloc_index
            allocs = []
            for lst in result.node_update.values():
                allocs.extend(lst)
            for lst in result.node_allocation.values():
                allocs.extend(lst)
                applied["placed"] += len(lst)
            if allocs:
                state.upsert_allocs(alloc_index, allocs)
            # Columnar results commit columnar, exactly like the FSM.
            if result.alloc_batches:
                state.upsert_alloc_blocks(alloc_index, result.alloc_batches)
                applied["placed"] += sum(b.n for b in result.alloc_batches)
            if result.update_batches:
                state.apply_update_batches(
                    alloc_index, result.update_batches
                )
            return result, None

        def update_eval(self, ev):
            pass

        def create_eval(self, ev):
            pass

    ev = Evaluation(
        id=generate_uuid(), priority=job.priority, type=job.type,
        triggered_by=structs.EVAL_TRIGGER_JOB_REGISTER, job_id=job.id,
    )
    sched = new_scheduler(
        factory, state.snapshot(), _P(), logging.getLogger("bench")
    )
    start = time.perf_counter()
    sched.process(ev)
    return time.perf_counter() - start, applied["placed"]


def _scaled(n):
    """Scale aux-config sizes with the headline override (smoke runs)."""
    return max(8, int(n * (N_NODES / 10_000)))


AUX_RUNS = max(1, int(os.environ.get("NOMAD_TPU_BENCH_AUX_RUNS", 3)))

# Config-5 pass/fail floors, env-overridable for new hardware baselines.
CONFIG5_INPLACE_BAR = float(
    os.environ.get("NOMAD_TPU_CONFIG5_INPLACE_BAR", 100_000)
)
CONFIG5_ROLLED_BAR = float(
    os.environ.get("NOMAD_TPU_CONFIG5_ROLLED_BAR", 5_000)
)


def run_config2():
    """BASELINE config 2: 1k-node / 5k-taskgroup service bin-pack, CPU+mem
    only."""
    from nomad_tpu import structs
    from nomad_tpu.state import StateStore
    from nomad_tpu.structs import Job, Resources, RestartPolicy, Task, TaskGroup, generate_uuid

    n_nodes, count = _scaled(1000), _scaled(5000)
    state = StateStore()
    for i, node in enumerate(_mk_nodes(n_nodes, cpu=14000, mem=30000,
                                       with_net=False)):
        state.upsert_node(i + 1, node)
    job = Job(
        region="global", id=generate_uuid(), name="bench-svc",
        type=structs.JOB_TYPE_SERVICE, priority=50, datacenters=["dc1"],
        task_groups=[TaskGroup(
            name="svc", count=count,
            restart_policy=RestartPolicy(attempts=2, interval=600.0, delay=5.0),
            tasks=[Task(name="t", driver="exec",
                        resources=Resources(cpu=100, memory_mb=256))],
        )],
    )
    state.upsert_job(n_nodes + 1, job)
    _eval_once(StateStoreView(state), job, "tpu-service", n_nodes + 2)  # warm
    # Each measured run gets a fresh alloc-free clone so every sample sees
    # identical initial conditions (a repeat eval on mutated state would
    # diff to zero placements).
    times = []
    placed = 0
    with _quiesced():
        for _ in range(AUX_RUNS):
            e2e, placed = _eval_once(
                StateStoreView(state), job, "tpu-service", n_nodes + 2
            )
            times.append(e2e)
    e2e = statistics.median(times)
    return {
        "n_nodes": n_nodes, "count": count, "placed": placed,
        "e2e_ms": round(e2e * 1000, 2),
        "e2e": _dist(times, warmup=1),
        "placements_per_sec": round(placed / e2e, 1) if e2e else 0,
    }


class StateStoreView:
    """Throwaway shim: a fresh store clone for warmups so the measured run
    sees the original (no existing allocs)."""

    def __new__(cls, state):
        import copy

        from nomad_tpu.state import StateStore

        s = StateStore()
        for i, node in enumerate(state.nodes()):
            s.upsert_node(i + 1, node)
        for job in state.jobs():
            s.upsert_job(10_000_000, job)
        return s


def run_config4():
    """BASELINE config 4: system scheduler, one-per-node with hard
    constraints, 10k nodes."""
    from nomad_tpu import structs
    from nomad_tpu.state import StateStore
    from nomad_tpu.structs import (
        Constraint, Job, Resources, RestartPolicy, Task, TaskGroup,
        generate_uuid,
    )

    n_nodes = _scaled(10_000)
    state = StateStore()
    for i, node in enumerate(_mk_nodes(n_nodes, with_net=False)):
        state.upsert_node(i + 1, node)
    job = Job(
        region="global", id=generate_uuid(), name="bench-sys",
        type=structs.JOB_TYPE_SYSTEM, priority=50, datacenters=["dc1"],
        constraints=[Constraint(
            l_target="$attr.kernel.name", r_target="linux", operand="=",
        )],
        task_groups=[TaskGroup(
            name="sys", count=1,
            restart_policy=RestartPolicy(attempts=2, interval=600.0, delay=5.0),
            tasks=[Task(name="t", driver="exec",
                        resources=Resources(cpu=50, memory_mb=64))],
        )],
    )
    state.upsert_job(n_nodes + 1, job)
    _eval_once(StateStoreView(state), job, "tpu-system", n_nodes + 2)  # warm
    # Steady-state posture: the mirror for each measured clone's node-table
    # generation is made resident BEFORE its timed eval (repeat evals in
    # production share a resident mirror; a cold build is not part of the
    # config-4 claim). Every sample runs on a fresh alloc-free clone so
    # the system scheduler has a full one-per-node placement to do.
    from nomad_tpu.server.plan_apply import _node_table
    from nomad_tpu.tpu.mirror import GLOBAL_MIRROR_CACHE

    times = []
    placed = 0
    with _quiesced():
        for _ in range(AUX_RUNS):
            clone = StateStoreView(state)
            snap = clone.snapshot()
            GLOBAL_MIRROR_CACHE.get(snap, job.datacenters)
            # The applier's columnar node table is likewise resident in
            # production (keyed by store generation, built by whichever
            # plan first verifies against it) — a cold build is not part
            # of the per-eval claim.
            _node_table(snap)
            e2e, placed = _eval_once(clone, job, "tpu-system", n_nodes + 2)
            times.append(e2e)
    e2e = statistics.median(times)
    return {
        "n_nodes": n_nodes, "placed": placed,
        "e2e_ms": round(e2e * 1000, 2),
        "e2e": _dist(times, warmup=1),
        "placements_per_sec": round(placed / e2e, 1) if e2e else 0,
    }


def run_config5():
    """BASELINE config 5: 50k nodes, existing allocs, rolling-update diff +
    anti-affinity — the object-diff and in-place machinery
    (/root/reference/scheduler/util.go:403-416 evictAndPlace)."""
    from nomad_tpu import structs
    from nomad_tpu.state import StateStore
    from nomad_tpu.structs import (
        Job, Resources, RestartPolicy, Task, TaskGroup, UpdateStrategy,
        generate_uuid,
    )

    n_nodes, count = _scaled(50_000), _scaled(10_000)
    state = StateStore()
    for i, node in enumerate(_mk_nodes(n_nodes, with_net=False)):
        state.upsert_node(i + 1, node)
    job = Job(
        region="global", id=generate_uuid(), name="bench-roll",
        type=structs.JOB_TYPE_SERVICE, priority=50, datacenters=["dc1"],
        update=UpdateStrategy(stagger=10.0, max_parallel=_scaled(1000)),
        task_groups=[TaskGroup(
            name="web", count=count,
            restart_policy=RestartPolicy(attempts=2, interval=600.0, delay=5.0),
            tasks=[Task(name="t", driver="exec",
                        resources=Resources(cpu=100, memory_mb=128))],
        )],
    )
    state.upsert_job(n_nodes + 1, job)
    # Phase 1 (unmeasured): initial placement seeds the existing allocs.
    _eval_once(state, job, "tpu-service", n_nodes + 2)
    # Deep-copies: existing allocs embed the job object, so an in-place
    # mutation would make the diff see no change.
    import copy

    # Phase 2a (measured): resource-only bump -> in-place update of all
    # `count` existing allocs (tasks_updated false, util.go:265-302; fit
    # re-checked with the new resources, util.go:344-358).
    job2 = copy.deepcopy(job)
    job2.task_groups[0].tasks[0].resources.cpu += 7
    state.upsert_job(n_nodes + 3, job2)
    with _quiesced():
        inplace_e2e, _ = _eval_once(state, job2, "tpu-service", n_nodes + 4)

    # Phase 2b (measured): env change -> destructive update; rolling
    # evict+place capped at max_parallel (evictAndPlace, util.go:403-416)
    # with anti-affinity ranking against the survivors.
    job3 = copy.deepcopy(job2)
    job3.task_groups[0].tasks[0].env = {"V": "2"}
    state.upsert_job(n_nodes + 5, job3)
    with _quiesced():
        e2e, placed = _eval_once(state, job3, "tpu-service", n_nodes + 6)
    inplace_rate = round(count / inplace_e2e, 1) if inplace_e2e else 0
    rolled_rate = round(placed / e2e, 1) if e2e else 0
    return {
        "n_nodes": n_nodes, "existing": count,
        "inplace_updated": count,
        "inplace_e2e_ms": round(inplace_e2e * 1000, 2),
        "inplace_updates_per_sec": inplace_rate,
        "rolled": placed, "max_parallel": _scaled(1000),
        "e2e_ms": round(e2e * 1000, 2),
        "rolled_updates_per_sec": rolled_rate,
        # Pass/fail bars (full 50k-node scale): conservative floors under
        # the worst CPU-backend capture on record (BENCH_SELF_r04: in-place
        # 10k/58ms ≈ 171k/s, rolled 1k/120ms ≈ 8.3k/s) — a regression
        # below them means the update machinery got slower, not noisier.
        # Only asserted at full scale: smoke runs shrink the task count
        # faster than the fixed per-eval overheads they still pay.
        "bar_inplace_updates_per_sec": CONFIG5_INPLACE_BAR,
        "bar_rolled_updates_per_sec": CONFIG5_ROLLED_BAR,
        "pass": (
            None if n_nodes < 50_000
            else bool(inplace_rate >= CONFIG5_INPLACE_BAR
                      and rolled_rate >= CONFIG5_ROLLED_BAR)
        ),
        # Phases mutate state (rolling update over the phase-1 allocs), so
        # each figure is a single sample; dispersion comes from the
        # repeatable configs.
        "runs": 1, "warmup_runs": 0,
    }


BREAKDOWN = os.environ.get("NOMAD_TPU_BENCH_BREAKDOWN", "1") == "1"
# Default sweep scales track the headline cluster size so smoke runs
# (reduced NOMAD_TPU_BENCH_NODES) don't pay for a 32k-node mirror.
_BREAKDOWN_SCALES_ENV = os.environ.get("NOMAD_TPU_BENCH_BREAKDOWN_SCALES", "")
BREAKDOWN_SCALES = tuple(
    int(s) for s in _BREAKDOWN_SCALES_ENV.split(",") if s
) if _BREAKDOWN_SCALES_ENV else tuple(
    s for s in (1024, 4096, 10000, 32768) if s <= 4 * N_NODES
) or (N_NODES,)


def run_breakdown(scales=BREAKDOWN_SCALES):
    """Device-time accounting: where does a solve's wall time go?

    Splits the production water-fill solve into host staging / H2D
    transfer / device execute / D2H readback, with bytes moved, at several
    node scales: the transfer+readback rows carry the host<->device cost
    that the aggregate solve_ms can't attribute (SURVEY §7 latency
    budget).

    Protocol per scale n (count = 10n tasks, the headline's ratio):
    - staging:  NodeMirror construction — host tensorization; device puts
                are dispatched async inside it, so this is host wall.
    - transfer: block_until_ready on the mirror's node tensors + clean
                usage — drains the H2D copies staged above; bytes counted.
    - execute:  solve_waterfill dispatch + block_until_ready on the
                device-resident counts (post-warmup, so no compile).
    - readback: device_get of the counts — D2H wire time; bytes counted.
    - warm_e2e: dispatch+block+readback in one timed pass, warm mirror —
                the steady-state per-eval device cost.
    """
    import jax

    from nomad_tpu.ops.binpack import device_const, solve_waterfill
    from nomad_tpu.tpu.mirror import NodeMirror
    from nomad_tpu.trace import StageTimer

    ask = (100, 128, 0, 0)  # the headline task's resource vector
    penalty_dev = device_const("f32", 0.0)
    bw_ask_dev = device_const("i32", 0)
    sweep = []
    for n in scales:
        count = 10 * n
        nodes_list = _mk_nodes(n, with_net=False)

        # Stage cuts through the SAME StageTimer the production solver's
        # trace spans use (nomad_tpu.trace) — one shared stage-timing
        # path, not a second parallel timer.
        prep_st = StageTimer()
        with prep_st.stage("staging"):
            mirror = NodeMirror(nodes_list)
            usage = mirror.clean_usage()
            eligible = mirror.device_mask(None, set(), None, None)[0]
        inputs = (mirror.total, mirror.sched_cap, mirror.bw_avail,
                  eligible, *usage)
        with prep_st.stage("transfer"):
            for arr in inputs:
                arr.block_until_ready()
        prep_ms = prep_st.durations_ms()
        transfer_bytes = int(sum(getattr(a, "nbytes", 0) for a in inputs))

        ask_dev = device_const("ask", ask)
        count_dev = device_const("i32", count)
        used0, job_count0, tg_count0, bw_used0 = usage

        def dispatch():
            return solve_waterfill(
                mirror.total, mirror.sched_cap, used0, job_count0,
                tg_count0, mirror.bw_avail, bw_used0, eligible, ask_dev,
                bw_ask_dev, count_dev, penalty_dev, False, False,
            )

        counts, unplaced = dispatch()  # warmup: compile for this bucket
        counts.block_until_ready()

        exec_times, read_times, e2e_times = [], [], []
        for _ in range(RUNS):
            st = StageTimer()
            with st.stage("execute"):
                counts, unplaced = dispatch()
                counts.block_until_ready()
                unplaced.block_until_ready()
            with st.stage("readback"):
                counts_host, _ = jax.device_get((counts, unplaced))
            d = st.durations_ms()
            exec_times.append(d["execute"] / 1000.0)
            read_times.append(d["readback"] / 1000.0)
            t = time.perf_counter()
            c2, u2 = dispatch()
            jax.device_get((c2, u2))
            e2e_times.append(time.perf_counter() - t)

        placed = int(counts_host.sum())
        warm_e2e = statistics.median(e2e_times)
        sweep.append({
            "n_nodes": n,
            "count": count,
            "placed": placed,
            "staging_ms": round(prep_ms.get("staging", 0.0), 2),
            "transfer_ms": round(prep_ms.get("transfer", 0.0), 2),
            "transfer_bytes": transfer_bytes,
            "execute_ms_p50": round(
                statistics.median(exec_times) * 1000, 3),
            "readback_ms_p50": round(
                statistics.median(read_times) * 1000, 3),
            "readback_bytes": int(counts_host.nbytes + 4),
            "warm_e2e_ms_p50": round(warm_e2e * 1000, 3),
            "placements_per_sec_warm": round(placed / warm_e2e, 1),
        })
    return sweep


_NODE_SWEEP_ENV = os.environ.get("NOMAD_TPU_BENCH_NODE_SWEEP", "")
NODE_SWEEP_SCALES = tuple(
    int(s) for s in _NODE_SWEEP_ENV.split(",") if s
) if _NODE_SWEEP_ENV else (1024, 10_000, 100_000)


def run_node_sweep(scales=NODE_SWEEP_SCALES, count=420):
    """Node-axis sweep to 100k: the ROADMAP item 1 proof arm.

    Holds the ask fixed (420 tasks — the steady-10k workload's job
    shape) and sweeps the NODE axis through 100k, measuring the warm
    water-fill solve wall per scale. The claim under test: with padded
    buffers, bucketed compiles, and (when configured) the node axis
    sharded over a device mesh, a 100k-node cell's warm per-eval solve
    stays in the same cost class as 10k — the verdict field pins the
    ratio. Uses the same clean-state staging as run_breakdown; the
    mirror build cost is reported but NOT in the warm wall (steady state
    reuses the resident mirror via MirrorCache)."""
    import jax

    from nomad_tpu.ops.binpack import device_const, solve_waterfill
    from nomad_tpu.tpu.mirror import NodeMirror

    ask_dev = device_const("ask", (100, 128, 0, 0))
    penalty_dev = device_const("f32", 0.0)
    bw_ask_dev = device_const("i32", 0)
    count_dev = device_const("i32", count)
    sweep = []
    for n in scales:
        nodes_list = _mk_nodes(n, with_net=False)
        t0 = time.perf_counter()
        mirror = NodeMirror(nodes_list)
        usage = mirror.clean_usage()
        eligible = mirror.device_mask(None, set(), None, None)[0]
        for arr in (mirror.total, mirror.sched_cap, eligible, *usage):
            arr.block_until_ready()
        staging_ms = (time.perf_counter() - t0) * 1000.0
        used0, job_count0, tg_count0, bw_used0 = usage

        def dispatch():
            return solve_waterfill(
                mirror.total, mirror.sched_cap, used0, job_count0,
                tg_count0, mirror.bw_avail, bw_used0, eligible, ask_dev,
                bw_ask_dev, count_dev, penalty_dev, False, False,
            )

        counts, unplaced = dispatch()  # compile for this node bucket
        counts.block_until_ready()
        times = []
        for _ in range(RUNS):
            t = time.perf_counter()
            c, u = dispatch()
            jax.device_get((c, u))
            times.append(time.perf_counter() - t)
        counts_host, unplaced_host = jax.device_get((counts, unplaced))
        placed = count - int(unplaced_host)
        warm_ms = statistics.median(times) * 1000.0
        sweep.append({
            "n_nodes": n,
            "padded": mirror.padded,
            "count": count,
            "placed": placed,
            "staging_ms": round(staging_ms, 2),
            "warm_solve_ms_p50": round(warm_ms, 3),
            "device_ms_per_placement": round(
                warm_ms / max(placed, 1), 4),
        })
        del mirror, usage, eligible, nodes_list, counts, unplaced
    by_n = {row["n_nodes"]: row for row in sweep}
    verdict = {}
    if 10_000 in by_n and 100_000 in by_n:
        ratio = (by_n[100_000]["warm_solve_ms_p50"]
                 / max(by_n[10_000]["warm_solve_ms_p50"], 1e-9))
        verdict = {
            "warm_100k_over_10k": round(ratio, 3),
            "same_cost_class_2x": ratio <= 2.0,
        }
    return {"sweep": sweep, **verdict}


STAGING_DELTA_SCALES = tuple(
    s for s in (1024, 4096, 10_000) if s <= N_NODES
) or (N_NODES,)


def run_staging_delta(scales=STAGING_DELTA_SCALES):
    """Delta-mirror arm: warm staging cost after a SINGLE node write.

    The BENCH_r05 breakdown showed staging (mirror build + masks + clean
    usage) at 21.57ms for 10k nodes while the device solve itself was
    ~1.3ms — and MirrorCache used to invalidate the WHOLE mirror on any
    node write. This arm measures what one node write actually costs now:
    ``delta`` re-stages through MirrorCache's change-log roll forward
    (one row patched + row-sliced device update), ``full`` forces the old
    posture (a cold cache rebuilding everything). Both stage to the same
    definition as the breakdown's staging row: mirror + eligibility mask
    + clean usage, blocked until device-resident."""
    from nomad_tpu.state import StateStore
    from nomad_tpu.tpu.mirror import MirrorCache

    dcs = ["dc1"]

    def stage(snap, cache):
        _nodes, m = cache.get(snap, dcs)
        usage = m.clean_usage()
        eligible = m.device_mask(None, set(), None, None)[0]
        for arr in (m.total, m.sched_cap, m.bw_avail, eligible, *usage):
            arr.block_until_ready()
        return m

    sweep = []
    for n in scales:
        nodes = _mk_nodes(n, with_net=False)
        state = StateStore()
        idx = 0
        for node in nodes:
            idx += 1
            state.upsert_node(idx, node)
        cache = MirrorCache()
        stage(state.snapshot(), cache)  # initial build (not measured)

        def write_one(r):
            # One node write: resource drift on a single node — the row
            # actually changes, so the delta path pays its full cost
            # (patch + row restage), not just a cache hit.
            nonlocal idx
            victim = state.node_by_id(nodes[r % n].id).copy()
            victim.resources = victim.resources.copy()
            victim.resources.cpu += 1
            idx += 1
            state.upsert_node(idx, victim)

        write_one(0)
        stage(state.snapshot(), cache)  # warm the scatter-update shapes

        delta_times, full_times = [], []
        with _quiesced():
            for r in range(1, RUNS + 1):
                write_one(r)
                snap = state.snapshot()
                t0 = time.perf_counter()
                stage(snap, cache)
                delta_times.append(time.perf_counter() - t0)
                # Forced full rebuild of the SAME state: a cold cache.
                t0 = time.perf_counter()
                stage(snap, MirrorCache())
                full_times.append(time.perf_counter() - t0)
        stats = cache.stats()
        delta_p50 = statistics.median(delta_times)
        full_p50 = statistics.median(full_times)
        sweep.append({
            "n_nodes": n,
            "delta_staging_ms_p50": round(delta_p50 * 1000, 3),
            "full_staging_ms_p50": round(full_p50 * 1000, 3),
            "speedup": round(full_p50 / delta_p50, 1) if delta_p50 else 0,
            "delta_rolls": stats["delta_rolls"],
            "full_rebuilds": stats["full_rebuilds"],
            "rows_restaged": stats["rows_restaged"],
            "runs": len(delta_times),
        })
    return sweep


def _solve_paths() -> dict:
    """Coalescer dispatches by the program family that carried them
    ("pallas" / "jnp" water-fill, "exact" greedy scan)."""
    from nomad_tpu.ops.coalesce import GLOBAL_SOLVER

    return dict(GLOBAL_SOLVER.paths)


def _measure_headline():
    """The one headline measurement protocol (config 3): build, warm one
    pass, clear, RUNS timed passes under a quiesced GC, distributions.
    Returns (solve_dist, e2e_dist, placed, nodes,
    trace_info): the headline dists are measured with tracing DISABLED
    (comparable with prior rounds); ``trace_info`` carries a second,
    tracing-ENABLED set of RUNS over the same state — the per-stage
    solver spans (one shared stage-timing path with the breakdown) and
    the measured overhead of leaving tracing on."""
    from nomad_tpu import trace as _trace

    nodes, job = build_cluster()
    state = build_state(nodes, job)
    _TimingStack.install()

    # Warmup: compile caches for the shape buckets
    run_once(state, job)
    _TimingStack.solve_times.clear()

    # Interleaved arms: each iteration runs one tracing-DISABLED and one
    # tracing-ENABLED pass (the production worker posture: each traced
    # eval under a root span, so solver stage spans record). Interleaving
    # matters — same-box drift between two sequential sets has been
    # observed to exceed any real tracing cost, which would make a
    # sequential overhead figure pure noise.
    tracer = _trace.configure(max_traces=2 * RUNS + 8, enabled=True)
    trace_ids = []
    e2e_times, e2e_traced = [], []
    solve_untraced, solve_traced = [], []
    placed = 0
    with _quiesced():
        for _ in range(RUNS):
            tracer.enabled = False
            mark = len(_TimingStack.solve_times)
            e2e, placed = run_once(state, job)
            e2e_times.append(e2e)
            solve_untraced.extend(_TimingStack.solve_times[mark:])

            tracer.enabled = True
            mark = len(_TimingStack.solve_times)
            e2e, _p = run_once(state, job, trace_ids=trace_ids)
            e2e_traced.append(e2e)
            solve_traced.extend(_TimingStack.solve_times[mark:])

    if not solve_untraced:
        raise RuntimeError(
            "no device solves recorded — the TPU factories fell back "
            "to the host scheduler mid-run"
        )

    if not solve_traced:
        # A traced-arm-only device fallback must surface as an error, not
        # be averaged into a nonsensical overhead figure.
        trace_info = {"error": "no traced solves recorded — device "
                               "fallback during the traced arm"}
    else:
        stage_samples = {}
        tracer = _trace.get_tracer()
        for tid in trace_ids:
            for s in tracer.get_trace(tid) or []:
                if (s["name"].startswith("solver.")
                        and s["duration_ms"] is not None):
                    stage_samples.setdefault(
                        s["name"][len("solver."):], []
                    ).append(s["duration_ms"])
        sp50_off = statistics.median(solve_untraced)
        sp50_on = statistics.median(solve_traced)
        trace_info = {
            "solve_ms_p50_traced": round(sp50_on * 1000, 3),
            "e2e_eval_ms_p50_traced": round(
                statistics.median(e2e_traced) * 1000, 3),
            # The acceptance bound: < 5% warm-path regression with
            # tracing on.
            "overhead_pct": (
                round((sp50_on / sp50_off - 1.0) * 100.0, 2)
                if sp50_off else 0.0
            ),
            "stages_ms_p50": {
                k: round(statistics.median(v), 4)
                for k, v in stage_samples.items()
            },
        }

    return (
        _dist(solve_untraced, warmup=1),
        _dist(e2e_times, warmup=1),
        placed,
        nodes,
        trace_info,
    )


def main():
    device = None
    _start_watchdog()
    try:
        # Claim the device in this process (nomad_tpu.scheduler, which
        # also places the compile cache); no device, no bench.
        from nomad_tpu.scheduler import acquire_device

        device = acquire_device()
        if device["platform"] != "tpu" and not ALLOW_CPU:
            raise RuntimeError(
                f"bench requires a TPU but JAX initialized on "
                f"{device['platform']!r}; set NOMAD_TPU_BENCH_ALLOW_CPU=1 "
                "for a local run labeled as such"
            )

        solve_dist, e2e_dist, placed, nodes, trace_info = _measure_headline()
        solve_p50 = solve_dist["p50_ms"] / 1000
        e2e_p50 = e2e_dist["p50_ms"] / 1000
        placements_per_sec = placed / solve_p50

        coalesce_wall, coalesce_placed, coalesce_dispatches = (
            run_coalesced(nodes)
        )

        # BASELINE configs 2 / 4 / 5 (config 1 is the unit-test scale
        # covered by the suite; config 3 is the headline above). A phase
        # that raises still appears in the JSON, under its name, and
        # fails the run's exit code.
        aux = {}
        phases = [("config2", run_config2), ("config4", run_config4),
                  ("config5", run_config5),
                  ("staging_delta", run_staging_delta),
                  ("node_sweep", run_node_sweep), ("simload", run_simload)]
        if BREAKDOWN:
            phases.append(("breakdown", run_breakdown))
        for name, fn in phases:
            try:
                aux[name] = fn()
            except Exception as e:
                traceback.print_exc(file=sys.stderr)
                aux[name] = {"error": f"{type(e).__name__}: {e}"}
        failed = sorted(k for k, v in aux.items()
                        if isinstance(v, dict) and "error" in v)

        figures = {
            "placements_per_sec": round(placements_per_sec, 1),
            "solve_ms_p50": round(solve_p50 * 1000, 2),
            "e2e_eval_ms_p50": round(e2e_p50 * 1000, 2),
            "solve_ms": solve_dist,
            "e2e_eval_ms": e2e_dist,
            "tracing": trace_info,
            "placed": placed,
            "n_nodes": N_NODES,
            "n_tasks": N_TASKS,
            "coalesced_evals": COALESCE_EVALS,
            "coalesced_wall_ms": round(coalesce_wall * 1000, 2),
            "coalesced_placed": coalesce_placed,
            "coalesced_dispatches": coalesce_dispatches,
            "solve_paths": _solve_paths(),
            **aux,
        }
        payload = {
            "metric": METRIC,
            "unit": "placements/s",
            "backend": device["platform"],
            "device": device,
        }
        if device["platform"] == "tpu":
            payload["value"] = figures["placements_per_sec"]
            payload["vs_baseline"] = round(
                placements_per_sec / TARGET_PLACEMENTS_PER_SEC, 3
            )
            payload.update(figures)
        else:
            # Not a device measurement: nothing of it may sit under the
            # device metric's ``value``.
            payload["cpu_run"] = figures
        if failed:
            payload["error"] = f"aux phase(s) raised: {failed}"
        emit(payload)
    except BaseException as e:  # always emit the JSON line, never a traceback
        traceback.print_exc(file=sys.stderr)
        emit({
            "metric": METRIC,
            "unit": "placements/s",
            "backend": device["platform"] if device else "unknown",
            "error": f"{type(e).__name__}: {e}",
        })
        _exit(1)
    _exit(1 if failed else 0)


def _exit(code: int) -> None:
    """Exit without interpreter teardown: daemon threads (shape warmer,
    broker timers) may sit inside an XLA compile, and finalizing python
    under them aborts the process (rc 134) AFTER the JSON was emitted.
    The one-line contract is already flushed; skip teardown entirely."""
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)


if __name__ == "__main__":
    main()
