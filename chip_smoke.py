#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the served placement path runs
on the chip.

ONE process, the first and only one to touch JAX, drives the system the
way a user does and checks what comes out against the plain reference:

  device    acquire the accelerator through nomad_tpu.scheduler (which
            places the compile cache) and REQUIRE platform "tpu";
  kernel    compile and run the batched Pallas water-fill at the 16,384
            node bucket for every coalesced width and at the 131,072
            bucket, against the jnp water-fill on the same device arrays;
  steady-10k / burst-100k
            the banked scale scenarios through ClusterServer + SimFleet
            over real RPC (nomad_tpu.simcluster, as tools/simload.py runs
            them), scheduler_backend="tpu", seed 42, contrast arms off —
            then the scheduler/ host oracle on the same seeded cluster;
  http      a dev agent's HTTP front: register one more job through
            ApiClient, read its evaluation and allocations back.

It fails (exit != 0, reasons in the JSON) when a phase raised, a placed
count is off, no dispatch reached the device, the host oracle disagrees,
the circuit breaker ever left ``closed``, an eval was routed to the host
scheduler, or the selected water-fill kernel was not the one that ran.
What it reports besides — solve paths, batch widths, buckets, compile
events and seconds, phase walls — are facts about this run, not metrics.

The last two lines of stdout are JSON objects: first the full report
(phases, failures, compiles — also written to chiprun_out/chip_smoke.json),
then, last, the result and nothing else:
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}``.
Without an accelerator it prints no result and exits 2; ``--dry-run-cpu``
is the only way to run it off-chip: a tiny (few hundred nodes,
interpret-mode kernel) dry run whose output says so.

    python chip_smoke.py                 # on the chip, through the chip tool
    python chip_smoke.py --dry-run-cpu   # control flow only, on a CPU
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import logging
import os
import sys
import time
import traceback

# steady-10k's canonical event digest at seed 42 on the CPU backend,
# byte-equal r08 -> r18 (SIMLOAD_steady-10k_s42_r18.json). Reported
# against, not required: TPU arithmetic may break score ties differently.
STEADY_10K_CPU_DIGEST = (
    "2318d581f27c35f7eb6e534fe200cbb08bee52c69aec175e3538fd77020a5b8a")

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


class CompileLog:
    """Every backend compile JAX reports, with the phase it landed in."""

    def __init__(self):
        self.phase = "device"
        self.events = []  # (phase, fun_name, seconds)
        self.cache = {"hits": 0, "misses": 0}

    def install(self) -> None:
        from jax import monitoring

        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event, seconds, **kw) -> None:
        if event == BACKEND_COMPILE:
            self.events.append((self.phase, kw.get("fun_name", "?"), seconds))

    def _event(self, event, **kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache["misses"] += 1

    def of_phase(self, phase: str) -> dict:
        by_name: dict = {}
        for ph, name, secs in self.events:
            if ph == phase:
                by_name.setdefault(name, []).append(round(secs, 3))
        return {
            "events": sum(len(v) for v in by_name.values()),
            "seconds_total": round(
                sum(s for v in by_name.values() for s in v), 3),
            "seconds_by_program": dict(sorted(by_name.items())),
        }


def _versions() -> dict:
    from importlib import metadata

    out = {}
    for pkg in ("jax", "jaxlib", "libtpu"):
        try:
            out[pkg] = metadata.version(pkg)
        except metadata.PackageNotFoundError:
            out[pkg] = None
    return out


def _cache_entries(path) -> int:
    try:
        return len(os.listdir(path)) if path else 0
    except FileNotFoundError:
        return 0


def _counter(suffix: str) -> float:
    """Process-lifetime sum of one telemetry counter, by key suffix."""
    from nomad_tpu import telemetry

    sink = telemetry.get_global().sink
    if not hasattr(sink, "cumulative"):
        return 0.0
    counters, _samples = sink.cumulative()
    return sum(v[0] for k, v in counters.items() if k.endswith(suffix))


# -- kernel phase -------------------------------------------------------------


def _instance(rng, n: int, kind: str):
    """One solve's inputs. ``sim`` is the simcluster shape (uniform nodes,
    partly filled — every score ties); ``random`` is heterogeneous."""
    import numpy as np

    if kind == "random":
        total = rng.integers(100, 5000, size=(n, 4)).astype(np.int32)
        used = (total * rng.uniform(0, 0.9, size=(n, 4))).astype(np.int32)
        jc = rng.integers(0, 3, size=n).astype(np.int32)
        bw_avail = rng.integers(0, 1000, size=n).astype(np.int32)
        bw_used = (bw_avail * rng.uniform(0, 1.0, size=n)).astype(np.int32)
        elig = rng.random(n) < 0.8
        ask = rng.integers(1, 300, size=4).astype(np.int32)
        bw_ask, penalty = int(rng.integers(0, 50)), 10.0
        count = int(rng.integers(n // 2, 3 * n))
    else:
        total = np.tile(np.array([4000, 8192, 102400, 150], np.int32), (n, 1))
        k = rng.integers(0, 12, size=n).astype(np.int32)
        used = np.stack([k * 100, k * 128, k * 0, k * 0], axis=1)
        used = used.astype(np.int32)
        jc = np.zeros(n, np.int32)
        bw_avail = np.full(n, 1000, np.int32)
        bw_used = np.zeros(n, np.int32)
        elig = np.arange(n) < int(n * 0.61)
        ask = np.array([100, 128, 0, 0], np.int32)
        bw_ask, penalty = 0, 5.0
        count = min(12_500, 2 * n)
    return (total, total[:, :2].astype(np.float32), used, jc,
            np.zeros(n, np.int32), bw_avail, bw_used, elig, ask,
            np.int32(bw_ask), np.int32(count), np.float32(penalty))


def _sound(rows, counts) -> bool:
    """Every placement on an eligible node, no node over capacity, no
    more placed than asked."""
    ok = True
    for r, c in zip(rows, counts):
        total, _cap, used, _jc, _tc, bw_avail, bw_used, elig, ask, bw_ask, \
            count, _pen = r
        on = c > 0
        ok &= bool((c[~elig] == 0).all()) and bool((c >= 0).all())
        ok &= bool(((used + c[:, None] * ask[None, :]) <= total)[on].all())
        ok &= bool(((bw_used + c * bw_ask) <= bw_avail)[on].all())
        ok &= int(c.sum()) <= int(count)
    return ok


def kernel_phase(dry_run: bool, seed: int) -> dict:
    """The Pallas water-fill vs the jnp water-fill on the same device
    arrays. Require: equal placed totals and soundness. Report: per-node
    bit-equality, and first-call (compile + run) seconds."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from nomad_tpu.ops import pallas_solve
    from nomad_tpu.ops.coalesce import solve_waterfill_batched

    shapes = ([(256, 1), (256, 2), (2048, 1)] if dry_run else
              [(16384, 1), (16384, 2), (16384, 4), (16384, 8), (131072, 1)])
    out = {"interpret": dry_run, "shapes": [], "failed": []}
    for n, b in shapes:
        rng = np.random.default_rng(seed + n + b)
        rows = [_instance(rng, n, "sim" if i % 2 == 0 else "random")
                for i in range(b)]
        args = [jnp.asarray(np.stack([r[i] for r in rows]))
                for i in range(12)]
        t0 = time.perf_counter()
        c1, r1 = jax.block_until_ready(
            pallas_solve.solve_waterfill_pallas_batched(
                *args, False, False, interpret=dry_run))
        pallas_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        c0, r0 = jax.block_until_ready(
            solve_waterfill_batched(*args, False, False))
        jnp_s = time.perf_counter() - t0
        c0, r0, c1, r1 = (np.asarray(x) for x in (c0, r0, c1, r1))
        rec = {
            "node_bucket": n, "width": b,
            "pallas_first_call_seconds": round(pallas_s, 3),
            "jnp_first_call_seconds": round(jnp_s, 3),
            "placed_pallas": int(c1.sum()), "placed_jnp": int(c0.sum()),
            "placed_equal": bool((c1.sum(axis=1) == c0.sum(axis=1)).all()
                                 and (r0 == r1).all()),
            "sound": _sound(rows, c1),
            "bit_equal_per_node": bool((c0 == c1).all()),
            "nodes_differing": int((c0 != c1).sum()),
        }
        out["shapes"].append(rec)
        if not (rec["placed_equal"] and rec["sound"]):
            out["failed"].append(f"n={n} b={b}")
    return out


# -- scenario phases ----------------------------------------------------------


def _specs(dry_run: bool):
    """(spec, expected placements). Full size is the banked scenarios as
    they stand; the dry run keeps their injectors and shrinks the cell."""
    from nomad_tpu.simcluster import SCENARIOS
    from nomad_tpu.simcluster.workload import (
        BatchBurstInjector,
        NodeRefreshInjector,
        SteadyServiceInjector,
    )

    steady = dataclasses.replace(
        SCENARIOS["steady-10k"], contrast_overrides=None)
    burst = dataclasses.replace(
        SCENARIOS["burst-100k"], contrast_overrides=None)
    if not dry_run:
        return [(steady, 10_080), (burst, 100_000)]
    steady = dataclasses.replace(
        steady, n_nodes=256, quiesce_timeout=90.0, ack_cap=20,
        injectors=lambda seed: [
            SteadyServiceInjector(seed, jobs=4, tasks_per_job=200, over=1.5),
            NodeRefreshInjector(seed, count=4, every=0.5, start=0.2,
                                until=1.4),
        ])
    burst = dataclasses.replace(
        burst, n_nodes=256, quiesce_timeout=90.0,
        injectors=lambda seed: [BatchBurstInjector(
            seed, bursts=1, jobs_per_burst=8, tasks_per_job=300)])
    return [(steady, 800), (burst, 2400)]


class _OraclePlanner:
    """The scheduler test harness's planner: applies every plan straight
    to its own state store (tests/sched_harness.py Harness)."""

    def __init__(self):
        from nomad_tpu.state import StateStore

        self.state = StateStore()
        self.index = 0

    def next_index(self) -> int:
        self.index += 1
        return self.index

    def submit_plan(self, plan):
        from nomad_tpu.structs import PlanResult

        index = self.next_index()
        allocs = []
        for group in (plan.node_update, plan.node_allocation):
            for alloc_list in group.values():
                allocs.extend(alloc_list)
        for batch in list(plan.alloc_batches) + list(plan.update_batches):
            allocs.extend(batch.materialize())
        allocs.extend(plan.failed_allocs)
        self.state.upsert_allocs(index, allocs)
        return PlanResult(
            node_update=plan.node_update,
            node_allocation=plan.node_allocation,
            alloc_batches=plan.alloc_batches,
            update_batches=plan.update_batches, alloc_index=index,
        ), None

    def update_eval(self, ev) -> None:
        pass

    def create_eval(self, ev) -> None:
        pass


def _running(allocs):
    from nomad_tpu import structs

    return [a for a in allocs
            if a.desired_status == structs.ALLOC_DESIRED_STATUS_RUN]


def host_oracle(nodes, jobs) -> dict:
    """What the scheduler/ host oracle places, job by job, on the same
    cluster: {job_id: placed}. Never touches jax."""
    from nomad_tpu import structs
    from nomad_tpu.scheduler import new_scheduler
    from nomad_tpu.structs import Evaluation, generate_uuid

    log = logging.getLogger("chip_smoke.oracle")
    planner = _OraclePlanner()
    for node in nodes:
        planner.state.upsert_node(planner.next_index(), node)
    for job in jobs:
        planner.state.upsert_job(planner.next_index(), job)
        ev = Evaluation(
            id=generate_uuid(), priority=job.priority, type=job.type,
            triggered_by=structs.EVAL_TRIGGER_JOB_REGISTER, job_id=job.id)
        new_scheduler(job.type, planner.state.snapshot(), planner,
                      log).process(ev)
    snap = planner.state.snapshot()
    return {job.id: len(_running(snap.allocs_by_job(job.id)))
            for job in jobs}


def _eligible_ids(snap, job) -> set:
    """Node ids the host oracle's feasibility chain admits for ``job``."""
    from nomad_tpu.scheduler.context import EvalContext
    from nomad_tpu.scheduler.feasible import (
        ConstraintIterator,
        DriverIterator,
        StaticIterator,
    )
    from nomad_tpu.scheduler.util import (
        ready_nodes_in_dcs,
        task_group_constraints,
    )
    from nomad_tpu.structs import Plan

    ctx = EvalContext(snap, Plan(), logging.getLogger("chip_smoke.oracle"))
    ids = None
    for tg in job.task_groups:
        constr = task_group_constraints(tg)
        chain = ConstraintIterator(
            ctx, StaticIterator(ctx, ready_nodes_in_dcs(
                snap, job.datacenters)))
        chain.set_constraints(job.constraints)
        chain = DriverIterator(ctx, chain)
        chain.set_drivers(constr.drivers)
        chain = ConstraintIterator(ctx, chain)
        chain.set_constraints(constr.constraints)
        admitted = set()
        while (node := chain.next()) is not None:
            admitted.add(node.id)
        ids = admitted if ids is None else ids & admitted
    return ids or set()


def check_against_oracle(snap, nodes, jobs) -> dict:
    """The contract of tests/test_fuzz_differential.py on the served
    run's final state: per job the device path placed exactly what the
    host oracle places; every placement sits on a node the oracle's
    feasibility chain admits; no node is over capacity by
    structs.allocs_fit; alloc ids are unique."""
    from nomad_tpu import structs
    from nomad_tpu.network import NetworkIndex

    oracle = host_oracle(nodes, jobs)
    per_job, problems, ids = {}, [], set()
    n_allocs = 0
    for job in jobs:
        placed = _running(snap.allocs_by_job(job.id))
        per_job[job.id] = {"device": len(placed), "oracle": oracle[job.id]}
        if len(placed) != oracle[job.id]:
            problems.append(
                f"{job.id}: device placed {len(placed)}, host oracle "
                f"{oracle[job.id]}")
        eligible = _eligible_ids(snap, job)
        off = sum(1 for a in placed if a.node_id not in eligible)
        if off:
            problems.append(f"{job.id}: {off} placements on ineligible nodes")
        for a in placed:
            ids.add(a.id)
        n_allocs += len(placed)
    if len(ids) != n_allocs:
        problems.append(f"{n_allocs - len(ids)} duplicate alloc ids")
    over = 0
    for node in snap.nodes():
        allocs = _running(snap.allocs_by_node(node.id))
        if not allocs:
            continue
        idx = NetworkIndex()
        idx.set_node(node)
        fit, _dim, _used = structs.allocs_fit(node, allocs, idx)
        over += not fit
    if over:
        problems.append(f"{over} nodes over capacity (allocs_fit)")
    return {"agrees": not problems, "problems": problems,
            "jobs": len(jobs), "allocs_checked": n_allocs,
            "per_job": per_job}


def scenario_phase(spec, expected: int, seed: int) -> dict:
    from nomad_tpu import structs
    from nomad_tpu.ops.coalesce import GLOBAL_SOLVER
    from nomad_tpu.simcluster import sim_node
    from nomad_tpu.simcluster.scenario import ScenarioRunner
    from nomad_tpu.simcluster.workload import build_job
    from nomad_tpu.tpu.solver import SOLVER_PANEL

    paths0 = dict(GLOBAL_SOLVER.paths)
    solves0 = SOLVER_PANEL.snapshot()["solves"]
    runner = ScenarioRunner(spec, seed=seed)
    artifact = runner.run()
    paths = {k: v - paths0.get(k, 0) for k, v in GLOBAL_SOLVER.paths.items()
             if v - paths0.get(k, 0)}
    panel = artifact["solver_panel"]
    widths = {w: row["dispatches"]
              for w, row in panel["window"]["batch_widths"].items()}
    out = {
        "scenario": spec.name, "n_nodes": runner.n_nodes, "seed": seed,
        "backend": artifact["backend"],
        "expected": expected,
        "placed": artifact["placements"]["placed"],
        "evals_injected": artifact["placements"]["evals_injected"],
        "device_dispatches": artifact["placements"]["device_dispatches"],
        "solve_paths": paths,
        "batch_widths": widths,
        "node_buckets": [b["bucket"] for b in panel["node_buckets"]],
        "panel_compiles": [c for c in panel["compiles"]["recent"]
                           if c["solve_seq"] > solves0],
        "scenario_wall_seconds": artifact["wall_seconds"],
        "events_digest": artifact["events"]["digest"],
    }
    # The plain reference, outside any timing: the warmup job, then the
    # scenario's jobs in registration order, on the same seeded fleet.
    nodes = [sim_node(i, "dc1" if i % 2 == 0 else "dc2")
             for i in range(runner.n_nodes)]
    jobs = list(runner._jobs.values())
    if spec.warmup_count:
        jobs.insert(0, build_job("sim-warmup", structs.JOB_TYPE_BATCH,
                                 spec.warmup_count))
    t0 = time.perf_counter()
    out["oracle"] = check_against_oracle(
        runner._srv.state_store.snapshot(), nodes, jobs)
    out["oracle"]["wall_seconds"] = round(time.perf_counter() - t0, 2)
    return out


# -- HTTP phase ---------------------------------------------------------------


def http_phase(dry_run: bool) -> dict:
    """A few requests through the HTTP front of a dev agent whose server
    schedules on the device this process holds."""
    import tempfile

    from nomad_tpu import structs
    from nomad_tpu.agent import Agent, AgentConfig
    from nomad_tpu.api.client import ApiClient
    from nomad_tpu.simcluster import sim_node
    from nomad_tpu.simcluster.workload import build_job

    n_nodes, count = (64, 150) if dry_run else (1000, 2000)
    with tempfile.TemporaryDirectory(prefix="chip-smoke-agent-") as data_dir:
        cfg = AgentConfig(server_enabled=True, dev_mode=True,
                          scheduler_backend="tpu", http_port=0,
                          data_dir=data_dir, node_name="chip-smoke")
        agent = Agent(cfg)
        agent.start()
        try:
            agent.server.node_batch_register(
                [sim_node(i, "dc1" if i % 2 == 0 else "dc2")
                 for i in range(n_nodes)])
            port = agent.http.addr.rsplit(":", 1)[1]
            client = ApiClient(address=f"http://127.0.0.1:{port}")
            job = build_job("smoke-http", structs.JOB_TYPE_SERVICE, count)
            eval_id, _ = client.jobs().register(job)
            deadline = time.monotonic() + 300.0
            status = None
            while time.monotonic() < deadline:
                ev, _ = client.evaluations().info(eval_id)
                status = ev.status
                if status in (structs.EVAL_STATUS_COMPLETE,
                              structs.EVAL_STATUS_FAILED):
                    break
                time.sleep(0.05)
            allocs, _ = client.jobs().allocations(job.id)
            running = [a for a in allocs if a.get("desired_status")
                       == structs.ALLOC_DESIRED_STATUS_RUN]
            info = client.agent().self_info()
            device = info["stats"]["server"]["scheduler"]["device"]
            solver = client.agent().solver()
        finally:
            agent.shutdown()
    return {
        "requests": ["PUT /v1/jobs", "GET /v1/evaluation/:id",
                     "GET /v1/job/:id/allocations", "GET /v1/agent/self",
                     "GET /v1/agent/solver"],
        "n_nodes": n_nodes, "expected": count, "eval_status": status,
        "placed": len(running),
        "agent_device": device,
        "solver_dispatches": solver.get("coalescer", {}).get("dispatches"),
    }


# -- main ---------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument(
        "--dry-run-cpu", action="store_true",
        help="accept a non-TPU platform and run at tiny size with the "
             "kernel in interpret mode; checks control flow, proves "
             "nothing about the chip")
    ap.add_argument("--seed", type=int, default=42)
    args = ap.parse_args(argv)
    dry_run = args.dry_run_cpu
    logging.basicConfig(
        level=logging.WARNING, stream=sys.stderr,
        format="%(asctime)s %(name)s %(levelname)s %(message)s")

    t_start = time.perf_counter()
    # Everything of the repo this needs, before any output: in a
    # directory holding this file alone the import fails, exit != 0.
    from nomad_tpu import native
    from nomad_tpu.scheduler import DEVICE_BREAKER, acquire_device

    compiles = CompileLog()
    compiles.install()
    # What jax.devices() reports: platform, device_kind, len(devices).
    acquired = acquire_device()
    device = {"platform": acquired["platform"],
              "kind": acquired["device_kind"], "count": acquired["count"]}
    versions = _versions()
    refused = device["platform"] != "tpu" and not dry_run
    print(f"chip_smoke: device {device} versions={versions} "
          f"dry_run={dry_run}", flush=True,
          file=sys.stderr if refused else sys.stdout)
    if refused:
        print(f"chip_smoke: no accelerator (JAX platform "
              f"{device['platform']!r}); refusing to run. --dry-run-cpu "
              "runs a tiny control-flow check instead.", file=sys.stderr)
        return 2

    cache_dir = acquired["compile_cache"]
    cache_entries0 = _cache_entries(cache_dir)
    report = {
        "ok": False, "device": device, "dry_run": dry_run,
        "seed": args.seed, "versions": versions, "failures": [],
        "phases": {},
    }
    failures = report["failures"]

    def run_phase(name, fn):
        compiles.phase = name
        t0 = time.perf_counter()
        try:
            out = fn()
        except Exception as e:  # a phase that raised fails the smoke
            traceback.print_exc()
            out = {"error": f"{type(e).__name__}: {e}"}
            failures.append(f"{name}: raised {out['error']}")
        out["wall_seconds"] = round(time.perf_counter() - t0, 2)
        out["compiles"] = compiles.of_phase(name)
        breaker = DEVICE_BREAKER.stats()
        out["breaker"] = {"state": breaker["state"],
                          "trips": breaker["trips"]}
        if breaker["state"] != "closed" or breaker["trips"]:
            failures.append(f"{name}: circuit breaker {breaker}")
        report["phases"][name] = out
        print(f"chip_smoke: phase {name} done in {out['wall_seconds']}s "
              f"{'FAILED ' + out['error'] if 'error' in out else ''}",
              flush=True)
        return out

    out = run_phase("kernel", lambda: kernel_phase(dry_run, args.seed))
    if out.get("failed"):
        failures.append(f"kernel: pallas != jnp at {out['failed']}")

    widest = 0
    for spec, expected in _specs(dry_run):
        out = run_phase(
            spec.name, lambda: scenario_phase(spec, expected, args.seed))
        if "error" in out:
            continue
        if out["placed"] != expected:
            failures.append(
                f"{spec.name}: placed {out['placed']} != {expected}")
        if out["device_dispatches"] == 0:
            failures.append(f"{spec.name}: no device dispatch")
        if not out["oracle"]["agrees"]:
            failures.append(
                f"{spec.name}: host oracle: {out['oracle']['problems']}")
        widest = max([widest] + [int(w) for w in out["batch_widths"]])
        if spec.name == "steady-10k" and not dry_run:
            out["digest_equals_cpu_bank"] = (
                out["events_digest"] == STEADY_10K_CPU_DIGEST)
    report["widest_coalesced_dispatch"] = widest
    if widest < 2 and not dry_run:
        # Whether evals stack is a matter of timing; only the full-size
        # burst, whose evals take long enough to queue, must show it.
        failures.append("no coalesced dispatch of width > 1 was seen")

    out = run_phase("http", lambda: http_phase(dry_run))
    if "error" not in out and (out["placed"] != out["expected"]
                               or out["eval_status"] != "complete"):
        failures.append(f"http: placed {out['placed']}/{out['expected']}, "
                        f"eval {out['eval_status']}")

    # Which water-fill ran, against which one the process selects: a
    # dispatch on the other path means the kernel was abandoned.
    from nomad_tpu.ops import pallas_solve
    from nomad_tpu.ops.coalesce import GLOBAL_SOLVER, quiesce_all
    from nomad_tpu.tpu.solver import SOLVER_PANEL

    buckets = [b["bucket"] for b in SOLVER_PANEL.snapshot()["node_buckets"]]
    select_pallas = {b: pallas_solve.selected(b) for b in buckets}
    other = "jnp" if all(select_pallas.values()) else (
        "pallas" if not any(select_pallas.values()) else None)
    report["solve_paths"] = {
        "dispatches_by_path": dict(GLOBAL_SOLVER.paths),
        "pallas_selected_by_bucket": select_pallas,
        "batch_retries": GLOBAL_SOLVER.batch_retries,
    }
    if other and GLOBAL_SOLVER.paths.get(other):
        failures.append(
            f"{GLOBAL_SOLVER.paths[other]} water-fill dispatches ran on "
            f"the {other} path, which this process does not select")
    if GLOBAL_SOLVER.batch_retries:
        failures.append(f"{GLOBAL_SOLVER.batch_retries} stacked dispatches "
                        "failed and were re-solved one at a time")
    host_fallbacks = _counter("scheduler.device.breaker_fallback")
    report["host_scheduler_fallbacks"] = host_fallbacks
    if host_fallbacks:
        failures.append(f"{host_fallbacks:.0f} evals were routed to the "
                        "host scheduler")
    report["breaker"] = DEVICE_BREAKER.stats()
    report["native"] = native.status()
    report["compile_cache"] = {
        "dir": cache_dir,
        "from_env": bool(os.environ.get("JAX_COMPILATION_CACHE_DIR")),
        "entries_before": cache_entries0,
        "entries_written": _cache_entries(cache_dir) - cache_entries0,
        "persistent_cache_hits": compiles.cache["hits"],
        "persistent_cache_misses": compiles.cache["misses"],
    }
    report["compile_events"] = len(compiles.events)
    report["compile_seconds_total"] = round(
        sum(s for _p, _n, s in compiles.events), 2)
    report["wall_seconds"] = round(time.perf_counter() - t_start, 2)
    report["ok"] = not failures

    # Drain device threads so interpreter teardown cannot abort under a
    # thread still inside XLA, and the result stays the last line.
    if not quiesce_all(30.0):
        failures.append("device work still in flight at exit")
        report["ok"] = False
    try:
        os.makedirs("chiprun_out", exist_ok=True)
        with open(os.path.join("chiprun_out", "chip_smoke.json"), "w") as f:
            json.dump(report, f, indent=1, sort_keys=True)
            f.write("\n")
    except OSError:
        pass
    sys.stderr.flush()
    print(json.dumps(report), flush=True)
    # The result, last and alone: exactly these keys, the device as JAX
    # reports it. Everything else (the reasons too) is the line above.
    print(json.dumps({"ok": report["ok"], "device": device}), flush=True)
    return 0 if report["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
