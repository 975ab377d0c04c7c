"""The server: wires state, broker, plan pipeline, workers, heartbeats.

Reference: /root/reference/nomad/server.go + the RPC endpoint files. This is
the single-process ("DevMode") composition — replication is the synchronous
InProcRaft (the reference's raft.NewInmemStore testing posture,
server.go:420-427); the multi-server layer slots in behind the same
apply/applied_index interface. Endpoint methods carry the semantics of the
net/rpc endpoints (job_endpoint.go, node_endpoint.go, eval_endpoint.go,
plan_endpoint.go) minus the wire format, which lives in nomad_tpu.api.
"""

from __future__ import annotations

import logging
import threading
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

from nomad_tpu import cpu_observe, structs, trace
from nomad_tpu.events import EventBroker
from nomad_tpu.server.core_sched import CoreScheduler
from nomad_tpu.server.eval_broker import EvalBroker
from nomad_tpu.server.fsm import FSM, InProcRaft
from nomad_tpu.server.heartbeat import HeartbeatManager
from nomad_tpu.server.plan_pipeline import PlanPipeline
from nomad_tpu.server.plan_queue import PlanQueue
from nomad_tpu.server.timetable import TimeTable
from nomad_tpu.server.worker import Worker
from nomad_tpu.structs import (
    CORE_JOB_EVAL_GC,
    CORE_JOB_NODE_GC,
    CORE_JOB_PRIORITY,
    JOB_TYPE_CORE,
    Evaluation,
    Job,
    Node,
    Plan,
    PlanResult,
    generate_uuid,
)


@dataclass
class ServerConfig:
    """Server tunables (reference: nomad/config.go:46-236 defaults)."""

    region: str = "global"
    datacenter: str = "dc1"
    node_name: str = "server-1"
    # Scheduler worker concurrency: N workers evaluate concurrently
    # against delta-rolled snapshots and the plan pipeline resolves
    # their plans optimistically (Omega posture). First-class validated
    # knob — agent config `server { scheduler_workers = N }` with
    # ``num_schedulers`` as the legacy alias; the AGENT layer resolves
    # the two (scheduler_workers preferred) and passes one value down.
    # At THIS constructor a passed num_schedulers wins over
    # scheduler_workers, because None-vs-set is the only explicit signal
    # a dataclass can see — scheduler_workers' default is
    # indistinguishable from an explicit 4.
    scheduler_workers: int = 4
    num_schedulers: Optional[int] = None
    # How many pending plans the pipeline drains and verifies per fused
    # batch pass (plan_pipeline.py). 1 degenerates to the serial applier.
    plan_batch_size: int = 8
    # Seed for the server's name-salted decision-path PRNG streams
    # (broker scheduler choice, heartbeat jitter — nomad_tpu.prng). The
    # simcluster scenario runner stamps its run seed here so replays
    # draw identically.
    seed: int = 0
    enabled_schedulers: List[str] = field(
        default_factory=lambda: [
            structs.JOB_TYPE_SERVICE,
            structs.JOB_TYPE_BATCH,
            structs.JOB_TYPE_SYSTEM,
            JOB_TYPE_CORE,
        ]
    )
    # 'tpu' routes service/batch/system evals to the dense-solve factories;
    # 'host' uses the scalar oracle.
    scheduler_backend: str = "tpu"
    eval_nack_timeout: float = 60.0
    eval_delivery_limit: int = 3
    # Broker-level eval coalescing: each worker drains up to this many
    # ready evals (distinct jobs) per dequeue and runs them concurrently,
    # stacking their device solves into one vmapped dispatch
    # (SURVEY.md §7 "Batched evals"; 1 disables).
    eval_batch_size: int = 4
    eval_gc_interval: float = 300.0
    eval_gc_threshold: float = 3600.0
    node_gc_interval: float = 300.0
    node_gc_threshold: float = 24 * 3600.0
    min_heartbeat_ttl: float = 10.0
    max_heartbeats_per_second: float = 50.0
    failover_heartbeat_ttl: float = 300.0
    periodic_dispatch: bool = False  # GC dispatch loop (leader.go:170-200)
    # Pre-compile the device solve programs for the cluster's shape buckets
    # in the background at start/leader-establish, so a first eval doesn't
    # pay a cold XLA compile against the nack timeout (tpu/solver.py
    # warm_shapes; the worker's nack-touch loop covers the gap meanwhile).
    prewarm_shapes: bool = True
    # Optional TLS on the RPC tier (reference nomad/rpc.go:104-110 rpcTLS
    # + tlsutil): a nomad_tpu.tlsutil.TLSConfig; None runs plaintext.
    tls: object = None
    # Ring size of the cluster event stream (nomad_tpu.events) — the
    # /v1/event/stream resume window. Consumers further behind than this
    # get a truncation marker and must re-list.
    event_buffer_size: int = 2048
    # Declarative latency SLOs (nomad_tpu.slo): objective name ->
    # threshold ms, e.g. {"submit_to_placed_p95_ms": 250}. None = the
    # slo.DEFAULT_OBJECTIVES set; {} disables the monitor entirely.
    slo_objectives: Optional[Dict[str, float]] = None
    # Rolling error-budget window for the SLO burn-rate accounting.
    slo_window_s: float = 3600.0
    # -- admission control & backpressure (nomad_tpu/server/admission.py).
    # Enforced bound on the broker's pending evals (ready + blocked +
    # waiting): the admission front door rejects QUEUE_FULL at it, and
    # the broker itself spills (typed NACK + readmission) past it for
    # internally generated evals. 0 = unbounded (historical posture).
    eval_pending_cap: int = 0
    # Enforced plan-queue depth cap: enqueue past it is a typed
    # PlanQueueError(ERR_QUEUE_FULL) -> worker nack. 0 = unbounded.
    plan_queue_cap: int = 0
    # Bound on blocking-query watcher registrations (state store + event
    # stream): past it register raises RejectError(WATCH_LIMIT) -> fast
    # 503 instead of unbounded registry growth. 0 = unbounded.
    max_blocking_watchers: int = 0
    # Admission front-door spec (AdmissionConfig.parse mapping): per-
    # client token-bucket rate lanes + SLO-coupled shedding. None =
    # permissive defaults (admit everything — decision-invariant).
    admission: Optional[Dict] = None
    # Express placement lane spec (ExpressConfig.parse mapping,
    # nomad_tpu/server/express.py): leader-local sub-millisecond
    # placement of express-eligible batch jobs under leased capacity
    # reservations. None = lane OFF (decision-invariant: the banked
    # steady-10k digests pin that default).
    express: Optional[Dict] = None
    # Capacity observatory spec (CapacityConfig.parse mapping,
    # nomad_tpu/capacity.py): the read-only accountant behind
    # /v1/agent/capacity — fragmentation, per-lane usage, stranded-
    # capacity %. None = defaults (enabled; decision-invariant by
    # construction, pinned by the churn-frag-200 contrast arm).
    capacity: Optional[Dict] = None
    # Raft & recovery observatory spec (RaftObserveConfig.parse mapping,
    # nomad_tpu/raft_observe.py): the read-only observer behind
    # /v1/agent/raft — write-path stage attribution per msg_type,
    # follower lag, log/snapshot economy, restart-replay timeline.
    # None = defaults (enabled; decision-invariant by construction: the
    # observer drains bounded books the raft node keeps as plain data).
    raft_observe: Optional[Dict] = None
    # Read-path observatory spec (ReadObserveConfig.parse mapping,
    # nomad_tpu/read_observe.py): the read-only observer behind
    # /v1/agent/reads — per-route serving attribution, the blocking
    # hold/serve partition, SSE session books, watch-registry wake
    # economy, response-staleness distribution. None = defaults
    # (enabled; decision-invariant by construction: the HTTP layer
    # writes plain books, nothing feeds back — pinned by the read-storm
    # contrast arm).
    reads: Optional[Dict] = None
    # Follower read plane spec (ReadPathConfig.parse mapping,
    # nomad_tpu/server/read_path.py): consistency-tiered read serving —
    # the stale lane's staleness-bound enforcement, the linearizable
    # lane's read-index/lease confirmation, per-(role, lane) serve
    # books. None = defaults (enabled). Decision scope: this is a
    # SERVING path (it refuses requests), not an observatory — but it is
    # read-decision-invariant for the write path: no lane ever touches
    # the log beyond the once-per-term barrier no-op, pinned by the
    # read-storm digest equality.
    read_path: Optional[Dict] = None
    # Runtime self-observatory spec (ProfileObserveConfig.parse mapping,
    # nomad_tpu/profile_observe.py): the read-only observer behind
    # /v1/agent/profile and /v1/agent/runtime — continuous stack-
    # sampling profiler (seeded-jittered cadence, thread-role wall
    # shares, flamegraph exports), lock-contention table (read from the
    # installed telemetry.LockWatchdog), and the byte-economy ledger
    # with the measured-per-row 1M-node mirror projection. None =
    # defaults (enabled; decision-invariant by construction: it samples
    # frames and reads array metadata, nothing feeds back — pinned by
    # the steady-10k profiler-off contrast arm).
    profile: Optional[Dict] = None
    # Solver mesh spec (SolverMeshConfig.parse mapping,
    # nomad_tpu/parallel/mesh.py): shard the node axis of every device
    # solve (and the mirror's padded buffers) over a JAX device mesh —
    # `{node_shards: N, eval_parallel: M}`. None/default = single-device
    # (decision-invariant: sharded solves are fuzz-pinned identical, the
    # knob only moves where the flops run). Applied at start with a
    # transparent single-device fallback when the local device set can't
    # satisfy the extents.
    solver_mesh: Optional[Dict] = None

    def __post_init__(self) -> None:
        if self.num_schedulers is not None:
            self.scheduler_workers = self.num_schedulers
        # Both spellings read the same resolved value afterwards.
        self.num_schedulers = self.scheduler_workers
        if (not isinstance(self.scheduler_workers, int)
                or isinstance(self.scheduler_workers, bool)
                or not 0 <= self.scheduler_workers <= 128):
            raise ValueError(
                "scheduler_workers must be an integer in [0, 128], got "
                f"{self.scheduler_workers!r}"
            )
        if (not isinstance(self.plan_batch_size, int)
                or isinstance(self.plan_batch_size, bool)
                or not 1 <= self.plan_batch_size <= 256):
            raise ValueError(
                "plan_batch_size must be an integer in [1, 256], got "
                f"{self.plan_batch_size!r}"
            )
        for knob in ("eval_pending_cap", "plan_queue_cap",
                     "max_blocking_watchers"):
            v = getattr(self, knob)
            if (not isinstance(v, int) or isinstance(v, bool)
                    or not 0 <= v <= 10_000_000):
                raise ValueError(
                    f"{knob} must be an integer in [0, 10000000], got {v!r}"
                )
        # Parse-time validation of the admission block (typo'd keys and
        # out-of-range values fail config load, like scheduler_workers);
        # the parsed config is what Server consumes.
        from nomad_tpu.server.admission import AdmissionConfig

        self.admission_config = AdmissionConfig.parse(self.admission)
        from nomad_tpu.server.express import ExpressConfig

        self.express_config = ExpressConfig.parse(self.express)
        from nomad_tpu.capacity import CapacityConfig

        self.capacity_config = CapacityConfig.parse(self.capacity)
        from nomad_tpu.raft_observe import RaftObserveConfig

        self.raft_observe_config = RaftObserveConfig.parse(self.raft_observe)
        from nomad_tpu.read_observe import ReadObserveConfig

        self.reads_config = ReadObserveConfig.parse(self.reads)
        from nomad_tpu.server.read_path import ReadPathConfig

        self.read_path_config = ReadPathConfig.parse(self.read_path)
        from nomad_tpu.profile_observe import ProfileObserveConfig

        self.profile_config = ProfileObserveConfig.parse(self.profile)
        from nomad_tpu.parallel.mesh_config import SolverMeshConfig

        self.solver_mesh_config = SolverMeshConfig.parse(self.solver_mesh)

    def scheduler_factory(self, eval_type: str) -> str:
        if self.scheduler_backend == "tpu" and eval_type in (
            structs.JOB_TYPE_SERVICE,
            structs.JOB_TYPE_BATCH,
            structs.JOB_TYPE_SYSTEM,
        ):
            return f"tpu-{eval_type}"
        return eval_type


class Server:
    """Single-process scheduling brain (reference: nomad/server.go:57-230,
    leader lifecycle at nomad/leader.go:99-140)."""

    def __init__(self, config: Optional[ServerConfig] = None,
                 logger: Optional[logging.Logger] = None):
        self.config = config or ServerConfig()
        self.logger = logger or logging.getLogger("nomad_tpu.server")
        # The collector's pauses are read from the interpreter book
        # (/v1/agent/solver); its hook is one per process.
        cpu_observe.BOOK.collector.install()

        self.eval_broker = EvalBroker(
            self.config.eval_nack_timeout, self.config.eval_delivery_limit,
            seed=self.config.seed,
            pending_cap=self.config.eval_pending_cap,
        )
        self.fsm = FSM(
            eval_broker=self.eval_broker, logger=self.logger,
            events=EventBroker(capacity=self.config.event_buffer_size,
                               emitter=self.config.node_name),
        )
        # Bounded blocking-query fan-out: the watcher-registration caps
        # ride the watch registries themselves (typed WATCH_LIMIT
        # rejection past them, server/blocking.py).
        if self.config.max_blocking_watchers:
            self.fsm.state.watch.max_watchers = \
                self.config.max_blocking_watchers
            self.fsm.events.watch.max_watchers = \
                self.config.max_blocking_watchers
        self.raft = InProcRaft(self.fsm)
        self.plan_queue = PlanQueue(max_depth=self.config.plan_queue_cap)
        self.time_table = TimeTable()
        self.heartbeat = HeartbeatManager(self)
        self.plan_applier = PlanPipeline(
            self.plan_queue, self.eval_broker, self.raft, self.fsm,
            self.logger, max_batch=self.config.plan_batch_size,
        )
        self.workers: List[Worker] = []
        # Live SLO accounting over this server's own event stream
        # (nomad_tpu.slo; /v1/agent/slo). An empty objectives dict opts
        # out; None means the default objective set. Read-only on
        # decisions: the monitor is an event-ring consumer.
        self.slo_monitor: Optional[object] = None
        if self.config.slo_objectives is None or self.config.slo_objectives:
            from nomad_tpu.slo import EXPRESS_OBJECTIVES, SLOMonitor

            objectives = self.config.slo_objectives
            if objectives is None and self.config.express_config.enabled:
                # Default objective set + the express lane's own target:
                # an enabled lane is judged (express_placed_p50_ms)
                # without the operator re-spelling the defaults.
                from nomad_tpu.slo import DEFAULT_OBJECTIVES

                objectives = {**DEFAULT_OBJECTIVES, **EXPRESS_OBJECTIVES}
            self.slo_monitor = SLOMonitor(
                self.fsm.events, objectives,
                window_s=self.config.slo_window_s,
            )
        # The bounded front door (server/admission.py): consulted by
        # job_register/job_evaluate BEFORE any raft apply. Default-
        # permissive — with no caps/rates configured it admits on a
        # no-lock fast path (decision-invariant with the banked digests).
        from nomad_tpu.server.admission import AdmissionController

        monitor = self.slo_monitor
        self.admission = AdmissionController(
            self.config.admission_config,
            seed=self.config.seed,
            queue_depth=self.eval_broker.pending_total,
            queue_cap=self.config.eval_pending_cap,
            burn_rate=(monitor.burn_rate if monitor is not None
                       else None),
            events=self.fsm.events,
        )
        # The express placement lane (server/express.py): constructed
        # always (exposition/stats answer lane-off too), active only
        # when configured. The plan pipeline verifies under the lane's
        # reservation ledger iff the lane is ON — a None ledger keeps
        # the verifier bit-identical to the pre-express posture.
        from nomad_tpu.server.express import ExpressLane

        self.express_lane = ExpressLane(self, self.config.express_config)
        if self.config.express_config.enabled:
            self.plan_applier.ledger = self.express_lane.ledger
        # The capacity observatory (nomad_tpu/capacity.py): a read-only
        # consumer of the state store's change logs, composed HERE and
        # only here — decision-path modules are statically barred from
        # importing it (nomadlint OBS001). The store getter re-reads
        # fsm.state per poll so a raft snapshot install (which rebinds
        # the store) rolls into a counted full rebuild, never a stale
        # view.
        from nomad_tpu.capacity import CapacityAccountant

        self.capacity_accountant = CapacityAccountant(
            lambda: self.fsm.state,
            self.config.capacity_config,
            events=self.fsm.events,
        )
        # The raft & recovery observatory (nomad_tpu/raft_observe.py):
        # drains the bounded write-path/log/recovery books the raft node
        # keeps as plain data. Composed HERE and only here — the same
        # OBS001 composition-root contract as the capacity accountant.
        # The raft getter re-reads self.raft per poll: ClusterServer
        # swaps InProcRaft for a RaftNode after this constructor runs.
        from nomad_tpu.raft_observe import RaftObservatory

        self.raft_observatory = RaftObservatory(
            lambda: self.raft,
            self.config.raft_observe_config,
            events=self.fsm.events,
            fsm_getter=lambda: self.fsm,
        )
        # The read-path observatory (nomad_tpu/read_observe.py): owns
        # the recorder the HTTP exposition layer writes per-request
        # books into, and samples the watch registries' plain wake-
        # economy counters. Same OBS001 composition-root contract; the
        # getters re-read per poll (snapshot installs rebind fsm.state,
        # ClusterServer swaps the raft node).
        from nomad_tpu.read_observe import ReadObservatory

        self.read_observatory = ReadObservatory(
            lambda: self.fsm.state,
            lambda: self.raft,
            self.config.reads_config,
            events=self.fsm.events,
        )
        # The follower read plane (server/read_path.py): consistency-
        # lane resolution for every HTTP read — stale-bound enforcement,
        # linearizable read-index confirmation, per-(role, lane) serve
        # books. A serving-path component (not an observatory): it can
        # refuse a request, so it lives with the server, and it re-reads
        # self.raft per request (ClusterServer swaps in a RaftNode).
        from nomad_tpu.server.read_path import ReadPath

        self.read_path = ReadPath(self, self.config.read_path_config)
        # The runtime self-observatory (nomad_tpu/profile_observe.py):
        # stack-sampling profiler + lock-contention table + byte-economy
        # ledger. Same OBS001 composition-root contract. The ring/table
        # getters re-read the live handles per poll so restarts and
        # snapshot installs never leave it holding a dead object.
        from nomad_tpu.profile_observe import RuntimeObservatory

        self.runtime_observatory = RuntimeObservatory(
            self.config.profile_config,
            events=self.fsm.events,
            store_getter=lambda: self.fsm.state,
            rings_getter=self._runtime_rings,
            tables_getter=self._runtime_tables,
        )
        self._periodic_stop = threading.Event()
        self._started = False

    def _runtime_rings(self):
        """The bounded rings the byte-economy ledger accounts: event
        broker, trace ring, admission decision ring, express
        pending/outcome queues, plan-pipeline commit log. getattr-
        guarded — a ring that doesn't exist on this composition simply
        doesn't appear in the ledger."""
        return {
            "events": getattr(self.fsm.events, "_events", None),
            "traces": getattr(trace.get_tracer(), "_traces", None),
            "admission_decisions": getattr(
                self.admission, "_decisions", None),
            "express_pending": getattr(
                self.express_lane, "_pending", None),
            "express_outcomes": getattr(
                self.express_lane, "_outcomes", None),
            "plan_commit_log": getattr(
                self.plan_applier, "_commit_log", None),
        }

    def _runtime_tables(self):
        """The sibling observatories' in-memory books, approximated via
        their summary views (deep-sized by the ledger) — the 'what does
        watching cost' line of the byte economy."""
        out = {}
        if self.config.capacity_config.enabled:
            out["capacity"] = self.capacity_accountant.snapshot()
        if self.config.raft_observe_config.enabled:
            out["raft_observe"] = self.raft_observatory.snapshot()
        if self.config.reads_config.enabled:
            out["read_observe"] = self.read_observatory.snapshot()
        return out

    @property
    def plan_pipeline(self) -> PlanPipeline:
        """The optimistic batch applier (``plan_applier`` is the legacy
        spelling kept for the reference's naming)."""
        return self.plan_applier

    @property
    def state_store(self):
        return self.fsm.state

    # -- lifecycle (leader.go:99-140 establishLeadership) -------------------

    def start(self) -> None:
        if self._started:
            return
        self._acquire_device()
        self._started = True
        self.plan_queue.set_enabled(True)
        self.eval_broker.set_enabled(True)
        self.plan_applier.start()
        if self.slo_monitor is not None:
            self.slo_monitor.start()
        self.express_lane.start()
        self.capacity_accountant.start()
        self.raft_observatory.start()
        self.read_observatory.start()
        self.runtime_observatory.start()
        self.restore_eval_broker()
        for i in range(self.config.scheduler_workers):
            worker = Worker(self, i)
            worker.start()
            self.workers.append(worker)
        if self.config.periodic_dispatch:
            t = threading.Thread(
                target=self._periodic_dispatcher, daemon=True,
                name="periodic-gc",
            )
            t.start()
        reaper = threading.Thread(
            target=self._reap_failed_evaluations, daemon=True,
            name="failed-eval-reaper",
        )
        reaper.start()
        self._start_readmission()
        emitter = threading.Thread(
            target=self._emit_stats, daemon=True, name="stats-emitter",
        )
        emitter.start()
        if self.config.prewarm_shapes and self.config.scheduler_backend == "tpu":
            warmer = threading.Thread(
                target=self._prewarm_solver, daemon=True, name="shape-warmer",
            )
            warmer.start()

    def _acquire_device(self) -> None:
        """With scheduler_backend="tpu", claim the device in this process
        BEFORE any worker starts: every worker sees the same device from
        its first eval, and a server that cannot claim one raises here
        and does not start. Then configure the process solve mesh from
        `server { solver_mesh }`, also before any worker can build a
        mirror: node tensors are born with the configured sharding
        (mirror.put_node_sharded), so ordering is what keeps the warm
        path reshard-free. Shared by Server.start and ClusterServer.start
        so the gating can never drift."""
        if self.config.scheduler_backend != "tpu":
            return
        from nomad_tpu.scheduler import acquire_device

        device = acquire_device()
        self.logger.info(
            "device solver on %s (%s x%d)", device["platform"],
            device["device_kind"], device["count"])
        if self.config.solver_mesh_config.enabled:
            from nomad_tpu.parallel import mesh as mesh_lib

            mesh_lib.apply_solver_mesh(
                self.config.solver_mesh_config, self.logger
            )

    def _prewarm_solver(self) -> None:
        """Background shape-bucket pre-compile (see ServerConfig
        .prewarm_shapes; started only with the device acquired).
        Re-warms whenever the cluster's node-bucket signature changes — a
        fresh cluster warms as soon as nodes register, and growth into a
        larger padded bucket triggers a new compile before an eval needs
        it."""
        from nomad_tpu.ops.binpack import bucket
        from nomad_tpu.tpu import solver

        warmed_sig = None
        while not self._periodic_stop.is_set():
            snap = self.state_store.snapshot()
            nodes = [
                n for n in snap.nodes()
                if n.status == structs.NODE_STATUS_READY and not n.drain
            ]
            per_dc: Dict[str, int] = {}
            for n in nodes:
                per_dc[n.datacenter] = per_dc.get(n.datacenter, 0) + 1
            sig = (
                bucket(len(nodes)) if nodes else 0,
                tuple(sorted(bucket(c) for c in per_dc.values())),
            )
            if nodes and sig != warmed_sig:
                try:
                    solver.warm_shapes(
                        snap, logger=self.logger,
                        stop=self._periodic_stop.is_set,
                    )
                    warmed_sig = sig
                except Exception:
                    self.logger.exception("shape prewarm failed")
            self._periodic_stop.wait(5.0)

    def shutdown(self) -> None:
        self._periodic_stop.set()
        for worker in self.workers:
            worker.stop()
        self.express_lane.stop()
        self.capacity_accountant.stop()
        self.raft_observatory.stop()
        self.read_observatory.stop()
        self.runtime_observatory.stop()
        if self.slo_monitor is not None:
            self.slo_monitor.stop()
        self.plan_applier.stop()
        self.plan_queue.set_enabled(False)
        self.eval_broker.set_enabled(False)
        self.heartbeat.clear_all()

    def _emit_stats(self) -> None:
        """Periodic telemetry gauges at 1 Hz (server.go:213-228 EmitStats ->
        eval_broker.go:557-575, plan_queue.go:198-209, heartbeat.go:135-148)."""
        from nomad_tpu import telemetry

        while not self._periodic_stop.wait(1.0):
            broker = self.eval_broker.snapshot_stats()
            telemetry.set_gauge(
                ("broker", "total_ready"), broker.total_ready
            )
            telemetry.set_gauge(
                ("broker", "total_unacked"), broker.total_unacked
            )
            telemetry.set_gauge(
                ("broker", "total_blocked"), broker.total_blocked
            )
            telemetry.set_gauge(
                ("broker", "total_waiting"), broker.total_waiting
            )
            for queue, stats in broker.by_scheduler.items():
                telemetry.set_gauge(
                    ("broker", queue, "ready"), stats.ready
                )
                telemetry.set_gauge(
                    ("broker", queue, "unacked"), stats.unacked
                )
            # The ONE plan.queue_depth writer: a periodic gauge keeps the
            # series present in every retained interval (an event-driven
            # write would vanish from the exposition after 60s of queue
            # inactivity, breaking absent()-style alerts).
            telemetry.set_gauge(
                ("plan", "queue_depth"), self.plan_queue.depth()
            )
            # Worker concurrency + pipeline batch ceiling: the two knobs
            # whose product bounds optimistic-apply parallelism; gauged
            # so the exposition names the posture a conflict-rate curve
            # was measured under.
            telemetry.set_gauge(
                ("worker", "concurrency"),
                sum(1 for w in self.workers if w.is_alive()),
            )
            telemetry.set_gauge(
                ("plan", "pipeline_batch_max"), self.plan_applier.max_batch
            )
            telemetry.set_gauge(
                ("heartbeat", "active"), self.heartbeat.num_timers()
            )
            # Blocking-query fan-out health: parked watcher counts and
            # typed WATCH_LIMIT rejections per registry (store + event
            # stream) — the 50k-watcher story's live gauges.
            for name, registry in (("state", self.state_store.watch),
                                   ("events", self.fsm.events.watch)):
                wstats = registry.stats()
                telemetry.set_gauge(
                    ("blocking", name, "watchers"), wstats["watchers"]
                )
                telemetry.set_gauge(
                    ("blocking", name, "watch_rejected"),
                    wstats["rejected"],
                )

    def restore_eval_broker(self) -> None:
        """Re-enqueue non-terminal evals after (re)gaining leadership
        (leader.go:142-168). wait_index = the post-barrier applied index:
        an earlier delivery of a restored eval may have committed a plan
        right before the previous leader died, and the next worker's
        snapshot must contain that plan or the eval gets placed twice."""
        from nomad_tpu.server.eval_broker import BrokerFullError

        wait_index = self.raft.applied_index
        for ev in self.state_store.evals():
            if ev.should_enqueue():
                try:
                    self.eval_broker.enqueue(ev, wait_index=wait_index)
                except BrokerFullError:
                    # Cap reached mid-restore: the rest stays durable in
                    # state; the readmission loop drains it as capacity
                    # frees (the spill flag is already set).
                    break

    def _start_readmission(self) -> None:
        """Arm the spill-readmission loop iff the broker is bounded (an
        unbounded broker never spills; the thread would idle forever).
        Shared by Server.start and ClusterServer.start."""
        if not self.config.eval_pending_cap:
            return
        threading.Thread(
            target=self._readmission_loop, daemon=True,
            name="eval-readmit",
        ).start()

    def _readmission_loop(self) -> None:
        """Drain spilled evals back into the bounded broker as capacity
        frees. Spilling (eval_broker.pending_cap) keeps over-cap evals
        durable in the state store only; this loop is the other half of
        that contract — without it a spilled eval would be stuck pending
        forever. Polling is cheap: the broker hands out one True per
        spill episode (reclaim_spilled), so the state scan runs only
        when there is actually something to readmit."""
        from nomad_tpu import telemetry
        from nomad_tpu.server.eval_broker import BrokerError, BrokerFullError

        while not self._periodic_stop.wait(0.5):
            if not self.eval_broker.reclaim_spilled():
                continue
            wait_index = self.raft.applied_index
            pending = [ev for ev in self.state_store.evals()
                       if ev.should_enqueue()]
            # Highest priority first, then oldest — the order the broker
            # itself would have served them in.
            pending.sort(key=lambda e: (-e.priority, e.create_index, e.id))
            readmitted = 0
            for ev in pending:
                try:
                    self.eval_broker.enqueue(
                        ev, wait_index=wait_index)
                    readmitted += 1
                except BrokerFullError:
                    break  # flag re-armed by the broker; next episode
                except BrokerError:
                    break  # disabled (leadership lost) — moot
            if readmitted:
                telemetry.incr_counter(("broker", "readmitted"), readmitted)
                self.logger.debug(
                    "readmitted %d spilled evals", readmitted)

    def _periodic_dispatcher(self) -> None:
        """Dispatch GC core evals periodically (leader.go:170-200)."""
        import time as _time

        last_eval_gc = last_node_gc = _time.monotonic()
        while not self._periodic_stop.wait(1.0):
            now = _time.monotonic()
            self.time_table.witness(self.raft.applied_index)
            if now - last_eval_gc >= self.config.eval_gc_interval:
                self._dispatch_core_job(CORE_JOB_EVAL_GC)
                last_eval_gc = now
            if now - last_node_gc >= self.config.node_gc_interval:
                self._dispatch_core_job(CORE_JOB_NODE_GC)
                last_node_gc = now

    def _reap_failed_evaluations(self) -> None:
        """Drain the broker's _failed queue: mark the eval failed through the
        log and ack it so the job's blocked evals unwedge
        (reference: leader.go:202-238)."""
        from nomad_tpu.server.eval_broker import FAILED_QUEUE, BrokerError

        while not self._periodic_stop.is_set():
            try:
                ev, token = self.eval_broker.dequeue([FAILED_QUEUE], timeout=0.5)
            except BrokerError:
                if self._periodic_stop.wait(0.2):
                    return
                continue
            if ev is None:
                continue
            self.logger.warning("failed evaluation %s reached delivery limit, marking as failed", ev.id)
            new_eval = ev.copy()
            new_eval.status = structs.EVAL_STATUS_FAILED
            new_eval.status_description = (
                f"evaluation reached delivery limit "
                f"({self.config.eval_delivery_limit})"
            )
            try:
                self.eval_upsert([new_eval])
                self.eval_broker.ack(ev.id, token)
            except Exception:
                self.logger.exception("failed to reap evaluation %s", ev.id)

    def _dispatch_core_job(self, job_id: str) -> None:
        from nomad_tpu.server.eval_broker import BrokerFullError

        ev = Evaluation(
            id=generate_uuid(),
            priority=CORE_JOB_PRIORITY,
            type=JOB_TYPE_CORE,
            triggered_by=structs.EVAL_TRIGGER_SCHEDULED,
            job_id=job_id,
            status=structs.EVAL_STATUS_PENDING,
        )
        try:
            self.eval_broker.enqueue(ev)
        except BrokerFullError:
            # GC is periodic: the next tick retries after the overload
            # passes; the breach itself is already counted by the broker.
            self.logger.debug("core job %s dispatch spilled at cap", job_id)

    # -- Job endpoint (job_endpoint.go) -------------------------------------

    def job_register(self, job: Job, client_id: str = "") -> Tuple[str, int]:
        """Register/update a job and create its evaluation
        (job_endpoint.go:18-72). Returns (eval_id, index).

        The admission front door is checked FIRST — before validation
        even, so an overload rejection stays cheap — and before any raft
        apply, so a raised RejectError proves zero side effects (the
        typed-retry safety contract).

        The handler's own time is the eval's ``frontdoor.job_register``
        span (entry -> return, with the two raft applies as children): it
        precedes the ``eval`` root, which starts at broker enqueue."""
        tracer = trace.get_tracer()
        if tracer.enabled:
            st = trace.StageTimer()
            t_entry = trace.now()
            cpu_entry = time.thread_time()
        else:
            st = trace.NULL_STAGES
            t_entry = cpu_entry = 0.0
        self.admission.admit_job(job, client_id)
        job.validate()
        if job.type == JOB_TYPE_CORE:
            raise ValueError("job type cannot be core")
        # Express lane (server/express.py): an eligible job places
        # synchronously against the leader's mirror under a leased
        # reservation — no broker, no worker, no plan queue on the
        # submit path; the raft entry commits asynchronously. None =
        # ineligible or the lane declined (capacity, backlog): take the
        # ordinary path below.
        express = self.express_lane.submit(job, client_id)
        if express is not None:
            return express
        # A same-id EXPRESS submission may still be mid-async-commit
        # (this one was ineligible or declined): wait it out so the
        # scheduler's snapshot contains its allocations — registering
        # over an uncommitted express entry would double-place the job.
        # A commit stalled past the wait is a typed capacity rejection,
        # not a green light: nothing has been applied yet, so the
        # client's replay-after-hint stays safe.
        if not self.express_lane.await_inflight(job.id):
            raise structs.RejectError(
                structs.REJECT_QUEUE_FULL,
                f"express commit for job {job.id} still in flight",
                retry_after=1.0,
            )
        with st.stage("raft_job"):
            index = self.raft.apply("job_register", {"job": job}).result()

        ev = Evaluation(
            id=generate_uuid(),
            priority=job.priority,
            type=job.type,
            triggered_by=structs.EVAL_TRIGGER_JOB_REGISTER,
            job_id=job.id,
            job_modify_index=index,
            status=structs.EVAL_STATUS_PENDING,
        )
        span = tracer.start_span(ev.id, "frontdoor.job_register",
                                 start=t_entry)
        try:
            with st.stage("raft_eval"):
                eval_index = self.eval_upsert([ev])
        finally:
            if st is not trace.NULL_STAGES:
                st.emit_spans(span, prefix="frontdoor.")
                span.annotate("cpu_ms", round(
                    (time.thread_time() - cpu_entry) * 1000.0, 4)).finish()
        return ev.id, eval_index

    def job_evaluate(self, job_id: str, client_id: str = "") -> Tuple[str, int]:
        """Force re-evaluation (job_endpoint.go:75-128). Eval ingress is
        admission-gated like registration (same front door, same typed
        rejection)."""
        job = self.state_store.job_by_id(job_id)
        if job is None:
            raise KeyError("job not found")
        self.admission.admit_job(job, client_id)
        ev = Evaluation(
            id=generate_uuid(),
            priority=job.priority,
            type=job.type,
            triggered_by=structs.EVAL_TRIGGER_JOB_REGISTER,
            job_id=job.id,
            job_modify_index=job.modify_index,
            status=structs.EVAL_STATUS_PENDING,
        )
        index = self.eval_upsert([ev])
        return ev.id, index

    def job_deregister(self, job_id: str) -> Tuple[str, int]:
        """Remove a job and evaluate the teardown
        (job_endpoint.go:130-183)."""
        # Same guard as registration: a deregister racing an in-flight
        # express commit would otherwise no-op against absent state and
        # then watch the committer resurrect the job (or strand its
        # allocations) after the "successful" removal.
        if not self.express_lane.await_inflight(job_id):
            raise structs.RejectError(
                structs.REJECT_QUEUE_FULL,
                f"express commit for job {job_id} still in flight",
                retry_after=1.0,
            )
        job = self.state_store.job_by_id(job_id)
        index = self.raft.apply("job_deregister", {"job_id": job_id}).result()

        priority = job.priority if job else structs.JOB_DEFAULT_PRIORITY
        jtype = job.type if job else structs.JOB_TYPE_SERVICE
        ev = Evaluation(
            id=generate_uuid(),
            priority=priority,
            type=jtype,
            triggered_by=structs.EVAL_TRIGGER_JOB_DEREGISTER,
            job_id=job_id,
            job_modify_index=index,
            status=structs.EVAL_STATUS_PENDING,
        )
        eval_index = self.eval_upsert([ev])
        return ev.id, eval_index

    # -- Node endpoint (node_endpoint.go) ------------------------------------

    @staticmethod
    def _validate_registration(node: Node) -> None:
        """Shared by the single and batch registration paths — a check
        added to one must hold on both or invalid nodes reach the raft
        log through whichever path drifted."""
        if not node.id:
            raise ValueError("missing node ID for client registration")
        if not node.datacenter:
            raise ValueError("missing datacenter for client registration")
        if not node.name:
            raise ValueError("missing node name for client registration")
        if not node.status:
            node.status = structs.NODE_STATUS_INIT
        if not structs.valid_node_status(node.status):
            raise ValueError("invalid status for node")

    def node_register(self, node: Node) -> Dict:
        """node_endpoint.go:18-80"""
        self._validate_registration(node)

        index = self.raft.apply("node_register", {"node": node}).result()

        reply: Dict = {"node_modify_index": index, "index": index, "eval_ids": []}
        if structs.should_drain_node(node.status):
            reply["eval_ids"], reply["eval_create_index"] = self.create_node_evals(
                node.id, index
            )
        if not node.terminal_status():
            reply["heartbeat_ttl"] = self.heartbeat.reset_heartbeat_timer(node.id)
        return reply

    def node_batch_register(self, nodes: List[Node]) -> Dict:
        """Bulk registration: one raft entry and one batched heartbeat arm
        for a whole tranche of nodes. The RPC-tier enabler for a 10k-node
        fleet (nomad_tpu/simcluster): per-node Node.Register would cost
        10k raft applies and 10k timer-arm lock hops. Semantics per node
        match node_register minus the drain-eval fan-out (batch
        registration is for fresh, non-draining fleets; a draining node
        must register individually)."""
        if not nodes:
            return {"index": 0, "heartbeat_ttls": {}}
        for node in nodes:
            self._validate_registration(node)
            if structs.should_drain_node(node.status):
                raise ValueError(
                    "batch registration only accepts init/ready nodes"
                )
        index = self.raft.apply(
            "node_batch_register", {"nodes": nodes}
        ).result()
        # Every node is init/ready here (validated above), so all get TTLs.
        ttls = self.heartbeat.reset_many([n.id for n in nodes])
        return {"index": index, "heartbeat_ttls": ttls}

    def node_batch_heartbeat(self, node_ids: List[str]) -> Dict:
        """Batched TTL renewal: equivalent to N node_heartbeat calls for
        already-ready nodes, under one heartbeat-manager lock hold. Nodes
        that are unknown get ttl 0.0 (the client re-registers); nodes in a
        non-ready state fall back to the full node_update_status path so
        the down->ready transition evals still fan out."""
        snap = self.state_store.snapshot()
        renew: List[str] = []
        out: Dict[str, float] = {}
        for node_id in node_ids:
            node = snap.node_by_id(node_id)
            if node is None:
                out[node_id] = 0.0
            elif node.status == structs.NODE_STATUS_READY:
                renew.append(node_id)
            else:
                # Per-node isolation: the snapshot is stale, and a node
                # deregistered since (KeyError from the live-store
                # re-read) must cost THAT node its renewal, not the
                # whole tranche — the batch path would otherwise amplify
                # one racing failure to batch_size nodes' TTLs.
                try:
                    out[node_id] = self.node_update_status(
                        node_id, structs.NODE_STATUS_READY
                    ).get("heartbeat_ttl", 0.0)
                except (KeyError, ValueError):
                    out[node_id] = 0.0
        if renew:
            out.update(self.heartbeat.reset_many(renew))
        return {"heartbeat_ttls": out}

    def node_deregister(self, node_id: str) -> Dict:
        """node_endpoint.go:82-117"""
        index = self.raft.apply("node_deregister", {"node_id": node_id}).result()
        self.heartbeat.clear_heartbeat_timer(node_id)
        eval_ids, eval_index = self.create_node_evals(node_id, index)
        return {
            "eval_ids": eval_ids,
            "eval_create_index": eval_index,
            "node_modify_index": index,
            "index": index,
        }

    def node_update_status(self, node_id: str, status: str) -> Dict:
        """node_endpoint.go:119-184"""
        if not structs.valid_node_status(status):
            raise ValueError("invalid status for node")
        node = self.state_store.node_by_id(node_id)
        if node is None:
            raise KeyError("node not found")

        index = node.modify_index
        if node.status != status:
            index = self.raft.apply(
                "node_status_update", {"node_id": node_id, "status": status}
            ).result()

        reply: Dict = {"node_modify_index": index, "index": index, "eval_ids": []}
        transition_to_ready = (
            node.status in (structs.NODE_STATUS_INIT, structs.NODE_STATUS_DOWN)
            and status == structs.NODE_STATUS_READY
        )
        if structs.should_drain_node(status) or transition_to_ready:
            reply["eval_ids"], reply["eval_create_index"] = self.create_node_evals(
                node_id, index
            )
        if status != structs.NODE_STATUS_DOWN:
            reply["heartbeat_ttl"] = self.heartbeat.reset_heartbeat_timer(node_id)
        return reply

    def node_update_drain(self, node_id: str, drain: bool) -> Dict:
        """node_endpoint.go:187-238"""
        node = self.state_store.node_by_id(node_id)
        if node is None:
            raise KeyError("node not found")
        index = node.modify_index
        if node.drain != drain:
            index = self.raft.apply(
                "node_drain_update", {"node_id": node_id, "drain": drain}
            ).result()
        reply: Dict = {"node_modify_index": index, "index": index, "eval_ids": []}
        if drain:
            reply["eval_ids"], reply["eval_create_index"] = self.create_node_evals(
                node_id, index
            )
        return reply

    def node_evaluate(self, node_id: str) -> Dict:
        """Force re-evaluation of a node (node_endpoint.go:240-280)."""
        node = self.state_store.node_by_id(node_id)
        if node is None:
            raise KeyError("node not found")
        eval_ids, eval_index = self.create_node_evals(node_id, node.modify_index)
        return {"eval_ids": eval_ids, "eval_create_index": eval_index,
                "index": eval_index}

    def node_heartbeat(self, node_id: str) -> float:
        """Client TTL renewal via Node.UpdateStatus(ready) in the reference;
        exposed directly for the client loop."""
        return self.node_update_status(node_id, structs.NODE_STATUS_READY).get(
            "heartbeat_ttl", 0.0
        )

    def update_allocs_from_client(self, allocs: List) -> int:
        """node_endpoint.go:385-457 (Node.UpdateAlloc)"""
        return self.raft.apply("alloc_client_update", {"allocs": allocs}).result()

    def node_batch_expire(self, node_ids: List[str]) -> Dict:
        """Mass TTL expiry (the heartbeat wheel's batch path): mark every
        node down and fan out the re-placement evaluations in ONE
        eval_upsert / broker enqueue instead of a per-node storm. Per-node
        semantics stay IDENTICAL to node_update_status(down) +
        create_node_evals: same per-node status applies (pipelined rather
        than serialized), same per-node eval fan-out with NO cross-node
        dedup — which nodes die in the same wheel pass is timing, and a
        node's eval set must not depend on it."""
        status = structs.NODE_STATUS_DOWN
        staged: List[Tuple[str, object, int]] = []
        for node_id in node_ids:
            node = self.state_store.node_by_id(node_id)
            if node is None:
                continue
            if node.status != status:
                fut = self.raft.apply(
                    "node_status_update",
                    {"node_id": node_id, "status": status},
                )
                staged.append((node_id, fut, 0))
            else:
                staged.append((node_id, None, node.modify_index))
        settled: List[Tuple[str, int]] = []
        for node_id, fut, index in staged:
            if fut is not None:
                index = fut.result()
            settled.append((node_id, index))
        # One snapshot for the whole batch: every status apply above has
        # committed, and the fan-out reads only allocs-by-node + system
        # jobs, which those applies don't change.
        snap = self.state_store.snapshot()
        evals: List[Evaluation] = []
        reply: Dict = {"eval_ids": [], "nodes": len(settled)}
        for node_id, node_index in settled:
            evals.extend(self._node_eval_fanout(snap, node_id, node_index))
        if evals:
            reply["eval_create_index"] = self.eval_upsert(evals)
            reply["eval_ids"] = [e.id for e in evals]
        return reply

    def create_node_evals(self, node_id: str, node_index: int) -> Tuple[List[str], int]:
        """Fan out node-update evals: one per job with allocs on the node,
        plus every system job (node_endpoint.go:459-551)."""
        snap = self.state_store.snapshot()
        if (not snap.allocs_by_node(node_id)
                and not snap.jobs_by_scheduler(structs.JOB_TYPE_SYSTEM)):
            return [], 0
        evals = self._node_eval_fanout(snap, node_id, node_index)
        index = self.eval_upsert(evals)
        return [e.id for e in evals], index

    def _node_eval_fanout(self, snap, node_id: str,
                          node_index: int) -> List[Evaluation]:
        """One node's node-update eval set (the create_node_evals body,
        shared with the batch-expiry path so single and mass expiry build
        byte-identical evals from the same snapshot reads)."""
        allocs = snap.allocs_by_node(node_id)
        sys_jobs = snap.jobs_by_scheduler(structs.JOB_TYPE_SYSTEM)

        evals: List[Evaluation] = []
        job_ids = set()
        for alloc in allocs:
            if alloc.job_id in job_ids or alloc.job is None:
                continue
            job_ids.add(alloc.job_id)
            evals.append(
                Evaluation(
                    id=generate_uuid(),
                    priority=alloc.job.priority,
                    type=alloc.job.type,
                    triggered_by=structs.EVAL_TRIGGER_NODE_UPDATE,
                    job_id=alloc.job_id,
                    node_id=node_id,
                    node_modify_index=node_index,
                    status=structs.EVAL_STATUS_PENDING,
                )
            )
        for job in sys_jobs:
            if job.id in job_ids:
                continue
            job_ids.add(job.id)
            evals.append(
                Evaluation(
                    id=generate_uuid(),
                    priority=job.priority,
                    type=job.type,
                    triggered_by=structs.EVAL_TRIGGER_NODE_UPDATE,
                    job_id=job.id,
                    node_id=node_id,
                    node_modify_index=node_index,
                    status=structs.EVAL_STATUS_PENDING,
                )
            )

        return evals

    # -- Eval endpoint (eval_endpoint.go) ------------------------------------

    def eval_dequeue(self, schedulers: List[str], timeout: float):
        """Returns (eval, token, wait_index) — wait_index is the raft
        index the worker must observe locally before snapshotting."""
        ev, token = self.eval_broker.dequeue(schedulers, timeout)
        if ev is None:
            return None, "", 0
        # Floor at the leader's applied index: whatever was committed
        # before this delivery (earlier plans for this eval included) must
        # be visible in the processing worker's snapshot.
        return ev, token, max(self.eval_broker.wait_index(ev.id),
                              self.raft.applied_index)

    def eval_dequeue_batch(self, schedulers: List[str], max_batch: int,
                           timeout: float):
        """Coalescing dequeue: block for one eval, drain up to max_batch-1
        more ready ones (distinct jobs). The broker half of SURVEY.md §7
        'Batched evals' — the worker runs the batch concurrently so the
        device solves stack into one dispatch (ops/coalesce.py).
        Returns (eval, token, wait_index) triples."""
        return [
            (ev, token, max(self.eval_broker.wait_index(ev.id),
                            self.raft.applied_index))
            for ev, token in self.eval_broker.dequeue_batch(
                schedulers, max_batch, timeout)
        ]

    def eval_ack(self, eval_id: str, token: str) -> None:
        self.eval_broker.ack(eval_id, token)

    def eval_touch(self, eval_id: str, token: str) -> None:
        """Reset the outstanding eval's nack timer mid-processing — keeps a
        long first-compile solve from being redelivered (the broker-side
        mechanism is OutstandingReset, eval_broker.go:396-412; the
        reference only exercises it from plan submission, which is too
        late for a pre-plan cold compile)."""
        self.eval_broker.outstanding_reset(eval_id, token)

    def eval_nack(self, eval_id: str, token: str) -> None:
        self.eval_broker.nack(eval_id, token)

    def eval_upsert(self, evals: List[Evaluation]) -> int:
        """Commit evals through the log (Eval.Update / Eval.Create RPC,
        eval_endpoint.go)."""
        return self.raft.apply("eval_update", {"evals": evals}).result()

    def eval_reap(self, eval_ids: List[str], alloc_ids: List[str]) -> int:
        return self.raft.apply(
            "eval_delete", {"evals": eval_ids, "allocs": alloc_ids}
        ).result()

    # -- Plan endpoint (plan_endpoint.go:16-38) ------------------------------

    def plan_submit(self, plan: Plan) -> PlanResult:
        pending = self.plan_queue.enqueue(plan)
        return pending.wait()

    # -- Read plane (server/read_path.py) ------------------------------------

    def confirmed_read_index(self, timeout: float = 2.0) -> int:
        """A leadership-confirmed read index for the linearizable lane
        (no raft log write). DevMode's InProcRaft confirms trivially; a
        ClusterServer follower overrides this to forward Raft.ReadIndex
        to the leader."""
        return self.raft.read_index(timeout=timeout)

    # -- Express endpoint (nomad_tpu/server/express.py) ----------------------

    def express_reconcile(self, job: Job, evals: List[Evaluation]) -> int:
        """Durably hand a bounced-out/failed-over express entry to the
        ordinary scheduler: upsert the job and its evals — the original
        express eval completed-with-successor plus the PENDING reconcile
        eval — through raft (the FSM's eval apply enqueues the pending
        one into the broker). On a ClusterServer a non-leader forwards
        (Express.Reconcile) — the express committer calls this from a
        possibly-deposed server."""
        self.raft.apply("job_register", {"job": job}).result()
        return self.eval_upsert(evals)

    # -- convenience --------------------------------------------------------

    def wait_for_eval(self, eval_id: str, timeout: float = 10.0) -> Evaluation:
        """Poll until the eval reaches a terminal status (the CLI monitor's
        polling loop, command/monitor.go)."""
        import time as _time

        deadline = _time.monotonic() + timeout
        while _time.monotonic() < deadline:
            ev = self.state_store.eval_by_id(eval_id)
            if ev is not None and ev.terminal_status():
                return ev
            _time.sleep(0.01)
        raise TimeoutError(f"eval {eval_id} did not complete")

    def stats(self) -> Dict:
        broker = self.eval_broker.snapshot_stats()
        return {
            "applied_index": self.raft.applied_index,
            "broker_ready": broker.total_ready,
            "broker_unacked": broker.total_unacked,
            "broker_blocked": broker.total_blocked,
            "plan_queue_depth": self.plan_queue.depth(),
            "plan_pipeline": self.plan_applier.stats(),
            "heartbeat_timers": self.heartbeat.num_timers(),
            "scheduler": self.solver_stats(),
            "slo": (self.slo_monitor.summary()
                    if self.slo_monitor is not None else None),
            "admission": self.admission.summary(),
            "express": self.express_lane.summary(),
            "capacity": (self.capacity_accountant.summary()
                         if self.config.capacity_config.enabled else None),
            "raft_observe": (self.raft_observatory.summary()
                             if self.config.raft_observe_config.enabled
                             else None),
            "reads": (self.read_observatory.summary()
                      if self.config.reads_config.enabled else None),
            "read_path": self.read_path.summary(),
            "runtime": (self.runtime_observatory.summary()
                        if self.config.profile_config.enabled else None),
        }

    @staticmethod
    def solver_stats() -> Dict:
        """Device-solver health: the device this process holds, the
        circuit breaker (an open breaker is the one way evals reach the
        host oracle under scheduler_backend="tpu"), the coalescer's
        dispatch/batch counters, and the mirror-cache hit rate. Surfaced
        through Stats()/agent-info. Metrics posture mirrors the
        reference's broker stats (nomad/eval_broker.go:557-575)."""
        from nomad_tpu.scheduler import DEVICE_BREAKER, device_status

        out: Dict = {"device": device_status(),
                     "breaker": DEVICE_BREAKER.stats()}
        try:
            import sys

            coalesce = sys.modules.get("nomad_tpu.ops.coalesce")
            mirror = sys.modules.get("nomad_tpu.tpu.mirror")
            if coalesce is not None:
                eng = coalesce.GLOBAL_SOLVER
                out["coalesce_dispatches"] = eng.dispatches
                out["coalesce_batched_evals"] = eng.coalesced
            if mirror is not None:
                cache = mirror.GLOBAL_MIRROR_CACHE
                out["mirror_cache_hits"] = cache.hits
                out["mirror_cache_misses"] = cache.misses
                out["mirror_delta_rolls"] = cache.delta_rolls
                out["mirror_full_rebuilds"] = cache.full_rebuilds
                out["mirror_rows_restaged"] = cache.rows_restaged
        except Exception:  # stats must never break agent-info
            pass
        return out
