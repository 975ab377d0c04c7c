"""Eval-lifecycle tracing: Dapper-style spans over the broker → scheduler →
solver → plan-apply pipeline.

The reference instruments every hot path with go-metrics timers
(nomad/worker.go:147, nomad/plan_apply.go:149, nomad/fsm.go:148,
nomad/rpc.go:68) but aggregates them — no single evaluation's latency can
be decomposed after the fact. This module adds the per-evaluation view:
lightweight spans with parent links and key/value annotations, recorded
into a bounded, lock-protected ring of traces keyed by evaluation id.

Span classification (producers in parentheses). ``cpu_ms`` is a thread's
CPU time inside the span, beside its wall, on the four spans where
running and waiting cannot be told apart otherwise:

- ``frontdoor.job_register``    Job.Register handler entry -> return; precedes
                                the root and has no parent; ``cpu_ms``
                                (server.job_register)
- ``frontdoor.raft_job``        the job_register raft apply (server.job_register)
- ``frontdoor.raft_eval``       the eval_update raft apply (server.job_register)
- ``eval``                      root; broker enqueue -> ack/failed (eval_broker)
- ``broker.wait``               ready-queue wait, enqueue/nack -> dequeue (eval_broker)
- ``worker.wait_for_index``     FSM catch-up before snapshot (worker)
- ``worker.invoke_scheduler``   the scheduler pass (worker)
- ``solver.staging``            host tensorization: masks + usage; ``cpu_ms``
                                (tpu/solver)
- ``solver.staging.mask``       task_group_constraints (tpu/solver) and
                                device_mask, ``cached`` (tpu/mirror)
- ``solver.staging.usage_base`` the job-independent usage base; ``path`` =
                                hit/shared/roll/rebuild/clean, ``dirty_rows``,
                                ``blocks`` (tpu/mirror build_usage)
- ``solver.staging.usage_job``  base copy + the job's own rows + plan deltas;
                                ``plan_batches`` (tpu/mirror build_usage)
- ``solver.staging.upload``     host -> device puts of the usage tensors and
                                ask constants (tpu/mirror, tpu/solver)
- ``solver.transfer``           per-eval device uploads + dispatch (tpu/solver)
- ``solver.execute``            the rider's wait for the dispatch, and the
                                first fetcher's device wait (ops/coalesce)
- ``solver.execute.hold``       the rider starts to wait -> its batch taken
                                off the pending list (ops/coalesce)
- ``solver.execute.launch``     taken -> the dispatch's ONE jit call returned
                                (grouping, the two host arrays of counts and
                                penalties, the call, any compile; stacking
                                and masks run inside the program); ``width``,
                                ``kind``, ``path``, ``cpu_ms`` of the
                                dispatcher thread (ops/coalesce)
- ``solver.execute.wake``       event set -> the rider runs again (ops/coalesce)
- ``solver.execute.device_wait``  block_until_ready of the first fetcher
                                (ops/coalesce)
- ``solver.readback``           D2H readback + host expansion (ops/coalesce,
                                ops/binpack)
- ``worker.submit_plan``        plan submit -> response (worker)
- ``plan.queue_wait``           plan-queue wait, enqueue -> applier dequeue
- ``plan.evaluate``             plan verification against the snapshot;
                                ``cpu_ms`` of the committer's thread, shared
                                by the batch (plan_pipeline)
- ``plan.apply``                raft apply -> commit (plan_apply)
- ``fsm.apply``                 one FSM log-entry apply, annotated msg_type

A span that XLA compiled under carries ``compiled: true``
(scheduler.acquire_device's jax.monitoring listener).

The span context (``{"trace_id", "span_id"}``) crosses the RPC boundary in
the request envelope: ``Plan.span_ctx`` rides Plan.Submit, and
Eval.Dequeue responses carry the root context so a follower's worker
parents its spans on the leader's broker span (``Tracer.adopt_root``).

Exposition lives in the HTTP tier: ``/v1/evaluation/<id>/trace``,
``/v1/agent/traces``, and Chrome trace-event export (``chrome_trace``)
that loads directly into Perfetto (https://ui.perfetto.dev).
"""

from __future__ import annotations

import collections
import itertools
import os
import threading
import time
from contextlib import contextmanager
from typing import Any, Dict, List, Optional, Tuple

# Monotonic wall clock: epoch-anchored perf_counter, so spans from every
# thread order consistently (time.time() can step backwards under NTP,
# which would break the nesting invariants the trace consumers rely on).
# nomadlint: allow(DET002) -- one-shot wall anchor for the monotonic
# span clock; sampled exactly once at import, never in span math.
_EPOCH = time.time() - time.perf_counter()


def now() -> float:
    return _EPOCH + time.perf_counter()


# Span ids need process-uniqueness, not entropy: os.urandom is a syscall
# (~30us under load — more than the rest of a span's lifecycle combined),
# so ids derive from one urandom seed and a counter pushed through a
# 64-bit odd-multiplier bijection (unique per process, random-looking).
_SPAN_SEED = int.from_bytes(os.urandom(8), "little")
_span_counter = itertools.count(1)


def _new_span_id() -> str:
    mixed = (next(_span_counter) * 0x9E3779B97F4A7C15) & 0xFFFFFFFFFFFFFFFF
    return format(_SPAN_SEED ^ mixed, "016x")


class Span:
    """One timed operation within a trace. Not thread-safe per instance:
    a span is started, annotated, and finished by one component."""

    __slots__ = (
        "trace_id", "span_id", "parent_id", "name", "start", "end",
        "annotations", "thread", "_tracer",
    )

    def __init__(self, tracer: "Tracer", trace_id: str, name: str,
                 parent_id: str = "", start: Optional[float] = None,
                 annotations: Optional[Dict[str, Any]] = None):
        self._tracer = tracer
        self.trace_id = trace_id
        self.span_id = _new_span_id()
        self.parent_id = parent_id
        self.name = name
        self.start = now() if start is None else start
        self.end: Optional[float] = None
        self.annotations: Dict[str, Any] = dict(annotations or {})
        self.thread = threading.current_thread().name

    def annotate(self, key: str, value: Any) -> "Span":
        self.annotations[key] = value
        return self

    def finish(self, end: Optional[float] = None) -> None:
        if self.end is not None:
            return  # idempotent: racing finishers keep the first stamp
        self.end = now() if end is None else end
        self._tracer._record_finished(self)

    def ctx(self) -> Dict[str, str]:
        """The wire-portable span context (rides RPC request envelopes)."""
        return {"trace_id": self.trace_id, "span_id": self.span_id}

    def to_dict(self) -> Dict[str, Any]:
        return {
            "trace_id": self.trace_id,
            "span_id": self.span_id,
            "parent_id": self.parent_id,
            "name": self.name,
            "start": self.start,
            "end": self.end,
            "duration_ms": (
                round((self.end - self.start) * 1000.0, 4)
                if self.end is not None else None
            ),
            "thread": self.thread,
            # Copy: serialization happens outside any lock, and an open
            # span's producer may annotate concurrently — handing out the
            # live dict would race json.dumps with a dict resize.
            "annotations": dict(self.annotations),
        }


class _NullSpan:
    """Inert span: returned when tracing is disabled so call sites never
    branch. Shared singleton; every method is a no-op."""

    __slots__ = ()
    trace_id = ""
    span_id = ""
    parent_id = ""
    name = ""
    start = 0.0
    end: Optional[float] = None
    annotations: Dict[str, Any] = {}

    def annotate(self, key: str, value: Any) -> "_NullSpan":
        return self

    def finish(self, end: Optional[float] = None) -> None:
        pass

    def ctx(self) -> Dict[str, str]:
        return {}

    def to_dict(self) -> Dict[str, Any]:
        return {}


NULL_SPAN = _NullSpan()


class _Trace:
    __slots__ = ("trace_id", "spans", "open", "root_ctx", "dropped",
                 "updated", "done")

    def __init__(self, trace_id: str):
        self.trace_id = trace_id
        self.spans: List[Span] = []          # finished spans
        self.open: Dict[str, Span] = {}      # span_id -> unfinished span
        self.root_ctx: Dict[str, str] = {}   # the root span's wire context
        self.dropped = 0
        self.updated = now()
        self.done = False


class Tracer:
    """Bounded ring of traces. Oldest-inserted trace evicted past
    ``max_traces``; per-trace span count capped at ``max_spans`` (excess
    finishes are counted, not stored). All methods are thread-safe."""

    def __init__(self, max_traces: int = 256, max_spans: int = 512,
                 enabled: bool = True):
        self.max_traces = max(1, max_traces)
        self.max_spans = max(1, max_spans)
        self.enabled = enabled
        self._lock = threading.Lock()
        self._traces: "collections.OrderedDict[str, _Trace]" = (
            collections.OrderedDict()
        )
        # Process-wide loss accounting (mutated under the lock): per-trace
        # ``dropped`` says one eval's trace is partial, but without an
        # aggregate, silent trace loss under 10k-node load is invisible
        # until someone opens the one trace that happens to be truncated.
        self.spans_dropped = 0
        self.traces_evicted = 0

    # -- producing ---------------------------------------------------------

    def start_span(self, trace_id: str, name: str, parent: Any = None,
                   start: Optional[float] = None,
                   annotations: Optional[Dict[str, Any]] = None,
                   root: bool = False):
        """Open a span. ``parent`` is a Span, a wire context dict, or a
        span_id string. ``root=True`` additionally registers the span's
        context as the trace root (what ``root_ctx`` returns)."""
        if not self.enabled or not trace_id:
            return NULL_SPAN
        parent_id = ""
        if isinstance(parent, Span):
            parent_id = parent.span_id
        elif isinstance(parent, dict):
            parent_id = parent.get("span_id", "")
        elif isinstance(parent, str):
            parent_id = parent
        span = Span(self, trace_id, name, parent_id, start, annotations)
        with self._lock:
            tr = self._trace_locked(trace_id)
            tr.open[span.span_id] = span
            tr.updated = now()
            if root:
                tr.root_ctx = span.ctx()
        return span

    def _record_finished(self, span: Span) -> None:
        with self._lock:
            tr = self._traces.get(span.trace_id)
            if tr is None:
                # Trace evicted while the span was open: re-admit it so a
                # slow eval's tail spans aren't silently lost.
                tr = self._trace_locked(span.trace_id)
            tr.open.pop(span.span_id, None)
            if len(tr.spans) >= self.max_spans:
                tr.dropped += 1
                self.spans_dropped += 1
            else:
                tr.spans.append(span)
            tr.updated = now()

    def record_batch(self, parent, stages, prefix: str = "") -> None:
        """Bulk-record already-measured stage cuts (``StageTimer.stages``:
        ``(name, start, end, cpu_s, parent_slot, annotations)``, in start
        order) as finished spans under ONE lock hold — the solver emits
        its stage cuts per eval, and per-span locking was a measurable
        slice of the tracing overhead budget. A cut opened inside another
        parents on that cut's span, every other on ``parent``; ``cpu_s``
        lands as the ``cpu_ms`` annotation."""
        if (not self.enabled or not stages or parent is None
                or isinstance(parent, _NullSpan)):
            return
        spans: List[Optional[Span]] = []
        for cut in stages:
            if cut is None:  # a cut still open (its thread raised past it)
                spans.append(None)
                continue
            name, t0, t1, cpu_s, slot, annotations = cut
            over = spans[slot] if slot >= 0 else None
            s = Span(self, parent.trace_id, prefix + name,
                     (over or parent).span_id, t0, annotations)
            if cpu_s is not None:
                s.annotations["cpu_ms"] = round(cpu_s * 1000.0, 4)
            s.end = t1
            spans.append(s)
        with self._lock:
            tr = self._trace_locked(parent.trace_id)
            for s in spans:
                if s is None:
                    continue
                if len(tr.spans) >= self.max_spans:
                    tr.dropped += 1
                    self.spans_dropped += 1
                else:
                    tr.spans.append(s)
            tr.updated = now()

    def adopt_root(self, trace_id: str, ctx: Dict[str, str]) -> None:
        """Register a REMOTE root context (received over RPC) so local
        spans of this trace can parent on it via root_ctx()."""
        if not self.enabled or not trace_id or not ctx:
            return
        with self._lock:
            tr = self._trace_locked(trace_id)
            if not tr.root_ctx:
                tr.root_ctx = dict(ctx)

    def root_ctx(self, trace_id: str) -> Dict[str, str]:
        with self._lock:
            tr = self._traces.get(trace_id)
            return dict(tr.root_ctx) if tr is not None else {}

    def mark_done(self, trace_id: str) -> None:
        with self._lock:
            tr = self._traces.get(trace_id)
            if tr is not None:
                tr.done = True
                tr.updated = now()

    def _trace_locked(self, trace_id: str) -> _Trace:
        tr = self._traces.get(trace_id)
        if tr is None:
            tr = _Trace(trace_id)
            self._traces[trace_id] = tr
            while len(self._traces) > self.max_traces:
                self._traces.popitem(last=False)
                self.traces_evicted += 1
        return tr

    def stats(self) -> Dict[str, Any]:
        """Aggregate tracer health for /v1/agent/metrics: retained count
        plus the process-wide loss counters — a 10k-node run silently
        evicting traces (or truncating span rings) shows up here, not
        only inside whichever single trace got clipped."""
        with self._lock:
            return {
                "enabled": self.enabled,
                "retained": len(self._traces),
                "max_traces": self.max_traces,
                "max_spans": self.max_spans,
                "spans_dropped": self.spans_dropped,
                "traces_evicted": self.traces_evicted,
            }

    # -- querying ----------------------------------------------------------

    def get_trace(self, trace_id: str) -> Optional[List[Dict[str, Any]]]:
        """All spans of one trace (finished + still-open), sorted by start
        time. None when the trace is unknown (or was evicted)."""
        with self._lock:
            tr = self._traces.get(trace_id)
            if tr is None:
                return None
            spans = list(tr.spans) + list(tr.open.values())
        out = [s.to_dict() for s in spans]
        out.sort(key=lambda d: (d["start"], d["name"]))
        return out

    def traces(self) -> List[Dict[str, Any]]:
        """Summaries of retained traces, most recently updated first."""
        with self._lock:
            items = list(self._traces.values())
        out = []
        for tr in items:
            spans = list(tr.spans)
            # The root is the span registered as such (root_ctx), not the
            # first parentless one: frontdoor.job_register precedes it.
            root_id = tr.root_ctx.get("span_id", "")
            root = next((s for s in spans if s.span_id == root_id), None)
            if root is None and not root_id:
                root = next((s for s in spans if not s.parent_id), None)
            out.append({
                "trace_id": tr.trace_id,
                "spans": len(spans),
                "open_spans": len(tr.open),
                "dropped_spans": tr.dropped,
                "done": tr.done,
                "updated": tr.updated,
                "root": root.name if root is not None else "",
                "duration_ms": (
                    round((root.end - root.start) * 1000.0, 4)
                    if root is not None and root.end is not None else None
                ),
            })
        out.sort(key=lambda d: d["updated"], reverse=True)
        return out

    def chrome_trace(self, trace_id: str) -> Optional[Dict[str, Any]]:
        """Chrome trace-event JSON for one trace — drops straight into
        Perfetto / chrome://tracing. Complete ('X') events in microseconds;
        thread-name metadata events map our thread names to tids."""
        spans = self.get_trace(trace_id)
        if spans is None:
            return None
        tids: Dict[str, int] = {}
        events: List[Dict[str, Any]] = []
        for s in spans:
            tid = tids.setdefault(s["thread"], len(tids) + 1)
            end = s["end"] if s["end"] is not None else now()
            events.append({
                "name": s["name"],
                "cat": "eval",
                "ph": "X",
                "ts": round(s["start"] * 1e6, 1),
                "dur": round((end - s["start"]) * 1e6, 1),
                "pid": 1,
                "tid": tid,
                "args": {
                    **s["annotations"],
                    "span_id": s["span_id"],
                    "parent_id": s["parent_id"],
                },
            })
        for name, tid in tids.items():
            events.append({
                "name": "thread_name", "ph": "M", "pid": 1, "tid": tid,
                "args": {"name": name},
            })
        return {"traceEvents": events, "displayTimeUnit": "ms"}


# ---------------------------------------------------------------------------
# Global tracer + thread-local context
# ---------------------------------------------------------------------------

_global_lock = threading.Lock()
_global: Optional[Tracer] = None


def get_tracer() -> Tracer:
    global _global
    with _global_lock:
        if _global is None:
            _global = Tracer()
        return _global


def set_tracer(tracer: Tracer) -> Tracer:
    global _global
    with _global_lock:
        _global = tracer
    return tracer


def configure(max_traces: int = 256, max_spans: int = 512,
              enabled: bool = True) -> Tracer:
    """Agent telemetry wiring: (re)build the process tracer from the
    ``telemetry { }`` config block knobs."""
    return set_tracer(Tracer(max_traces, max_spans, enabled))


_tls = threading.local()


def current_span():
    """The active span on this thread (set by use_span), or None."""
    return getattr(_tls, "span", None)


@contextmanager
def use_span(span):
    """Install ``span`` as this thread's active span: downstream
    components (solver stages, FSM applies) parent on it without any
    signature plumbing. NULL_SPAN installs as None."""
    prev = getattr(_tls, "span", None)
    _tls.span = span if not isinstance(span, _NullSpan) else None
    try:
        yield span
    finally:
        _tls.span = prev


# ---------------------------------------------------------------------------
# Stage timing — the ONE stage-cut path of a solve: the solver's spans and
# the panel's staging books both come from it (no second parallel timer).
# ---------------------------------------------------------------------------


class _StageCtx:
    """Slotted stage context: measurably cheaper than a generator-based
    contextmanager on the per-solve hot path. With ``cpu`` it reads the
    thread's CPU clock beside the wall clock: a stage runs on one thread
    by construction, so wall minus CPU is the time it sat blocked (a
    lock, the interpreter lock). Asked for where running and waiting
    cannot be told apart otherwise, not on every cut: the thread CPU
    clock is a real system call (6-15 us a read on the sandboxed host of
    the chip, where perf_counter costs 0.09; PERF.md, PR 26)."""

    __slots__ = ("timer", "name", "t0", "cpu0", "slot", "over",
                 "annotations")
    live = True  # False on the inert context: skip work done only to annotate

    def __init__(self, timer: "StageTimer", name: str, cpu: bool):
        self.timer = timer
        self.name = name
        self.cpu0: Optional[float] = 0.0 if cpu else None
        self.annotations: Optional[Dict[str, Any]] = None

    def annotate(self, key: str, value: Any) -> "_StageCtx":
        if self.annotations is None:
            self.annotations = {}
        self.annotations[key] = value
        return self

    def __enter__(self):
        st = self.timer
        # The slot is taken at enter so that ``stages`` is in start order
        # and an enclosing cut always precedes the cuts inside it.
        self.slot = len(st.stages)
        st.stages.append(None)
        self.over = st.open[-1] if st.open else -1
        st.open.append(self.slot)
        if self.cpu0 is not None:
            self.cpu0 = time.thread_time()
        self.t0 = now()
        return self

    def __exit__(self, exc_type, exc, tb):
        t1 = now()
        cpu_s = (None if self.cpu0 is None
                 else time.thread_time() - self.cpu0)
        st = self.timer
        st.open.pop()
        st.stages[self.slot] = (self.name, self.t0, t1, cpu_s, self.over,
                                self.annotations)
        return False


class _NullCtx:
    __slots__ = ()
    live = False

    def annotate(self, key: str, value: Any) -> "_NullCtx":
        return self

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        return False


_NULL_CTX = _NullCtx()


class StageTimer:
    """Named, ordered stage cuts (staging / transfer / execute / readback,
    published as the ``solver.*`` spans), with the thread's CPU
    seconds beside the wall where asked for (``cpu=True``). A cut opened
    inside another (by name ``staging.mask`` inside ``staging``) is
    emitted as its child span. One timer belongs to one solve on one
    thread."""

    __slots__ = ("stages", "open")

    def __init__(self):
        # (name, start, end, cpu_s or None, slot of the enclosing cut or
        # -1, annotations or None), in start order; None while a cut is
        # open.
        self.stages: List[Optional[tuple]] = []
        self.open: List[int] = []

    def stage(self, name: str, cpu: bool = False) -> _StageCtx:
        return _StageCtx(self, name, cpu)

    def add(self, name: str, t0: float, t1: float, **annotations) -> None:
        """Record a cut measured elsewhere (another thread's stamps), under
        the cut open on this thread. It carries no CPU reading."""
        self.stages.append((name, t0, t1, None,
                            self.open[-1] if self.open else -1,
                            annotations or None))

    def durations_ms(self) -> Dict[str, float]:
        """Summed per-stage wall in milliseconds."""
        out: Dict[str, float] = {}
        for cut in self.stages:
            if cut is not None:
                out[cut[0]] = out.get(cut[0], 0.0) + (cut[2] - cut[1]) * 1000.0
        return out

    def wall_cpu_ms(self, name: str) -> Tuple[float, float]:
        """(wall, CPU) milliseconds summed over the cuts named ``name``."""
        wall = cpu = 0.0
        for cut in self.stages:
            if cut is not None and cut[0] == name:
                wall += cut[2] - cut[1]
                cpu += cut[3] or 0.0
        return wall * 1000.0, cpu * 1000.0

    def emit_spans(self, parent, prefix: str = "solver.") -> None:
        """Retroactively record each stage as a child span of ``parent``
        (a live Span), preserving the measured start/end stamps — one
        bulk insert, one lock hold."""
        if parent is None or isinstance(parent, _NullSpan):
            return
        tracer = getattr(parent, "_tracer", None) or get_tracer()
        tracer.record_batch(parent, self.stages, prefix)


class _NullStageTimer(StageTimer):
    """Inert stage timer handed out when no timer is installed: ``stage``
    costs one shared-singleton enter/exit on the solve hot path."""

    __slots__ = ()

    def stage(self, name: str, cpu: bool = False):
        return _NULL_CTX

    def add(self, name: str, t0: float, t1: float, **annotations) -> None:
        pass

    def emit_spans(self, parent, prefix: str = "solver.") -> None:
        pass


NULL_STAGES = _NullStageTimer()


def active_stages() -> StageTimer:
    """The stage timer installed on this thread (by the solver entry
    point), or the inert singleton."""
    return getattr(_tls, "stages", None) or NULL_STAGES


@contextmanager
def use_stages(st: StageTimer):
    prev = getattr(_tls, "stages", None)
    _tls.stages = None if isinstance(st, _NullStageTimer) else st
    try:
        yield st
    finally:
        _tls.stages = prev


def stage(name: str):
    """Record ``name`` on this thread's active stage timer (no-op when
    none is installed) — used by the device-path fetch closures to cut
    execute/readback without plumbing a timer through their signatures."""
    return active_stages().stage(name)
