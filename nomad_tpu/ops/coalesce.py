"""Coalescing solve engine: many concurrent evals, one device dispatch.

The TPU reformulation of the reference's optimistic concurrency
(/root/reference/nomad/worker.go:45-125 — N workers schedule simultaneously
against snapshots; conflicts surface at plan apply). Here concurrent
workers' counts-solves are stacked on an eval axis and dispatched as ONE
vmapped water-fill, so K in-flight evaluations cost one device round trip
instead of K. This is the dispatch half of the broker's coalescing dequeue
(eval_broker.py dequeue_batch; SURVEY.md §7 "Batched evals").

No unconditional batching window: the dispatcher drains whatever is
pending the moment it wakes, so an idle system pays ~zero added latency
while a busy one coalesces naturally (submissions arriving during an
in-flight dispatch pile up for the next one). The one exception is an
ANNOUNCED burst: a batch worker that just dequeued K compatible evals
calls hint_burst(K), and the dispatcher holds its next dispatch until
those K solves have all arrived or a short deadline passes — without
this, the K eval threads' staggered host prep (snapshot, masks) lands
their submits a few ms apart and the burst fragments into several
small dispatches instead of one stacked one.

One program a dispatch: the launch (_launch_rows) is ONE call into the
device runtime for either program family at any width. The riders' rows
go into the jitted entry (solve_waterfill_rows / solve_greedy_rows) as
the device arrays each entry already holds, the per-eval counts,
penalties and candidate keys as three small typed host arrays; the
stacking on the eval axis (a lone solve is B = 1), each evaluation's
candidates (scheduler/candidates.py), the exact scan's active masks and
the choice of water-fill kernel all happen inside that program. Nothing eager runs on
the dispatcher thread beside it: each eager op or transfer costs as much
host time as the launch itself (0.7-0.8 ms on the benchmark's host, where
a width-1 water-fill used to make 19 such calls around a 27 us kernel).
The span solver.execute.launch covers exactly this: taken off the
pending list -> that one call returned.
"""

from __future__ import annotations

import logging
import os
import threading
import time
from functools import partial
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from nomad_tpu import telemetry, trace
from nomad_tpu.ops import pallas_solve
from nomad_tpu.ops.binpack import (
    bucket,
    restrict_to_candidates,
    solve_greedy,
    solve_waterfill,
)
from nomad_tpu.parallel import mesh as mesh_lib
from nomad_tpu.scheduler.candidates import NO_KEY

logger = logging.getLogger("nomad_tpu.coalesce")

# Cap on the vmapped eval-axis batch: dispatch in chunks of at most this
# many entries so the power-of-two bucket set {1, 2, 4, 8} is the ENTIRE
# steady-state compile surface (warm_batch_shapes compiles exactly these).
MAX_BATCH_BUCKET = 8

# Burst-hold tuning. The dispatcher keeps holding while announced solves
# keep ARRIVING (progress-based): burst fill time scales with batch size
# and node count (K eval threads' host prep contends on the GIL), so a
# fixed window either fragments big bursts or stalls small ones. GAP is
# the give-up threshold between consecutive arrivals; WINDOW is the hard
# cap on total hold — the worst added latency when announced evals never
# submit (e.g. scale-downs that need no solve).
# 50ms: must ride out a GC pause or GIL-contention stall in the middle
# of K eval threads' host prep at 10k+ nodes; precise member accounting
# (burst_done) keeps the give-up path rare, so the gap mostly never pays.
BURST_GAP_S = float(os.environ.get("NOMAD_TPU_COALESCE_GAP", "0.05"))
BURST_WINDOW_S = float(os.environ.get("NOMAD_TPU_COALESCE_WINDOW", "0.25"))

# Per-thread burst membership: False = this thread is an announced burst
# member that hasn't yet accounted against the expectation (its first
# submit or its burst_done will). Threads outside any burst never have
# the attribute and never touch the expectation.
_BURST_TLS = threading.local()


def _stack_columns(rows):
    """[B rows of per-eval tensors] -> one [B, ...] tensor a column."""
    return tuple(jnp.stack(col) for col in zip(*rows))


def _candidates_only(eligible, counts, keys, spread, total, used0,
                     job_count0, tg_count0, bw_avail, bw_used0, ask, bw_ask,
                     job_distinct, tg_distinct):
    """``eligible`` [B, N] narrowed, row by row, to each evaluation's
    candidates (binpack.restrict_to_candidates; ``spread`` names the
    program family). ``total`` and ``bw_avail`` are the rows' own
    ([B, N, .]) or, unstacked, the one mirror's every row shares. No
    ``keys``: nothing is narrowed."""
    if keys is None:
        return eligible
    shared = None if total.ndim == 2 else 0
    return jax.vmap(
        restrict_to_candidates,
        in_axes=(shared, 0, 0, 0, shared) + (0,) * 6 + (None, None, None),
    )(total, used0, job_count0, tg_count0, bw_avail, bw_used0, eligible,
      ask, bw_ask, counts, keys, job_distinct, tg_distinct, spread)


@partial(jax.jit,
         static_argnames=("job_distinct", "tg_distinct", "kernel", "mesh"))
def solve_waterfill_rows(rows, counts, penalties, job_distinct, tg_distinct,
                         kernel="jnp", mesh=None, keys=None):
    """ONE program a water-fill dispatch, whatever its width. ``rows`` is
    a tuple of B rows of the ten device arrays of solve_waterfill's
    positional order, as each rider holds them; ``counts`` (int32[B]),
    ``penalties`` (float32[B]) and the evaluations' candidate ``keys``
    (int32[B], scheduler/candidates.py; None restricts nothing) ride as
    host arrays, typed, so that no value retraces. The stacking on the eval axis (the ``[None]``
    of a lone solve is the case B = 1) happens in here, then each row's
    eligibility is narrowed to its evaluation's candidates, then the
    Pallas kernel
    (``kernel="pallas"``, decided before the call by pallas_solve.selected)
    or the vmapped closed form. Every eval solves independently against
    its own optimistic view, like concurrent reference workers. Returns
    (counts[B, N], remaining[B])."""
    stacked = _stack_columns(rows)
    if mesh is not None:
        stacked = mesh_lib.constrain_eval_stack(
            mesh, stacked, mesh_lib.WF_SPECS)
    (total, _sched_cap, used0, job_count0, tg_count0, bw_avail, bw_used0,
     eligible, ask, bw_ask) = stacked
    eligible = _candidates_only(
        eligible, counts, keys, True, total, used0, job_count0, tg_count0,
        bw_avail, bw_used0, ask, bw_ask, job_distinct, tg_distinct)
    stacked = stacked[:7] + (eligible,) + stacked[8:]
    if kernel == "pallas":
        return pallas_solve.solve_waterfill_pallas_batched(
            *stacked, counts, penalties, job_distinct, tg_distinct)
    return jax.vmap(solve_waterfill, in_axes=(0,) * 12 + (None, None))(
        *stacked, counts, penalties, job_distinct, tg_distinct)


# Which of the ten tensors of a row the evals of one exact dispatch share
# (total, sched_cap, bw_avail: the mirror's) and which are each eval's own.
_SHARED_COLS = (0, 1, 5)
_EVAL_COLS = (2, 3, 4, 6, 7, 8, 9)


@partial(jax.jit,
         static_argnames=("k", "job_distinct", "tg_distinct", "mesh"))
def solve_greedy_rows(shared, rows, counts, penalties, k, job_distinct,
                      tg_distinct, mesh=None, keys=None):
    """ONE program an exact-scan dispatch, whatever its width: the vmap of
    solve_greedy over the eval axis, each row the IDENTICAL sequential
    scan it would run alone (rows never read each other: bit-equal to B
    lone dispatches, fuzz-pinned). ``shared`` is the mirror's (total,
    sched_cap, bw_avail), read once by every row (the dispatcher groups
    exact entries by mirror identity; broadcasting beats materializing B
    copies of the [N, .] node data); ``rows`` is a tuple of B rows of the
    seven per-eval tensors (_EVAL_COLS). The active masks are built in
    here from ``counts`` (int32[B], host), and each row's eligibility is
    narrowed to its evaluation's candidates (``keys``, int32[B], host;
    None restricts nothing). Returns (idxs[B, k], oks[B, k])."""
    total, sched_cap, bw_avail = shared
    stacked = _stack_columns(rows)
    if mesh is not None:
        stacked = mesh_lib.constrain_eval_stack(
            mesh, stacked, [mesh_lib.WF_SPECS[i] for i in _EVAL_COLS])
    used0, job_count0, tg_count0, bw_used0, eligible, ask, bw_ask = stacked
    eligible = _candidates_only(
        eligible, counts, keys, False, total, used0, job_count0, tg_count0,
        bw_avail, bw_used0, ask, bw_ask, job_distinct, tg_distinct)
    active = jnp.arange(k, dtype=jnp.int32)[None, :] < counts[:, None]
    idxs, oks, _scores = jax.vmap(
        solve_greedy,
        in_axes=(None, None, 0, 0, 0, None, 0, 0, 0, 0, 0, 0,
                 None, None, None),
    )(
        total, sched_cap, used0, job_count0, tg_count0, bw_avail, bw_used0,
        eligible, ask, bw_ask, active, penalties, k, job_distinct,
        tg_distinct,
    )
    return idxs, oks


def _panel():
    """The solver panel (SOLVER_PANEL is the process-wide
    /v1/agent/solver book), or None. Late import: the coalescer must
    stay importable (and the dispatch must not fail) when the solver
    stack never initialized — e.g. pure-kernel benchmarks."""
    try:
        from nomad_tpu.tpu.solver import SOLVER_PANEL
    except Exception:  # pragma: no cover - import breakage only
        return None
    return SOLVER_PANEL


class _Entry:
    __slots__ = ("args", "event", "group", "index", "error", "kind", "k",
                 "cand_key", "traced", "t_taken", "t_launched",
                 "launch_cpu_s")

    def __init__(self, args, kind: str = "wf", k: int = 0,
                 cand_key: int = NO_KEY):
        self.args = args
        # The evaluation's candidate key (scheduler/candidates.py): a
        # per-eval scalar of the dispatch like count and penalty.
        self.cand_key = cand_key
        self.event = threading.Event()
        self.group: Optional["_Group"] = None
        self.index = 0
        self.error: Optional[BaseException] = None
        # Which program family this solve stacks into: "wf" (water-fill
        # counts, the columnar path) or "exact" (the greedy scan of
        # small counts, k = padded count bucket). Only same-kind,
        # same-k entries share a dispatch.
        self.kind = kind
        self.k = k
        # Made on its rider's thread: does the rider carry a stage timer?
        # Only then the dispatcher stamps it, on the span clock
        # (trace.now): its batch taken off the pending list (the hold is
        # over), and the dispatch's one jit call returned (launch and any
        # compile done; the event is set next), with the dispatcher
        # thread's CPU seconds from taken to launched. The group carries
        # t_ready. taken <= launched <= ready.
        self.traced = trace.active_stages() is not trace.NULL_STAGES
        self.t_taken: Optional[float] = None
        self.t_launched: Optional[float] = None
        self.launch_cpu_s = 0.0

    def result(self) -> Tuple[np.ndarray, int]:
        """Block for the dispatch, then return (counts[N], n_unplaced) —
        (idxs[k], oks[k]) for exact entries — or re-raise the dispatch
        failure instead of hanging."""
        # The dispatcher-hold + device wall both land in the caller's
        # 'execute' stage cut (trace.stage no-ops when the calling thread
        # carries no stage timer).
        st = trace.active_stages()
        with st.stage("execute"):
            # A stamp of its own, after the execute cut's: a cut that
            # starts with the cut it is in cannot be told from it.
            traced = st is not trace.NULL_STAGES
            t0 = trace.now() if traced else 0.0
            self.event.wait()
            if traced and self.t_launched is not None:
                self._cut_wait(st, t0, trace.now())
        if self.group is None:
            raise RuntimeError("coalesced solve failed") from self.error
        return self.group.fetch(self.index)

    def _cut_wait(self, st, t0: float, woke: float) -> None:
        """Cut the rider's wait [t0, woke] at the dispatcher's stamps, as
        far as they fall inside it (the rider's overlapped host work may
        have outlasted the hold): hold + launch + wake are the wait."""
        taken = min(max(self.t_taken, t0), woke)
        launched = min(max(self.t_launched, taken), woke)
        group = self.group
        st.add("execute.hold", t0, taken)
        st.add("execute.launch", taken, launched, kind=self.kind,
               width=group.width if group is not None else 1,
               path=group.path if group is not None else "",
               cpu_ms=round(self.launch_cpu_s * 1000.0, 4))
        st.add("execute.wake", launched, woke)


class _Group:
    """One dispatched batch: device arrays + lazily-fetched host results."""

    __slots__ = ("counts_dev", "remaining_dev", "_fetch_lock", "_host",
                 "width", "t0", "path", "t_ready")

    def __init__(self, counts_dev, remaining_dev, width: int = 1,
                 t0: Optional[float] = None, path: str = ""):
        self.counts_dev = counts_dev
        self.remaining_dev = remaining_dev
        self._fetch_lock = threading.Lock()
        self._host = None
        # Eval-stack width of the dispatch (real entries, not padding)
        # and its dispatch timestamp: the first fetch records the
        # (width, wall) pair on the solver panel's batch-width axis.
        self.width = width
        self.t0 = t0
        # The program family that carried it, and when the first
        # fetcher's block_until_ready returned (trace.now).
        self.path = path
        self.t_ready: Optional[float] = None

    def _materialize(self) -> None:
        """First fetch blocks on the device and copies the whole batch
        down; later fetches index the cached host arrays."""
        with self._fetch_lock:
            if self._host is None:
                # Split the first fetcher's wall into the shared
                # execute/readback stage cuts (the solve's one
                # StageTimer; the benchmark reads them as spans). An
                # async device fault surfaces here and raises to the
                # fetching eval.
                with trace.stage("execute"), \
                        trace.stage("execute.device_wait"):
                    jax.block_until_ready(
                        (self.counts_dev, self.remaining_dev)
                    )
                self.t_ready = trace.now()
                with trace.stage("readback"):
                    counts, remaining = jax.device_get(
                        (self.counts_dev, self.remaining_dev)
                    )
                self._host = (np.asarray(counts), np.asarray(remaining))
                if self.t0 is not None:
                    # Dispatch→ready wall, rider-attributed like the
                    # panel's per-solve device_ms (an upper bound when
                    # the fetcher arrives late).
                    panel = _panel()
                    if panel is not None:
                        panel.record_dispatch(
                            self.width,
                            (time.perf_counter() - self.t0) * 1000.0,
                        )

    def fetch(self, index: int) -> Tuple[np.ndarray, int]:
        self._materialize()
        counts, remaining = self._host
        return counts[index], int(remaining[index])


class _ExactGroup(_Group):
    """A stacked exact-scan dispatch: device outs are (idxs[B, k],
    oks[B, k]) riding the base class's counts/remaining slots."""

    __slots__ = ()

    def fetch(self, index: int) -> Tuple[np.ndarray, np.ndarray]:
        self._materialize()
        idxs, oks = self._host
        return idxs[index], oks[index]


class CoalescingSolver:
    """Process-wide dispatcher stacking concurrent counts-solves.

    submit(...) returns a fetch() closure with the same contract as
    binpack.solve_counts_async: () -> (counts[N] np.int32, n_unplaced).
    """

    def __init__(self):
        self._lock = threading.Lock()
        self._cond = threading.Condition(self._lock)
        self._pending: List[_Entry] = []
        self._thread: Optional[threading.Thread] = None
        # Count of in-flight dispatches (the daemon thread's current batch
        # plus any inline fast-path dispatches).
        self._active = 0
        # Announced burst: how many announced evals are still unresolved
        # (no submit seen AND not yet reported done), the hard deadline,
        # and the last-progress timestamp (give-up gap). Zero = never
        # wait. Resolution is precise, not queue-depth guessing: each
        # announced eval thread accounts exactly once — its first submit
        # (burst-aware via _BURST_TLS) or its completion (burst_done) —
        # so evals that never reach the coalescer (exact-path small
        # counts, scale-downs) release the hold the moment they finish
        # instead of taxing unrelated solves until the window expires.
        self._burst_outstanding = 0
        self._burst_deadline = 0.0
        self._burst_last = 0.0
        self._burst_gap = BURST_GAP_S
        # Monotonic burst generation: members account only against their
        # own burst, so stragglers from a given-up or over-announced
        # burst can't decrement a successor's expectation and release
        # its hold early.
        self._burst_gen = 0
        # Observability: how many dispatches carried how many evals,
        # which program family carried each ("pallas" / "jnp" water-fill,
        # "exact" greedy scan), and how many stacked dispatches failed
        # and were re-solved one entry at a time.
        self.dispatches = 0
        self.coalesced = 0
        self.paths: Dict[str, int] = {}
        self.batch_retries = 0
        # (span clock, the dispatcher thread's CPU clock) when it took a
        # batch with a traced rider in it; None for a batch with none.
        self._taken: Optional[Tuple[float, float]] = None

    def hint_burst(self, n: int, window_s: float = BURST_WINDOW_S,
                   gap_s: float = BURST_GAP_S) -> int:
        """Announce ``n`` concurrent evals about to be processed (a batch
        worker's dequeue_batch drain): the dispatcher holds its next
        dispatch until every announced eval resolves (first submit or
        burst_done), progress stalls for ``gap_s``, or ``window_s``
        passes. Worst case for an expectation that never resolves (a
        crashed eval thread) is the window, then it resets.

        Returns a generation token to pass to burst_begin, scoping each
        member thread's accounting to ITS burst — without it a straggler
        from a given-up or over-announced burst would decrement a
        successor's expectation and release that hold early. A lone eval
        (n<=1) gets the -1 sentinel: it is NOT a burst member, and the
        sentinel can never match a real generation, so passing it to
        burst_begin cannot decrement a concurrent burst's expectation."""
        if n <= 1:
            return -1
        with self._cond:
            now = time.monotonic()
            self._burst_gen += 1
            # REPLACE any unresolved expectation, never stack onto it:
            # the generation bump just orphaned the previous burst's
            # members (their gen no longer matches, so they can never
            # account), and a stacked total could then only drain via
            # the gap/window give-up — up to BURST_GAP_S of dispatch
            # hold whenever two workers' hints overlap.
            self._burst_outstanding = n
            self._burst_deadline = now + window_s
            self._burst_last = now
            self._burst_gap = gap_s
            self._cond.notify()
            return self._burst_gen

    def burst_begin(self, token: Optional[int] = None) -> None:
        """Mark the calling thread as an announced burst member that has
        not yet accounted against the expectation. Call once per eval
        thread before scheduler invocation, with the token its worker's
        hint_burst returned (None = the current generation; -1 = the
        lone-eval sentinel, which matches no generation and so accounts
        against nothing)."""
        if token is None:
            with self._lock:
                token = self._burst_gen
        _BURST_TLS.gen = token
        _BURST_TLS.counted = False

    def burst_done(self) -> None:
        """The calling eval thread finished processing. If none of its
        submits accounted it (it never reached the coalescer — a
        scale-down, a no-placement diff, failed prep; exact-path solves
        DO reach it now via submit_exact and account on first submit),
        resolve its slot now so the hold doesn't wait for a solve that
        will never come."""
        if getattr(_BURST_TLS, "counted", True):
            return
        _BURST_TLS.counted = True
        with self._cond:
            if (self._burst_outstanding > 0
                    and getattr(_BURST_TLS, "gen", -1) == self._burst_gen):
                self._burst_outstanding -= 1
                self._burst_last = time.monotonic()
                self._cond.notify()

    def _ensure_thread(self) -> None:
        if self._thread is None or not self._thread.is_alive():
            self._thread = threading.Thread(
                target=self._run, daemon=True, name="solve-coalescer"
            )
            self._thread.start()

    def submit(
        self, total, sched_cap, used0, job_count0, tg_count0, bw_avail,
        bw_used0, eligible, ask, bw_ask, count: int, penalty: float,
        job_distinct: bool = False, tg_distinct: bool = False,
        cand_key: int = NO_KEY,
    ):
        entry = _Entry((
            total, sched_cap, used0, job_count0, tg_count0, bw_avail,
            bw_used0, eligible, ask, bw_ask, count, penalty,
            bool(job_distinct), bool(tg_distinct),
        ), cand_key=int(cand_key))
        self._enqueue(entry)
        return entry.result

    def submit_exact(
        self, total, sched_cap, used0, job_count0, tg_count0, bw_avail,
        bw_used0, eligible, ask, bw_ask, count: int, penalty: float,
        job_distinct: bool = False, tg_distinct: bool = False,
        cand_key: int = NO_KEY, scan_steps: int = 0,
    ):
        """Queue one exact greedy scan (count <= EXACT_THRESHOLD).
        Concurrent exact solves of one (node bucket, count bucket,
        distinct flags) shape stack on the eval axis and dispatch as ONE
        solve_greedy_rows program — each stacked row runs the
        identical independent scan, so results are bit-equal to a lone
        dispatch. ``scan_steps`` picks the count bucket where it is the
        larger (the program of the whole group, for a remainder of it).
        Returns fetch() -> (node_indices[count], ok[count])."""
        entry = _Entry((
            total, sched_cap, used0, job_count0, tg_count0, bw_avail,
            bw_used0, eligible, ask, bw_ask, count, penalty,
            bool(job_distinct), bool(tg_distinct),
        ), kind="exact", k=bucket(max(count, scan_steps)),
            cand_key=int(cand_key))

        self._enqueue(entry)

        def fetch_exact():
            idxs, oks = entry.result()
            return idxs[:count], oks[:count]

        return fetch_exact

    def _enqueue(self, entry: _Entry) -> None:
        # Always hand off to the dispatcher thread — an inline fast path
        # was A/B-measured ~2ms SLOWER per eval: the handoff is what lets
        # the caller's overlapped host work (bulk id generation) run while
        # the dispatcher drives the device.
        with self._cond:
            self._ensure_thread()
            self._pending.append(entry)
            if (self._burst_outstanding > 0
                    and getattr(_BURST_TLS, "counted", True) is False
                    and getattr(_BURST_TLS, "gen", -1) == self._burst_gen):
                # First submit from a member of the CURRENT burst: its
                # slot in the expectation is resolved, and the arrival is
                # progress for the give-up gap. Unrelated threads and
                # stale-generation stragglers touch neither — they can't
                # extend the hold or release someone else's.
                _BURST_TLS.counted = True
                self._burst_outstanding -= 1
                self._burst_last = time.monotonic()
            self._cond.notify()

    # -- dispatcher ---------------------------------------------------------

    def _run(self) -> None:
        while True:
            with self._cond:
                while not self._pending:
                    self._cond.wait()
                # Announced-burst hold: while announced evals are still
                # unresolved, keep waiting as long as progress (submits,
                # burst_done reports) keeps landing within the gap,
                # hard-capped at the window deadline. A full dispatch
                # chunk never waits — more pending can't improve its
                # coalescing. Give-up clears the residual expectation so
                # later lone evals never inherit the wait.
                now = time.monotonic()
                while (self._burst_outstanding > 0
                       and len(self._pending) < MAX_BATCH_BUCKET):
                    deadline = min(self._burst_last + self._burst_gap,
                                   self._burst_deadline)
                    if now >= deadline:
                        self._burst_outstanding = 0
                        break
                    self._cond.wait(deadline - now)
                    now = time.monotonic()
                batch = self._pending
                self._pending = []
                self._active += 1
            try:
                self._dispatch(batch)
            except BaseException as exc:  # noqa: BLE001 — last-resort net
                # _dispatch fails open per entry, so anything landing
                # here is unexpected (a bug, MemoryError, interpreter
                # teardown). A dead dispatcher would park every current
                # AND future waiter forever — fail this batch's waiters
                # and keep the loop alive instead.
                for e in batch:
                    if e.group is None and e.error is None:
                        e.error = exc
                        e.event.set()
            finally:
                with self._cond:
                    self._active -= 1

    def quiesce(self, timeout: float = 5.0) -> bool:
        """Wait for the dispatcher to go idle (no queued or in-flight
        solves, queued or inline). Process teardown while a thread sits
        inside an XLA call aborts the interpreter (std::terminate) — clean
        shutdowns and test harnesses drain first. Returns False on
        timeout."""
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            with self._lock:
                if not self._pending and self._active == 0:
                    return True
            time.sleep(0.01)
        return False

    def _dispatch(self, batch: List[_Entry]) -> None:
        # Group by (padded node count, program kind, count bucket, static
        # flags): only same-shaped, same-specialization solves stack into
        # one program. Water-fill entries carry k=0, so the two kinds can
        # never share a key. Exact entries additionally key on MIRROR
        # IDENTITY (id of the total tensor — entries hold refs, so ids
        # are stable for the dispatch): a stacked exact dispatch shares
        # the node tensors across its rows (solve_greedy_rows' ``shared``)
        # instead of materializing B copies, which is only sound when
        # every row reads the same mirror. Same-generation burst members
        # do; cross-generation stragglers dispatch separately.
        groups: Dict[Tuple, List[_Entry]] = {}
        # Off the pending list: the hold is over.
        self._taken = ((trace.now(), time.thread_time())
                       if any(e.traced for e in batch) else None)
        for e in batch:
            total = e.args[0]
            key = (total.shape[0], e.kind, e.k, e.args[12], e.args[13],
                   id(total) if e.kind == "exact" else None)
            groups.setdefault(key, []).append(e)

        for (n, _kind, _k, jd, td, _mid), entries in groups.items():
            # Chunk at the largest warmed eval-axis bucket: the compile
            # surface stays exactly the warmed set (1, 2, 4, 8) no matter
            # how deep a load spike's drain is.
            for start in range(0, len(entries), MAX_BATCH_BUCKET):
                chunk = entries[start:start + MAX_BATCH_BUCKET]
                try:
                    self._dispatch_group(chunk, jd, td)
                except Exception:
                    # Solve each entry individually so waiters never hang
                    # on a batch-level error — the SAME entry at B = 1,
                    # counted and logged so a stacked program that cannot
                    # run is never silent. An entry whose retry also
                    # fails carries the exception to its fetch() caller.
                    self.batch_retries += 1
                    telemetry.incr_counter(
                        ("scheduler", "coalesce", "batch_retry"))
                    logger.exception(
                        "coalesced dispatch of %d entries failed; "
                        "re-solving one at a time", len(chunk))
                    for e in chunk:
                        try:
                            self._launch([e], jd, td)
                        except Exception as exc:
                            e.error = exc
                            self._launched([e])

    def _launched(self, entries: List[_Entry]) -> None:
        """Wake one dispatch's riders; where one of the batch is traced,
        first stamp them taken and launched (wall, and the dispatcher's
        CPU since their batch was taken)."""
        if self._taken is not None:
            taken, cpu_taken = self._taken
            cpu_s = time.thread_time() - cpu_taken
            launched = trace.now()
            for e in entries:
                e.t_taken = taken
                e.launch_cpu_s = cpu_s
                e.t_launched = launched
        for e in entries:
            e.event.set()

    def _count_path(self, path: str) -> None:
        self.paths[path] = self.paths.get(path, 0) + 1

    def _dispatch_group(self, entries: List[_Entry], jd: bool, td: bool) -> None:
        self.dispatches += 1
        telemetry.incr_counter(("scheduler", "coalesce", "dispatch"))
        telemetry.add_sample(
            ("scheduler", "coalesce", "batch_size"), float(len(entries))
        )
        if len(entries) > 1:
            self.coalesced += len(entries)
        if self._launch(entries, jd, td, t0=time.perf_counter()):
            panel = _panel()
            if panel is not None:
                panel.record_single_program_dispatch()

    def _launch(self, entries: List[_Entry], jd: bool, td: bool,
                t0: Optional[float] = None) -> bool:
        """One dispatch's launch (_launch_rows), its riders pointed at
        the group and woken. Returns whether it went out as one call."""
        head = entries[0]
        a_dev, b_dev, path, single = _launch_rows(
            [e.args for e in entries], head.kind, head.k, jd, td,
            keys=[e.cand_key for e in entries])
        self._count_path(path)
        cls = _ExactGroup if head.kind == "exact" else _Group
        group = cls(a_dev, b_dev, width=len(entries), t0=t0, path=path)
        for i, e in enumerate(entries):
            e.group = group
            e.index = i
        self._launched(entries)
        return single


def _launch_rows(rows, kind: str, k: int, jd: bool, td: bool, keys=None):
    """THE launch of a dispatch of either family at any width, shared by
    the dispatcher, its per-entry retry (B = 1) and the warm calls, so
    the warmed set is provably the dispatched set. Off the mesh it is ONE
    call into the device runtime: the riders' rows go in as they are, the
    per-eval scalars (count, penalty, candidate key) as three small typed
    host arrays, and everything else (stacking, candidates, active masks,
    the kernel) happens inside the jitted entry.
    The eval axis pads to its power-of-two bucket; padding rows repeat
    row 0's arrays with count 0 (a no-op solve). On a configured mesh
    (parallel/mesh.py) the rows' placement calls run first, in front of
    the same entries. Returns (a_dev[B, .], b_dev[B, .], path, single):
    (counts, remaining) for "wf", (idxs, oks) for "exact"; path names the
    program family that carried it; single says the launch was one call."""
    pad = bucket(len(rows), floor=1) - len(rows)
    counts = np.array([r[10] for r in rows] + [0] * pad, dtype=np.int32)
    penalties = np.array([r[11] for r in rows] + [0.0] * pad,
                         dtype=np.float32)
    keys = np.array(list(keys or [NO_KEY] * len(rows)) + [NO_KEY] * pad,
                    dtype=np.int32)
    rows10 = [r[:10] for r in rows]
    n_padded = rows10[0][0].shape[0]
    mesh = mesh_lib.mesh_for_nodes(n_padded)
    if mesh is not None:
        rows10 = [mesh_lib.shard_waterfill_args(mesh, r) for r in rows10]
    rows10 = tuple(rows10) + (rows10[0],) * pad
    if kind == "exact":
        path = "exact"
        out = solve_greedy_rows(
            tuple(rows10[0][i] for i in _SHARED_COLS),
            tuple(tuple(r[i] for i in _EVAL_COLS) for r in rows10),
            counts, penalties, k, jd, td, mesh, keys)
    else:
        path = ("pallas" if mesh is None and pallas_solve.selected(n_padded)
                else "jnp")
        out = solve_waterfill_rows(
            rows10, counts, penalties, jd, td, path, mesh, keys)
    return (*out, path, mesh is None)


# Process-wide engine shared by all workers (like GLOBAL_MIRROR_CACHE).
GLOBAL_SOLVER = CoalescingSolver()

# In-flight direct device work — warm compiles and exact-path solves run
# jitted calls on their OWN threads (not via the queue), so the
# dispatcher's idle flag can't see them.
_warm_lock = threading.Lock()
_active_warms = 0


class device_activity:
    """Context manager marking a thread as inside direct device work
    (dispatch/compile outside the coalescer queue), so quiesce_all can
    drain it before interpreter teardown."""

    def __enter__(self):
        global _active_warms
        with _warm_lock:
            _active_warms += 1
        return self

    def __exit__(self, *exc):
        global _active_warms
        with _warm_lock:
            _active_warms -= 1
        return False


def quiesce_all(timeout: float = 10.0) -> bool:
    """Wait until no device work is in flight anywhere — queued/
    dispatching coalescer solves AND direct jit dispatches (warm compiles,
    exact-path solves). Process teardown while a daemon thread sits inside
    an XLA call aborts the interpreter (std::terminate from the C++
    runtime); callers drain first. Returns False on timeout."""
    deadline = time.monotonic() + timeout
    if not GLOBAL_SOLVER.quiesce(max(deadline - time.monotonic(), 0.01)):
        return False
    while time.monotonic() < deadline:
        with _warm_lock:
            if _active_warms == 0:
                return True
        time.sleep(0.02)
    return False


# Best-effort drain of device work before interpreter teardown for EVERY
# embedder, not just the test conftest and bench (which call quiesce_all
# themselves): a daemon worker still inside an XLA dispatch when CPython
# finalizes aborts the process ("FATAL: exception not rethrown"). This
# covers the common case — a script whose solves have completed by exit —
# with a bounded 2s wait; an embedder exiting UNDER LOAD must stop its
# Server first (Server.shutdown), since producers still submitting can
# outrun any drain.
import atexit  # noqa: E402  (intentionally after module definitions)

atexit.register(quiesce_all, 2.0)




def _noop_row(n_padded: int):
    """One entry's args at one node-axis bucket whose solve is a no-op
    (count 0): what the warm calls stack."""
    zero4 = jnp.zeros((n_padded, 4), dtype=jnp.int32)
    zcap = jnp.zeros((n_padded, 2), dtype=jnp.float32)
    zvec = jnp.zeros((n_padded,), dtype=jnp.int32)
    elig = jnp.zeros((n_padded,), dtype=bool)
    return (zero4, zcap, zero4, zvec, zvec, zvec, zvec, elig,
            jnp.zeros((4,), dtype=jnp.int32), jnp.int32(0),
            0, 0.0, False, False)


def warm_batch_shapes(n_padded: int, buckets=(1, 2, 4, 8), stop=None) -> int:
    """Pre-compile the water-fill for each eval-axis bucket at one
    node-axis bucket. Dispatch chunking caps real batches at
    MAX_BATCH_BUCKET, so the default buckets are the ENTIRE steady-state
    compile surface — and they run through the dispatcher's own launch
    (_launch_rows), so warm shapes can't drift from real dispatch shapes.
    Returns the number of dispatches issued."""
    args = _noop_row(n_padded)
    done = 0
    with device_activity():
        for b in buckets:
            if stop is not None and stop():
                return done
            counts_dev, _rem, _path, _single = _launch_rows(
                [args] * b, "wf", 0, False, False)
            jax.block_until_ready(counts_dev)
            done += 1
    return done


def warm_exact_batch_shapes(n_padded: int, counts=(8, 16, 32, 64, 128),
                            buckets=(2, 4, 8), stop=None) -> int:
    """Pre-compile the STACKED exact greedy scan for each (count bucket ×
    eval-axis width) at one node-axis bucket — the third axis of the
    shape-key space the cross-eval batcher adds. Width 1 is warmed by
    warm_shapes' real solve_group dispatches; the widths here are the
    coalesced ones a burst's first drain would otherwise compile
    in-window (blamed, correctly, on bucket_crossing by the compile-
    attribution ring). Runs through _launch_rows — the SAME launch real
    dispatches use — so warm shapes can't drift. Returns the number of
    dispatches issued."""
    args = _noop_row(n_padded)
    done = 0
    with device_activity():
        for k in sorted({bucket(c) for c in counts}):
            for b in buckets:
                if stop is not None and stop():
                    return done
                idxs_dev, _oks, _path, _single = _launch_rows(
                    [args] * b, "exact", k, False, False)
                jax.block_until_ready(idxs_dev)
                done += 1
    return done
