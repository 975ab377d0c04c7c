"""The interpreter book: where the process's CPU goes, by thread role, and
how long the cyclic garbage collector stops the interpreter.

Every host stage of a server shares one Python interpreter, so a stage's
wall-clock span also holds the time its thread waited for whoever held the
interpreter lock. The book counts CPU instead:

- ``eval``, ``committer`` and ``apply``: thread CPU (``time.thread_time``)
  inside ``charge()`` scopes, two clock reads a scope: an evaluation's
  ``Worker._process``, one batch of the plan pipeline, one FSM apply.
  Scopes nest on a thread and a role keeps its own CPU only: the FSM
  apply that a one-member raft runs on the committer's own thread is the
  ``apply`` role's, not the committer's. So the roles never overlap.
- every thread: a role from its name (``THREAD_TABLE``), and the
  thread's CPU clock (``clock_gettime``), read for every live
  thread when the book is read and at no other time. The roles partition
  the threads; ``eval``, ``committer`` and ``apply`` are finer cuts of
  the ``worker`` and ``pipeline`` threads.
- the observers' work: polls of the capacity accountant, passes of the
  runtime profiler, and the CPU of the profiler's byte ledger.
- the collector: a ``gc.callbacks`` hook that times every collection of
  every generation, and the interpreter's own cumulative counts per
  generation (``gc.get_stats``).
- the process: ``time.process_time``. What the threads leave of it is
  ``native``: the runtime's own threads (XLA's), and Python threads that
  started and ended between two reads without banking.

The totals run from process start and are process-wide, like the solver
panel that serves them: a reader differences two snapshots. This module
imports nothing of jax: a host-backend server imports it.
"""

from __future__ import annotations

import gc
import threading
import time
from contextlib import contextmanager
from typing import Dict, Set, Tuple

# Per-thread stack of open scopes: [thread CPU at entry, CPU of the scopes
# nested in it]. Each thread reads and writes only its own stack.
_SCOPES = threading.local()


class CpuRole:
    """Thread CPU spent in one role's ``charge()`` scopes, less what the
    scopes nested in them spent."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._seconds = 0.0

    @contextmanager
    def charge(self):
        stack = _SCOPES.__dict__.setdefault("stack", [])
        frame = [time.thread_time(), 0.0]
        stack.append(frame)
        try:
            yield
        finally:
            stack.pop()
            spent = time.thread_time() - frame[0]
            if stack:
                stack[-1][1] += spent
            with self._lock:
                self._seconds += spent - frame[1]

    def ms(self) -> float:
        with self._lock:
            return self._seconds * 1000.0


# Every thread of a server, by the prefix of its name; the first match
# wins ("raft-observatory" before "raft-"). A row: the prefix, the
# book's role, and the role the runtime profiler files the thread's wall
# samples under (``profile_observe.ROLES``).
THREAD_TABLE: Tuple[Tuple[str, str, str], ...] = (
    ("worker-", "worker", "worker"),  # its -batch<i> and -touch too
    ("shape-warmer", "worker", "other"),
    ("nomad-uuid", "worker", "other"),
    ("plan-pipeline", "pipeline", "pipeline-committer"),
    ("solve-coalescer", "dispatcher", "other"),
    ("capacity-accountant", "accountant", "observer"),
    ("runtime-profiler", "profiler", "observer"),
    ("raft-observatory", "observers", "observer"),
    ("read-observatory", "observers", "observer"),
    ("stats-emitter", "observers", "observer"),
    ("slo-monitor", "observers", "observer"),
    ("raft-", "raft", "raft"),
    ("heartbeat-wheel", "serving", "heartbeat-wheel"),
    ("express-commit", "serving", "express-committer"),
    ("http-server", "serving", "http"),
    ("rpc-", "serving", "other"),
    ("scada-", "serving", "other"),
    ("membership-", "serving", "other"),
    ("retry-join-", "serving", "other"),
    ("eval-broker-", "serving", "other"),  # its wait and nack timers
    ("failed-eval-reaper", "serving", "other"),
    ("eval-readmit", "serving", "other"),
    ("periodic-gc", "serving", "other"),
)
THREAD_ROLES = ("worker", "pipeline", "dispatcher", "accountant", "profiler",
                "observers", "raft", "serving", "other")


def thread_role(name: str) -> Tuple[str, str]:
    """Thread name -> (the book's role, the profiler's role).
    ThreadingHTTPServer's request handlers ("Thread-N
    (process_request_thread)") serve; the main thread and every name the
    table lacks (a client's threads, a benchmark's) are ``other``."""
    for prefix, role, sampled in THREAD_TABLE:
        if name.startswith(prefix):
            return role, sampled
    if "process_request_thread" in name:
        return "serving", "http"
    if name == "MainThread":
        return "other", "main"
    return "other", "other"


def _thread_clock(tid: int) -> int:
    """The CPU clock of this process's thread ``tid`` (Linux: per-thread
    ``CPUCLOCK_SCHED``), built as ``pthread_getcpuclockid`` builds it but
    from the kernel's thread id: the pthread handle of a thread that has
    just ended points at freed memory, where a read can crash the
    process. The clock of a thread that has ended reads ``EINVAL``."""
    return (~tid << 3) | 6


class ThreadClocks:
    """Every live Python thread's CPU clock, summed by role, read only
    when the book is read. Each thread keeps its last reading; a thread
    that ended since is banked in its role at that reading, so a role's
    total never falls. A ``Thread`` banks its whole CPU itself as it
    ends: one that starts and ends between two reads is counted too."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._banked = dict.fromkeys(THREAD_ROLES, 0.0)
        # Live thread -> (role, seconds at the last read).
        self._last: Dict[threading.Thread, Tuple[str, float]] = {}
        # Threads that banked themselves and may still be alive.
        self._closed: Set[threading.Thread] = set()

    def bank(self) -> None:
        """The calling thread, as it ends: its CPU, in its role."""
        me = threading.current_thread()
        spent = time.thread_time()
        role = thread_role(me.name)[0]
        with self._lock:
            self._last.pop(me, None)
            self._closed = {t for t in self._closed if t.is_alive()}
            self._closed.add(me)
            self._banked[role] += spent

    def read(self) -> Dict[str, float]:
        """Seconds of CPU by role, every thread ever seen."""
        # A thread not yet running, or past its end, has no clock.
        live = [t for t in threading.enumerate() if t.is_alive()]
        with self._lock:
            listed = set(live)
            self._closed &= listed
            for thread in live:
                if thread in self._closed:
                    continue
                last = self._last.get(thread)
                role = last[0] if last else thread_role(thread.name)[0]
                try:
                    seconds = time.clock_gettime(
                        _thread_clock(thread.native_id))
                except OSError:
                    continue  # it ended after the listing
                self._last[thread] = (role, seconds)
            for thread in [t for t in self._last if t not in listed]:
                role, seconds = self._last.pop(thread)
                self._banked[role] += seconds
            out = dict(self._banked)
            for role, seconds in self._last.values():
                out[role] += seconds
        return out


class Thread(threading.Thread):
    """A thread that banks its CPU in the book as it ends: for threads
    that can start and end between two reads (one an evaluation, an RPC
    request, a round to a peer)."""

    def run(self) -> None:
        try:
            super().run()
        finally:
            BOOK.threads.bank()


class Count:
    """A running count that many threads add to."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self.n = 0

    def add(self) -> None:
        with self._lock:
            self.n += 1


class Collector:
    """Wall time the cyclic collector held the interpreter, every
    generation. Collections never overlap (the interpreter runs one at a
    time, and calls the hook from inside it), so the hook needs no lock."""

    def __init__(self) -> None:
        self.pause_s = 0.0
        self._started = 0.0

    def install(self) -> None:
        if self._on_gc not in gc.callbacks:
            gc.callbacks.append(self._on_gc)

    def _on_gc(self, phase: str, info: Dict) -> None:
        if phase == "start":
            self._started = time.perf_counter()
        else:
            self.pause_s += time.perf_counter() - self._started


class InterpreterBook:
    def __init__(self) -> None:
        self.eval = CpuRole()
        self.committer = CpuRole()
        self.apply = CpuRole()
        self.profiler_ledger = CpuRole()
        self.threads = ThreadClocks()
        self.accountant_polls = Count()
        self.profiler_passes = Count()
        self.collector = Collector()

    def snapshot(self) -> Dict[str, float]:
        """Running totals in ms, counts, and collections per generation."""
        threads = {role: s * 1000.0 for role, s in self.threads.read().items()}
        # Read after the threads: the process's clock holds theirs.
        process = time.process_time() * 1000.0
        native = process - sum(threads.values())
        out = {
            "interp_process_cpu_ms": process,
            "interp_eval_cpu_ms": self.eval.ms(),
            "interp_dispatch_cpu_ms": threads["dispatcher"],
            "interp_native_cpu_ms": native,
            "interp_observer_cpu_ms": (threads["accountant"]
                                       + threads["profiler"]
                                       + threads["observers"]),
            "interp_unnamed_cpu_ms": threads["other"] + native,
            "interp_accountant_polls": self.accountant_polls.n,
            "interp_profiler_passes": self.profiler_passes.n,
            "interp_profiler_ledger_cpu_ms": self.profiler_ledger.ms(),
            "interp_gc_pause_ms": self.collector.pause_s * 1000.0,
        }
        for role, ms in threads.items():
            out[f"interp_thread_{role}_cpu_ms"] = ms
        for gen, stats in enumerate(gc.get_stats()):
            out[f"interp_gc_gen{gen}_collections"] = stats["collections"]
        return out


# Process-wide, like the solver panel and the pipeline's totals.
BOOK = InterpreterBook()
