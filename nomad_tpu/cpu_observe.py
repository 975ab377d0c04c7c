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
- a long-lived thread, such as the coalescer's dispatcher: a
  ``ThreadRole`` its owner keeps, read by the thread's CPU clock
  (``pthread_getcpuclockid``) only when it is read.
- the collector: a ``gc.callbacks`` hook that times every collection of
  every generation, and the interpreter's own cumulative counts per
  generation (``gc.get_stats``).
- the process: ``time.process_time``, native threads (XLA's) included.
  What the roles leave of it is everything else.

The totals run from process start and are process-wide, like the solver
panel that serves them: a reader differences two snapshots. This module
imports nothing of jax: a host-backend server imports it.
"""

from __future__ import annotations

import gc
import threading
import time
from contextlib import contextmanager
from typing import Dict, Optional

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


class ThreadRole:
    """CPU of the long-lived thread that holds one role, read from outside
    it by the thread's CPU clock. A thread that took the role over from an
    ended one adds to the ended one's last reading."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._thread: Optional[threading.Thread] = None
        self._last = 0.0
        self._banked = 0.0

    def watch(self, thread: threading.Thread) -> None:
        with self._lock:
            self._banked += self._read_locked()
            self._thread = thread
            self._last = 0.0

    def _read_locked(self) -> float:
        thread = self._thread
        # An ended thread's clock id names no thread (or another one):
        # it keeps the last reading taken while it ran.
        if thread is not None and thread.is_alive():
            try:
                self._last = time.clock_gettime(
                    time.pthread_getcpuclockid(thread.ident))
            except OSError:
                pass  # it ended after the check
        return self._last

    def ms(self) -> float:
        with self._lock:
            return (self._banked + self._read_locked()) * 1000.0


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
        self.collector = Collector()

    def snapshot(self) -> Dict[str, float]:
        """Running totals in ms, and collections per generation."""
        out = {
            "interp_process_cpu_ms": time.process_time() * 1000.0,
            "interp_eval_cpu_ms": self.eval.ms(),
            "interp_gc_pause_ms": self.collector.pause_s * 1000.0,
        }
        for gen, stats in enumerate(gc.get_stats()):
            out[f"interp_gc_gen{gen}_collections"] = stats["collections"]
        return out


# Process-wide, like the solver panel and the pipeline's totals.
BOOK = InterpreterBook()
