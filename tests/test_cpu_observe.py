"""The interpreter book (``nomad_tpu/cpu_observe.py``): CPU by thread role
and the collector's pauses, read by the benchmark's ``counters`` reader
as ``panel.interp_*`` and ``pipeline.cpu_ms`` / ``pipeline.apply_cpu_ms``.
"""

from __future__ import annotations

import gc
import json
import logging
import os
import subprocess
import sys
import threading
import time
import types

import pytest

from nomad_tpu import cpu_observe, mock, structs
from nomad_tpu.cpu_observe import BOOK, Collector, ThreadRole
from nomad_tpu.server.server import Server, ServerConfig
from nomad_tpu.server.worker import Worker

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.readers import counters  # noqa: E402

BURN_S = 0.05
# The metric files this book feeds: {name: (counter, per)}.
METRICS = {
    "interp_process_cpu_ms_per_eval.drain":
        ("panel.interp_process_cpu_ms", "window.evals"),
    "interp_gc_pause_ms_per_eval.drain":
        ("panel.interp_gc_pause_ms", "window.evals"),
    "eval_cpu_ms_per_eval.drain":
        ("panel.interp_eval_cpu_ms", "window.evals"),
    "dispatcher_cpu_ms_per_dispatch.drain":
        ("panel.interp_dispatch_cpu_ms", "coalescer.dispatches"),
    "committer_cpu_ms_per_plan.drain": ("pipeline.cpu_ms", "pipeline.plans"),
    "fsm_apply_cpu_ms_per_plan.drain":
        ("pipeline.apply_cpu_ms", "pipeline.plans"),
    "interp_process_cpu_ms_per_eval.steady":
        ("panel.interp_process_cpu_ms", "window.evals"),
    "interp_gc_pause_ms_per_eval.steady":
        ("panel.interp_gc_pause_ms", "window.evals"),
}


def burn(seconds: float) -> None:
    start = time.thread_time()
    while time.thread_time() - start < seconds:
        pass


def metric_file(name: str) -> dict:
    with open(os.path.join(ROOT, "benchmark", "metrics",
                           f"{name}.json")) as f:
        return json.load(f)


# -- evaluation CPU ------------------------------------------------------------


class _Worker(Worker):
    """A worker whose evaluation burns BURN_S of CPU and which hands out
    one dequeue and then stops."""

    def __init__(self, batch):
        server = types.SimpleNamespace(
            config=types.SimpleNamespace(
                eval_batch_size=len(batch), scheduler_backend="host",
                eval_nack_timeout=60.0),
            logger=logging.getLogger("test_cpu_observe"))
        super().__init__(server, 0)
        self._batch = list(batch)
        self.threads = set()

    def _next(self):
        batch, self._batch = self._batch, []
        if not batch:
            self._stop.set()
        return batch

    def _dequeue_batch(self, n):
        return self._next()

    def _dequeue_evaluation(self):
        batch = self._next()
        return batch[0] if batch else None

    def _wait_for_index(self, index, timeout):
        pass

    def _invoke_scheduler(self, ev, token, planner):
        self.threads.add(threading.current_thread().name)
        burn(BURN_S)
        return True

    def _send_ack(self, eval_id, token, ack):
        pass


@pytest.mark.parametrize("width", [1, 2], ids=["lone", "batch"])
def test_an_evaluations_cpu_is_charged_on_its_own_thread(width):
    batch = [(mock.evaluation(), f"token-{i}", 0) for i in range(width)]
    worker = _Worker(batch)
    before = BOOK.eval.ms()
    worker.run()
    per_eval = (BOOK.eval.ms() - before) / width
    assert 40.0 <= per_eval <= 60.0
    if width > 1:
        assert worker.threads == {f"worker-0-batch{i}" for i in range(width)}
    else:
        assert worker.threads == {threading.current_thread().name}


def test_a_nested_scope_is_its_own_roles_cpu_only():
    outer, inner = cpu_observe.CpuRole(), cpu_observe.CpuRole()
    with outer.charge():
        burn(0.02)
        with inner.charge():
            burn(0.03)
    assert 25.0 <= inner.ms() <= 40.0
    assert 15.0 <= outer.ms() <= 28.0


# -- the collector -------------------------------------------------------------


def test_a_forced_collection_is_counted_and_timed():
    collector = Collector()
    collector.install()
    collector.install()  # one hook however often it is installed
    try:
        assert gc.callbacks.count(collector._on_gc) == 1
        before = BOOK.snapshot()["interp_gc_gen2_collections"]
        gc.collect()
        assert BOOK.snapshot()["interp_gc_gen2_collections"] >= before + 1
        assert collector.pause_s > 0.0
    finally:
        gc.callbacks.remove(collector._on_gc)


def test_a_server_installs_the_books_collector():
    Server(ServerConfig(scheduler_backend="host", scheduler_workers=0))
    assert gc.callbacks.count(BOOK.collector._on_gc) == 1
    before = BOOK.collector.pause_s
    gc.collect()
    assert BOOK.collector.pause_s > before


# -- long-lived threads --------------------------------------------------------


def test_a_long_lived_thread_is_read_by_its_clock_and_keeps_its_last_reading():
    release = threading.Event()
    burnt = threading.Event()

    def body():
        burn(0.04)
        burnt.set()
        release.wait(10.0)

    thread = threading.Thread(target=body, name="test-dispatcher")
    role = ThreadRole()
    assert role.ms() == 0.0
    thread.start()
    role.watch(thread)
    try:
        assert burnt.wait(10.0)
        running = role.ms()
        assert running >= 35.0
    finally:
        release.set()
        thread.join(10.0)
    assert not thread.is_alive()
    assert role.ms() == running  # ended: the last reading, no error
    # A new thread in the role adds to what the ended one read.
    successor = threading.Thread(target=burn, args=(0.02,))
    successor.start()
    successor.join(10.0)
    role.watch(successor)
    assert role.ms() == running


def test_the_dispatcher_is_watched_from_its_start():
    from nomad_tpu.ops.coalesce import CoalescingSolver

    engine = CoalescingSolver()
    with engine._cond:
        engine._ensure_thread()
    assert engine.cpu._thread is engine._thread


# -- the roles against the process ---------------------------------------------


def test_in_a_busy_run_the_roles_stay_under_process_cpu():
    from nomad_tpu.ops.coalesce import GLOBAL_SOLVER

    before = BOOK.snapshot()
    committer0, apply0 = BOOK.committer.ms(), BOOK.apply.ms()
    dispatch0 = GLOBAL_SOLVER.cpu.ms()
    srv = Server(ServerConfig(scheduler_backend="host", scheduler_workers=4))
    try:
        srv.start()
        for _ in range(10):
            srv.node_register(mock.node())
        eval_ids = [srv.job_register(mock.job())[0] for _ in range(4)]
        for eid in eval_ids:
            ev = srv.wait_for_eval(eid, timeout=20.0)
            assert ev.status == structs.EVAL_STATUS_COMPLETE
    finally:
        srv.shutdown()
    after = BOOK.snapshot()
    stats = srv.plan_applier.stats()
    roles = {
        "eval": after["interp_eval_cpu_ms"] - before["interp_eval_cpu_ms"],
        "committer": stats["cpu_ms"] - committer0,
        "apply": stats["apply_cpu_ms"] - apply0,
        "dispatch": GLOBAL_SOLVER.cpu.ms() - dispatch0,
    }
    process = (after["interp_process_cpu_ms"]
               - before["interp_process_cpu_ms"])
    assert roles["eval"] > 0 and roles["committer"] > 0 \
        and roles["apply"] > 0, roles
    assert sum(roles.values()) <= process, (roles, process)


# -- what the benchmark reads --------------------------------------------------


def test_every_metric_file_reads_a_counter_of_the_snapshot():
    srv = Server(ServerConfig(scheduler_backend="host", scheduler_workers=0))
    snap = counters.snapshot(srv)
    for name, (counter, per) in METRICS.items():
        source = metric_file(name)["source"]
        assert source == {"reader": "counters", "counter": counter,
                          "per": per}
        for key in (counter, per):
            if not key.startswith("window."):
                assert isinstance(snap.get(key), (int, float)), key
    assert not any("_per_" in k for k in snap if k.startswith("panel.interp"))


@pytest.mark.parametrize("name", sorted(METRICS))
def test_a_parent_without_the_book_leaves_the_metric_out(name):
    args = metric_file(name)["source"]
    ctx = types.SimpleNamespace(counters={
        "window.evals": 10, "coalescer.dispatches": 4, "pipeline.plans": 5})
    assert counters.read(args, ctx) is None
    ctx.counters[args["counter"]] = 20.0
    assert counters.read(args, ctx) > 0


def test_the_book_imports_no_jax():
    code = ("import sys\n"
            "import nomad_tpu.cpu_observe as c\n"
            "c.BOOK.snapshot()\n"
            "assert 'jax' not in sys.modules, sorted(sys.modules)\n")
    proc = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                          capture_output=True, text=True, timeout=60)
    assert proc.returncode == 0, proc.stderr[-2000:]
