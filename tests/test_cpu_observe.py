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
from nomad_tpu.capacity import CapacityAccountant
from nomad_tpu.cpu_observe import BOOK, THREAD_TABLE, Collector, thread_role
from nomad_tpu.profile_observe import ROLES, RuntimeObservatory, classify_thread
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
    "observer_cpu_ms_per_eval.drain":
        ("panel.interp_observer_cpu_ms", "window.evals"),
    "observer_cpu_ms_per_eval.steady":
        ("panel.interp_observer_cpu_ms", "window.evals"),
    "accountant_cpu_ms_per_poll.drain":
        ("panel.interp_thread_accountant_cpu_ms",
         "panel.interp_accountant_polls"),
    "profiler_cpu_ms_per_pass.drain":
        ("panel.interp_thread_profiler_cpu_ms",
         "panel.interp_profiler_passes"),
    "raft_loop_cpu_ms_per_plan.drain":
        ("panel.interp_thread_raft_cpu_ms", "pipeline.plans"),
    "interp_unnamed_cpu_ms_per_eval.drain":
        ("panel.interp_unnamed_cpu_ms", "window.evals"),
}
# One clock tick of the process's CPU accounting, in ms.
TICK_MS = 1000.0 / os.sysconf("SC_CLK_TCK")


def burn(seconds: float) -> None:
    start = time.thread_time()
    while time.thread_time() - start < seconds:
        pass


def role_ms(role: str) -> float:
    return BOOK.snapshot()[f"interp_thread_{role}_cpu_ms"]


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


# -- threads by role -----------------------------------------------------------


def _named(prefix: str) -> str:
    return prefix + ("test" if prefix.endswith("-") else "-test")


@pytest.mark.parametrize(
    "name,role",
    [(_named(prefix), role) for prefix, role, _ in THREAD_TABLE]
    + [("Thread-9 (process_request_thread)", "serving"),
       ("bench-send-0", "other")],
    ids=lambda v: v if isinstance(v, str) else None)
def test_a_thread_that_burns_cpu_lands_in_its_role(name, role):
    assert thread_role(name)[0] == role
    release, burnt = threading.Event(), threading.Event()

    def body():
        burn(0.03)
        burnt.set()
        release.wait(10.0)

    before = role_ms(role)
    thread = threading.Thread(target=body, name=name, daemon=True)
    thread.start()
    try:
        assert burnt.wait(10.0)
        assert role_ms(role) - before >= 25.0
    finally:
        release.set()
        thread.join(10.0)
    assert not thread.is_alive()


def test_a_long_lived_thread_is_read_by_its_clock_and_keeps_its_last_reading():
    release = threading.Event()
    burnt = threading.Event()

    def body():
        burn(0.04)
        burnt.set()
        release.wait(10.0)

    thread = threading.Thread(target=body, name="solve-coalescer-test")
    before = role_ms("dispatcher")
    thread.start()
    try:
        assert burnt.wait(10.0)
        running = role_ms("dispatcher")
        assert running - before >= 35.0
    finally:
        release.set()
        thread.join(10.0)
    assert not thread.is_alive()
    # Ended: banked at its last reading, and the role never falls.
    ended = role_ms("dispatcher")
    assert running <= ended <= running + 2.0
    assert thread not in BOOK.threads._last


def test_the_dispatcher_is_watched_from_its_start():
    from nomad_tpu.ops.coalesce import CoalescingSolver

    engine = CoalescingSolver()
    with engine._cond:
        engine._ensure_thread()
    BOOK.snapshot()
    assert BOOK.threads._last[engine._thread][0] == "dispatcher"


@pytest.mark.parametrize("banks", [False, True], ids=["read", "banked"])
def test_a_thread_that_ends_between_two_snapshots_keeps_its_cpu(banks):
    """A thread seen by one read keeps that reading once it has ended; a
    ``cpu_observe.Thread`` banks all of its CPU itself, seen or not."""
    kind = cpu_observe.Thread if banks else threading.Thread
    seen = threading.Event()

    def body():
        burn(0.03)
        seen.wait(10.0)
        burn(0.03)

    before = BOOK.snapshot()
    thread = kind(target=body, name="worker-9-batch0", daemon=True)
    thread.start()
    time.sleep(0.01)
    BOOK.snapshot()
    seen.set()
    thread.join(10.0)
    assert not thread.is_alive()
    after = BOOK.snapshot()
    worker = (after["interp_thread_worker_cpu_ms"]
              - before["interp_thread_worker_cpu_ms"])
    native = after["interp_native_cpu_ms"] - before["interp_native_cpu_ms"]
    if banks:
        assert worker >= 55.0
    else:
        # The burn after the read falls outside every role: native.
        assert worker < 55.0 and native >= 25.0


def test_the_book_keeps_no_thread_that_banked_and_ended():
    """Without a read between them, threads that bank as they end are not
    held on to: the set of banked threads keeps only those still alive."""
    for i in range(50):
        t = cpu_observe.Thread(target=burn, args=(0.0005,),
                               name=f"rpc-stream-{i}", daemon=True)
        t.start()
        t.join(10.0)
        assert not t.is_alive()
    assert len(BOOK.threads._closed) <= 5


def test_the_threads_never_sum_past_process_cpu():
    """Threads start, burn and end, some banking, while another thread
    reads the book: every read closes, and no role's total falls."""
    stop = threading.Event()
    reads = []

    def churn(i):
        while not stop.is_set():
            kind = cpu_observe.Thread if i % 2 else threading.Thread
            t = kind(target=burn, args=(0.002,), name=f"worker-9-batch{i}",
                     daemon=True)
            t.start()
            t.join(10.0)

    def reader():
        while not stop.is_set():
            reads.append(BOOK.snapshot())

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    threads = [threading.Thread(target=churn, args=(i,), daemon=True)
               for i in range(2 * (os.cpu_count() or 1) + 2)]
    threads.append(threading.Thread(target=reader, daemon=True))
    try:
        for t in threads:
            t.start()
        time.sleep(1.0)
    finally:
        stop.set()
        sys.setswitchinterval(interval)
        for t in threads:
            t.join(10.0)
    assert not any(t.is_alive() for t in threads)
    assert len(reads) >= 2
    roles = [f"interp_thread_{r}_cpu_ms" for r in cpu_observe.THREAD_ROLES]
    for snap in reads:
        threads_ms = sum(snap[k] for k in roles)
        assert threads_ms + snap["interp_native_cpu_ms"] == pytest.approx(
            snap["interp_process_cpu_ms"])
        assert threads_ms <= snap["interp_process_cpu_ms"] + TICK_MS
    for a, b in zip(reads, reads[1:]):
        for k in roles:
            assert b[k] >= a[k], k


def test_a_started_server_names_every_thread_it_starts(monkeypatch):
    """Accountant, profiler, raft, heartbeat, RPC, HTTP, workers and their
    batch threads: none of them is ``other``."""
    from http.client import HTTPConnection

    from nomad_tpu.api.http import HTTPServer
    from nomad_tpu.server.cluster import form_cluster, wait_for_leader

    started = []
    start = threading.Thread.start

    def record(thread):
        started.append(thread.name)
        start(thread)

    monkeypatch.setattr(threading.Thread, "start", record)
    (srv,) = form_cluster(1, ServerConfig(
        scheduler_backend="host", scheduler_workers=2, eval_batch_size=2))
    http = None
    try:
        wait_for_leader([srv])

        class Agent:
            server = srv
            debug_enabled = staticmethod(lambda: False)

        http = HTTPServer(Agent(), port=0)
        http.start()
        conn = HTTPConnection("127.0.0.1", http.port, timeout=10)
        conn.request("GET", "/v1/jobs")
        conn.getresponse().read()
        conn.close()
        for _ in range(4):
            srv.node_register(mock.node())
        eval_ids = [srv.job_register(mock.job())[0] for _ in range(2)]
        for eid in eval_ids:
            srv.wait_for_eval(eid, timeout=20.0)
        srv.runtime_observatory.sample_once()
    finally:
        if http is not None:
            http.shutdown()
        srv.shutdown()
    roles = {name: thread_role(name)[0] for name in started}
    assert {"accountant", "profiler", "raft", "serving", "worker",
            "pipeline", "observers"} <= set(roles.values()), roles
    assert [n for n, role in roles.items() if role == "other"] == []


def test_the_observers_count_their_polls_and_passes():
    snap0 = BOOK.snapshot()
    CapacityAccountant(lambda: None).refresh()
    observatory = RuntimeObservatory()
    observatory.sample_once()
    observatory.sample_once()
    observatory.refresh()
    snap1 = BOOK.snapshot()
    assert (snap1["interp_accountant_polls"]
            - snap0["interp_accountant_polls"]) == 1
    assert (snap1["interp_profiler_passes"]
            - snap0["interp_profiler_passes"]) == 2
    assert (snap1["interp_profiler_ledger_cpu_ms"]
            > snap0["interp_profiler_ledger_cpu_ms"])


@pytest.mark.parametrize("prefix,sampled",
                         [(p, s) for p, _, s in THREAD_TABLE])
def test_the_profiler_files_each_name_of_the_table_under_its_pinned_roles(
        prefix, sampled):
    assert sampled in ROLES
    assert classify_thread(_named(prefix)) == sampled


# -- the roles against the process ---------------------------------------------


def test_in_a_busy_run_the_roles_stay_under_process_cpu():
    before = BOOK.snapshot()
    committer0, apply0 = BOOK.committer.ms(), BOOK.apply.ms()
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
        "dispatch": (after["interp_dispatch_cpu_ms"]
                     - before["interp_dispatch_cpu_ms"]),
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
        "window.evals": 10, "coalescer.dispatches": 4, "pipeline.plans": 5,
        "panel.interp_accountant_polls": 3,
        "panel.interp_profiler_passes": 60})
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
