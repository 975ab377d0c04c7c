"""Device acquisition: one process, in process, loud.

With ``scheduler_backend="tpu"`` the server claims the device itself in
``start()`` before any worker exists, and a ``jax.devices()`` that raises
stops the start; nothing returns a host scheduler because a device is
missing. ``scheduler_backend="host"`` is the explicit host choice and never
imports jax. The compile cache is placed where acquisition happens.
"""

import os
import subprocess
import sys

import pytest

import nomad_tpu.scheduler as sched
from nomad_tpu.scheduler import acquire_device, device_status, new_scheduler
from nomad_tpu.server.cluster import ClusterConfig, ClusterServer
from nomad_tpu.server.server import Server, ServerConfig
from nomad_tpu.state import StateStore

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def unacquired(monkeypatch):
    """This process as it was before any acquisition (the suite shares
    one interpreter, so an earlier test has usually acquired)."""
    monkeypatch.setattr(sched, "_device", None)


def _no_child_processes(monkeypatch):
    def refuse(*a, **k):
        raise AssertionError("device acquisition started a child process")

    monkeypatch.setattr(subprocess, "Popen", refuse)
    monkeypatch.setattr(os, "fork", refuse)


def test_acquisition_is_in_process(unacquired, monkeypatch):
    _no_child_processes(monkeypatch)
    assert device_status() == {"acquired": False}
    import jax

    got = acquire_device()
    assert got["acquire_s"] >= 0.0
    assert {k: v for k, v in got.items() if k != "acquire_s"} == {
        "platform": jax.devices()[0].platform,
        "device_kind": jax.devices()[0].device_kind,
        "count": len(jax.devices()),
        "compile_cache": os.environ.get("JAX_COMPILATION_CACHE_DIR"),
    }
    assert device_status() == {"acquired": True, **got}
    assert acquire_device() == got  # idempotent


def test_acquisition_after_caller_touched_jax(unacquired, monkeypatch):
    """A parent that called jax.devices() first (a solver_mesh block, a
    fingerprinting dev client) holds the chip; acquisition must be that
    same process's claim, not a second claimant."""
    import jax

    first = jax.devices()
    _no_child_processes(monkeypatch)
    assert acquire_device()["count"] == len(first)


def _device_absent(monkeypatch):
    import jax

    def no_device(*a, **k):
        raise RuntimeError("Unable to initialize backend 'tpu': no device")

    monkeypatch.setattr(jax, "devices", no_device)


@pytest.mark.parametrize("make", [
    lambda cfg: Server(cfg),
    lambda cfg: ClusterServer(cfg, ClusterConfig(node_id="s1")),
], ids=["Server", "ClusterServer"])
def test_failing_devices_stops_start(unacquired, monkeypatch, make):
    _device_absent(monkeypatch)
    srv = make(ServerConfig(scheduler_backend="tpu", scheduler_workers=2,
                            prewarm_shapes=False))
    try:
        with pytest.raises(RuntimeError, match="no device"):
            srv.start()
        assert srv.workers == []
        assert not srv._started
        assert device_status() == {"acquired": False}
    finally:
        srv.shutdown()


def test_factory_never_returns_host_scheduler_for_missing_device(
        unacquired, monkeypatch):
    _device_absent(monkeypatch)
    with pytest.raises(RuntimeError, match="no device"):
        new_scheduler("tpu-service", StateStore().snapshot(), object())


def test_acquired_before_any_worker_starts(unacquired, monkeypatch):
    from nomad_tpu.server import server as server_mod

    seen = []
    real_start = server_mod.Worker.start

    def start(self):
        seen.append(device_status()["acquired"])
        real_start(self)

    monkeypatch.setattr(server_mod.Worker, "start", start)
    srv = Server(ServerConfig(scheduler_backend="tpu", scheduler_workers=3,
                              prewarm_shapes=False))
    try:
        srv.start()
        assert seen == [True, True, True]
    finally:
        srv.shutdown()


def test_host_backend_never_imports_jax():
    code = (
        "import sys\n"
        "from nomad_tpu import mock\n"
        "from nomad_tpu.server.server import Server, ServerConfig\n"
        "s = Server(ServerConfig(scheduler_backend='host',"
        " scheduler_workers=1))\n"
        "s.start()\n"
        "s.node_register(mock.node())\n"
        "job = mock.job()\n"
        "eval_id, _index = s.job_register(job)\n"
        "s.wait_for_eval(eval_id, timeout=30.0)\n"
        "n = sum(a.desired_status == 'run' for a in"
        " s.state_store.snapshot().allocs_by_job(job.id))\n"
        "s.shutdown()\n"
        "assert n > 0, 'host scheduler placed nothing'\n"
        "assert 'jax' not in sys.modules, 'host backend imported jax'\n"
        "from nomad_tpu.scheduler import device_status\n"
        "assert device_status() == {'acquired': False}\n"
    )
    proc = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]


# -- compile cache -----------------------------------------------------------


def _record_config_updates(monkeypatch):
    import jax

    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: calls.append((k, v)))
    return calls


def test_compile_cache_env_set_means_code_sets_nothing(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    calls = _record_config_updates(monkeypatch)
    assert sched.configure_compile_cache("tpu") == str(tmp_path)
    assert sched.configure_compile_cache("cpu") == str(tmp_path)
    assert calls == []


def test_compile_cache_default_is_fixed_in_checkout_path(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    calls = _record_config_updates(monkeypatch)
    expected = os.path.join(REPO, ".jax_cache")
    # Same answer every call and in every process: the path is part of
    # the cache key, so nothing of it may come from tempfile, a pid or
    # the clock.
    assert sched.configure_compile_cache("tpu") == expected
    assert sched.configure_compile_cache("tpu") == expected
    assert calls[:2] == [
        ("jax_compilation_cache_dir", expected),
        ("jax_persistent_cache_min_compile_time_secs", 0),
    ]


def test_compile_cache_not_placed_for_the_cpu_backend(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    calls = _record_config_updates(monkeypatch)
    assert sched.configure_compile_cache("cpu") is None
    assert calls == []


def test_acquisition_places_the_cache_before_the_solver_compiles(
        unacquired, monkeypatch):
    """The agent and the benchmark both get the cache by going through
    acquisition; neither sets one of its own."""
    order = []
    monkeypatch.setattr(
        sched, "configure_compile_cache",
        lambda platform: order.append(("cache", platform)) or "/placed")
    assert acquire_device()["compile_cache"] == "/placed"
    assert order == [("cache", "cpu")]
    assert device_status()["compile_cache"] == "/placed"
    for script in ("nomad_tpu/agent.py", "benchmark/run.py"):
        text = open(os.path.join(REPO, script)).read()
        assert "jax_compilation_cache_dir" not in text, script
