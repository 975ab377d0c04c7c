"""bench.py's one-line JSON contract: it measures the device or it fails.

On a platform that is not a TPU the bench exits != 0 and prints no
``value``; with ``NOMAD_TPU_BENCH_ALLOW_CPU=1`` the line says ``backend:
"cpu"`` and its figures sit under ``cpu_run``, never under the device
metric's ``value``. An aux phase that raises still appears in the JSON and
makes the exit code != 0. The suite pins the cpu backend, which is exactly
the platform the bench must refuse.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_bench(extra_env):
    env = {
        **os.environ,
        # The coalesced phase warms 8 jobs x 129 tasks on dc1 (half the
        # nodes, 40 tasks/node by cpu) before the timed batch; 128 nodes
        # is the smallest comfortable fit.
        "NOMAD_TPU_BENCH_NODES": "128",
        "NOMAD_TPU_BENCH_TASKS": "512",
        "NOMAD_TPU_BENCH_RUNS": "1",
        "NOMAD_TPU_BENCH_BREAKDOWN_SCALES": "256",
    }
    env.pop("NOMAD_TPU_BENCH_ALLOW_CPU", None)
    env.update(extra_env)
    proc = subprocess.run(
        [sys.executable, "bench.py"], cwd=REPO, env=env,
        capture_output=True, text=True, timeout=600,
    )
    lines = [l for l in proc.stdout.strip().splitlines() if l.strip()]
    assert len(lines) == 1, f"contract is ONE stdout line, got: {lines!r}"
    return proc, json.loads(lines[0])


def test_unallowed_platform_fails_without_a_value():
    proc, payload = _run_bench({})
    assert proc.returncode != 0
    assert "value" not in payload and "cpu_run" not in payload
    assert payload["backend"] == "cpu"
    assert "requires a TPU" in payload["error"]


def test_allow_cpu_run_is_labeled_and_not_under_the_device_metric():
    proc, payload = _run_bench({"NOMAD_TPU_BENCH_ALLOW_CPU": "1"})
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert payload["backend"] == "cpu"
    assert payload["device"]["platform"] == "cpu"
    assert "value" not in payload and "vs_baseline" not in payload
    assert "error" not in payload
    run = payload["cpu_run"]
    assert run["placements_per_sec"] > 0 and run["solve_ms_p50"] > 0
    assert run["solve_paths"].get("jnp", 0) > 0  # cpu never selects pallas
    _check_breakdown(run["breakdown"])
    _check_config5(run["config5"])
    _check_staging_delta(run["staging_delta"])
    for name in ("config2", "config4", "node_sweep", "simload"):
        assert "error" not in run[name], run[name]


def test_aux_phase_that_raises_is_in_the_json_and_fails_the_exit(monkeypatch):
    """main()'s own bookkeeping, in process, with the measurements stubbed:
    a raising aux phase is reported under its name and the run exits 1."""
    sys.path.insert(0, REPO)
    import bench

    emitted = []

    class Exit(Exception):
        pass

    def fake_exit(code):
        raise Exit(code)

    def boom():
        raise ValueError("config4 blew up")

    dist = {"p50_ms": 2.0}
    monkeypatch.setattr(bench, "ALLOW_CPU", True)
    monkeypatch.setattr(bench, "_start_watchdog", lambda: None)
    monkeypatch.setattr(bench, "emit", emitted.append)
    monkeypatch.setattr(bench, "_exit", fake_exit)
    monkeypatch.setattr(bench, "_measure_headline",
                        lambda: (dist, dist, 512, [], {}))
    monkeypatch.setattr(bench, "run_coalesced", lambda nodes: (0.1, 512, 1))
    for name in ("run_config2", "run_config5", "run_staging_delta",
                 "run_node_sweep", "run_simload", "run_breakdown"):
        monkeypatch.setattr(bench, name, lambda: {"stub": True})
    monkeypatch.setattr(bench, "run_config4", boom)

    with pytest.raises(Exit) as ei:
        bench.main()
    assert ei.value.args == (1,)
    (payload,) = emitted
    assert payload["cpu_run"]["config4"] == {
        "error": "ValueError: config4 blew up"}
    assert payload["cpu_run"]["config2"] == {"stub": True}
    assert "config4" in payload["error"]
    assert "value" not in payload

    # ...and with every phase healthy the same run exits 0.
    emitted.clear()
    monkeypatch.setattr(bench, "run_config4", lambda: {"stub": True})
    with pytest.raises(Exit) as ei:
        bench.main()
    assert ei.value.args == (0,)
    assert "error" not in emitted[0]


def _check_staging_delta(sweep):
    """The delta arm must show the roll path actually engaging: every
    measured single-node write rode a delta roll (not a rebuild of the
    warm cache), and both staging figures are real. The >=5x speedup bar
    is a full-scale (10k-node) acceptance judged from banked artifacts,
    not at smoke scale where fixed overheads dominate."""
    assert isinstance(sweep, list) and sweep, sweep
    for row in sweep:
        assert row["delta_staging_ms_p50"] > 0
        assert row["full_staging_ms_p50"] > 0
        assert row["speedup"] > 0
        assert row["delta_rolls"] >= row["runs"]
        assert row["rows_restaged"] >= row["runs"]


def _check_config5(c5):
    """Config-5 must state its rates AND its pass/fail bars; at reduced
    (smoke) scale the verdict abstains rather than judging scaled-down
    rates against full-scale bars."""
    assert c5["inplace_updates_per_sec"] > 0
    assert c5["rolled_updates_per_sec"] > 0
    assert c5["bar_inplace_updates_per_sec"] > 0
    assert c5["bar_rolled_updates_per_sec"] > 0
    if c5["n_nodes"] < 50_000:
        assert c5["pass"] is None
    else:
        assert isinstance(c5["pass"], bool)


def _check_breakdown(sweep):
    """The device-time split must attribute every phase with real numbers."""
    assert isinstance(sweep, list) and sweep, sweep
    for row in sweep:
        assert row["placed"] > 0
        assert row["transfer_bytes"] > 0
        assert row["readback_bytes"] > 0
        assert row["execute_ms_p50"] > 0
        assert row["warm_e2e_ms_p50"] > 0
        assert row["placements_per_sec_warm"] > 0
