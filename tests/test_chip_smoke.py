"""chip_smoke.py's contract, as far as a CPU can check it: it refuses to
run off-chip unless asked for the dry run by flag; the dry run drives every
phase at tiny size and says it is one; alone in a directory it fails; and
its oracle comparison has teeth."""

import json
import os
import shutil
import subprocess
import sys

from nomad_tpu import structs

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run(args, cwd=REPO, script=SMOKE):
    return subprocess.run(
        [sys.executable, script, *args], cwd=cwd, capture_output=True,
        text=True, timeout=600,
    )


def test_without_the_flag_a_cpu_run_fails_and_prints_no_result():
    proc = _run([])
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no accelerator" in proc.stderr


def test_alone_in_a_directory_it_fails_and_prints_no_result(tmp_path):
    alone = tmp_path / "chip_smoke.py"
    shutil.copy(SMOKE, alone)
    proc = _run([], cwd=str(tmp_path), script=str(alone))
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""


def test_dry_run_flag_drives_every_phase_at_tiny_size():
    proc = _run(["--dry-run-cpu"])
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = proc.stdout.strip().splitlines()
    # last line: the result, exactly {"ok", "device": {platform, kind, count}}
    result = json.loads(lines[-1])
    assert set(result) == {"ok", "device"} and result["ok"] is True
    assert set(result["device"]) == {"platform", "kind", "count"}
    assert isinstance(result["device"]["count"], int)
    # the line before it: the full report
    report = json.loads(lines[-2])
    assert report["device"] == result["device"]
    assert report["ok"] is True and report["failures"] == []
    assert report["dry_run"] is True
    assert report["device"] == {"platform": "cpu", "kind": "cpu",
                                "count": report["device"]["count"]}
    assert list(report["phases"]) == [
        "kernel", "steady-10k", "burst-100k", "http"]
    kernel = report["phases"]["kernel"]
    assert kernel["interpret"] is True and kernel["failed"] == []
    assert all(s["placed_equal"] and s["sound"] for s in kernel["shapes"])
    for name, expected in (("steady-10k", 800), ("burst-100k", 2400)):
        phase = report["phases"][name]
        assert phase["placed"] == expected and phase["n_nodes"] == 256
        assert phase["device_dispatches"] > 0
        assert phase["oracle"]["agrees"] is True
        assert phase["breaker"] == {"state": "closed", "trips": 0}
        # the cpu backend never selects the pallas kernel
        assert set(phase["solve_paths"]) <= {"jnp", "exact"}
    assert report["phases"]["http"]["placed"] == 150
    assert report["host_scheduler_fallbacks"] == 0
    assert report["solve_paths"]["batch_retries"] == 0
    assert report["native"]["verifier"] in ("native", "numpy")
    # no cache is placed for the cpu backend, and none was asked for
    assert report["compile_cache"]["dir"] == os.environ.get(
        "JAX_COMPILATION_CACHE_DIR")


def test_oracle_comparison_has_teeth():
    sys.path.insert(0, REPO)
    import chip_smoke
    from nomad_tpu.simcluster import sim_node
    from nomad_tpu.simcluster.workload import build_job

    nodes = [sim_node(i, "dc1" if i % 2 == 0 else "dc2") for i in range(8)]
    jobs = [build_job("a", structs.JOB_TYPE_BATCH, 40),
            build_job("b", structs.JOB_TYPE_SERVICE, 30, cpu=500)]

    # A "served run" that is the host oracle itself agrees with itself.
    served = chip_smoke._OraclePlanner()
    for node in nodes:
        served.state.upsert_node(served.next_index(), node)
    for job in jobs:
        served.state.upsert_job(served.next_index(), job)
    assert chip_smoke.host_oracle(nodes, jobs) == {"a": 40, "b": 30}
    snap = served.state.snapshot()
    verdict = chip_smoke.check_against_oracle(snap, nodes, jobs)
    # ...but this one placed nothing: every job disagrees.
    assert verdict["agrees"] is False
    assert len(verdict["problems"]) == 2
    assert verdict["per_job"]["a"] == {"device": 0, "oracle": 40}

    # Placements on a node the feasibility chain rejects, and more than
    # the node holds, are both caught.
    from nomad_tpu.structs import Allocation, generate_uuid

    job = jobs[1]
    bad_node = nodes[0]
    bad_node.attributes["kernel.name"] = "darwin"
    served.state.upsert_node(served.next_index(), bad_node)
    tg = job.task_groups[0]
    allocs = [
        Allocation(
            id=generate_uuid(), eval_id="e", name=f"b.web[{i}]",
            node_id=bad_node.id, job_id=job.id, job=job, task_group=tg.name,
            resources=tg.tasks[0].resources,
            desired_status=structs.ALLOC_DESIRED_STATUS_RUN,
        ) for i in range(30)
    ]
    served.state.upsert_allocs(served.next_index(), allocs)
    verdict = chip_smoke.check_against_oracle(
        served.state.snapshot(), nodes, jobs)
    problems = " | ".join(verdict["problems"])
    assert "30 placements on ineligible nodes" in problems
    assert "1 nodes over capacity" in problems
