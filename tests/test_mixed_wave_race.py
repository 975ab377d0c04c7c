"""Sixteen evaluations in flight on one snapshot, on a cell whose machines
and tasks differ in shape (ISSUE 37).

The rehearsal cell of four machine shapes (``rehearsal-shapes-256``) is
given one wave of the rehearsal's mixed mix (32 jobs: twenty of one task,
five of ten, jobs of 40-100, three of two task groups up to 300; five
task shapes, three priority bands, ``=`` / ``!=`` on ``platform``), every
evaluation in ONE raft entry, so that the four workers' batches of four
all solve on the same snapshot. The dense backend has to place every job
whole, as the host backend does and as the plain reference's first fit
does in totals. Before the candidate rule (scheduler/candidates.py) every
evaluation took the global best fit, the plan pipeline refused the losers
and after two (batch) or five (service) attempts evaluations ended
``failed`` with their jobs short: that is the case this fails on.

It asserts outcomes, never an order of events: which evaluation wins a
race is the scheduler's business, and a plan refused in part is no fault
where its remainder is placed inside the reference's attempt limits. The
limit on those plans is stated, and far below what the parent's program
read.
"""

from __future__ import annotations

import json
import os
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import reference  # noqa: E402
from benchmark.generators import traffic  # noqa: E402
from benchmark.generators.fleet import (  # noqa: E402
    build_node,
    node_count,
    node_spec,
)
from benchmark.generators.jobs import build_job  # noqa: E402

from nomad_tpu import structs  # noqa: E402
from nomad_tpu.server import Server, ServerConfig  # noqa: E402
from nomad_tpu.structs import Evaluation, generate_uuid  # noqa: E402

HERE = os.path.join(ROOT, "benchmark")
# Plans the pipeline may refuse in part, per plan submitted. The parent's
# program read 0.66 on the cell of 12,583 machines (PERF.md section 7) and
# leaves jobs short here on every seed; the change read 0, 1 or 2 of 32-34
# plans in 24 runs in a row over these four seeds, every job whole (PR 37).
# A cell of 256 nodes has sixteen classes for its sixteen evaluations in
# flight, and its three water-fills of 140-160 copies cover two nodes in
# three each, so some plans are refused in part and their remainder placed
# on the second attempt; on the chip's cell the same groups cover a tenth
# of the machines.
SEEDS = [1, 2, 3, 4]
CONFLICTS_PER_PLAN = 0.2


def _load(*parts):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def _wave(config, mix, seed):
    return [traffic.item_spec(config, mix, item)
            for item in traffic.round_plan(mix, seed, 45.0, 0, f"race{seed}")]


def _place_wave(backend: str, seed: int):
    """One wave through a server of ``backend``: ({job id: tasks placed},
    {job id: how its evaluation ended}, the pipeline's stats)."""
    config = _load("configs", "rehearsal-shapes-256.json")
    mix = _load("traffic", "rehearsal-mixed-shapes.json")
    shape = config["nodes"]
    specs = [node_spec(shape, i) for i in range(node_count(shape))]
    wave = _wave(config, mix, seed)
    srv = Server(ServerConfig(**dict(config["server"],
                                     scheduler_backend=backend)))
    try:
        for spec in specs:
            srv.raft.apply("node_register",
                           {"node": build_node(shape, spec)})
        evals = []
        for spec in wave:
            job = build_job(spec)
            index = srv.raft.apply("job_register", {"job": job}).result()
            evals.append(Evaluation(
                id=generate_uuid(), priority=job.priority, type=job.type,
                triggered_by=structs.EVAL_TRIGGER_JOB_REGISTER,
                job_id=job.id, job_modify_index=index,
                status=structs.EVAL_STATUS_PENDING))
        # The pipeline's totals are the process's: this wave's are what
        # it adds to them.
        before = srv.plan_applier.stats()
        srv.start()
        # One entry: all of them ready at once, on one snapshot.
        srv.raft.apply("eval_update", {"evals": evals})
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline:
            done = [srv.state_store.eval_by_id(e.id) for e in evals]
            if all(d is not None and d.terminal_status() for d in done):
                break
            time.sleep(0.02)
        else:
            raise AssertionError("evaluations did not end")
        placed = {
            spec["id"]: sum(
                a.desired_status == structs.ALLOC_DESIRED_STATUS_RUN
                for a in srv.state_store.allocs_by_job(spec["id"]))
            for spec in wave}
        ended = {e.job_id: srv.state_store.eval_by_id(e.id).status
                 for e in evals}
        after = srv.plan_applier.stats()
        stats = {k: after[k] - before[k] for k in ("plans", "conflicts")}
        return wave, specs, placed, ended, stats
    finally:
        srv.shutdown()


@pytest.mark.parametrize("seed", SEEDS)
def test_sixteen_in_flight_place_the_mixed_wave_whole(seed):
    wave, nodes, placed, ended, stats = _place_wave("tpu", seed)
    short = {j["id"]: (placed[j["id"]], j["count"], ended[j["id"]])
             for j in wave if placed[j["id"]] != j["count"]}
    assert not short, short
    assert set(ended.values()) == {structs.EVAL_STATUS_COMPLETE}
    # As the plain reference's first fit does, in totals.
    want = reference.place(nodes, wave)
    assert {jid: len(rows) for jid, rows in want.items()} == placed
    assert sum(placed.values()) == 1170
    assert stats["plans"] >= len(wave)
    assert stats["conflicts"] <= CONFLICTS_PER_PLAN * stats["plans"], stats


def test_the_host_backend_places_the_same_wave_whole():
    wave, _nodes, placed, ended, _stats = _place_wave("host", 1)
    assert placed == {j["id"]: j["count"] for j in wave}
    assert set(ended.values()) == {structs.EVAL_STATUS_COMPLETE}
