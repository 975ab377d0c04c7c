"""``borg-12k.mixed-shapes`` (ISSUE 37), held on the CPU: the cell resolves
to the configuration's and the mix's own files, it lists the standing
``.drain`` and ``setup_*`` metrics and its own ``.mixed`` ones but not the
water-fill's roofline, and a traced rehearsal of its stand-in mix on the
rehearsal cell of four machine shapes reports every one of them that
needs no device, each new counter and span under the name its metric
file reads.
"""

from __future__ import annotations

import json
import math
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import run  # noqa: E402
from benchmark.readers import counters  # noqa: E402

CELL = "borg-12k.mixed-shapes"
REHEARSAL = "rehearsal-shapes-256.rehearsal-mixed-shapes"
MIXED = {
    "schedule_attempts_per_eval.mixed", "solves_per_eval.mixed",
    "sampled_solve_share.mixed", "widened_per_sampled_solve.mixed",
    "staging_mask_mean_ms.mixed", "staging_usage_job_mean_ms.mixed",
    "greedy_kernel_us.mixed", "broker_wait_mean_ms.mixed",
}


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_the_cell_is_the_configurations_and_the_mixs_own_files():
    bench = _bench()
    cell = run.Cell(CELL)
    assert (cell.config_name, cell.traffic_name, cell.chips) == (
        "borg-12k", "mixed-shapes", 1)
    listed = next(c for c in bench["configs"] if c["name"] == "borg-12k")
    assert listed["file"] == "benchmark/configs/borg-12k.json"
    assert listed["source"] == cell.config["source"]
    assert listed["reduced"] == cell.config["reduced"] == ["window", "servers"]
    # The six server settings are the standing configurations' own.
    assert cell.config["server"] == run.Cell(
        "cell-10k.burst-100k").config["server"]
    assert cell.mix["arrivals"] == {"process": "at_once", "jobs": 64}
    assert cell.mix["fill_limit"] == 0.6 and cell.mix["senders"] == 8
    assert sum(t["copies"] * sum(g["count"] for g in t["groups"])
               for t in cell.mix["templates"]) == 6940
    assert [w["name"] for w in bench["workloads"]][-1] == CELL
    assert len(bench["workloads"]) == 6


def test_the_cell_joins_the_standing_lists_and_brings_its_own():
    cell = run.Cell(CELL)
    assert {m["name"] for m in cell.end_to_end()} == {
        "placements_per_s", "setup_s"}
    mine = {m["name"] for m in cell.per_layer()}
    burst = {m["name"] for m in run.Cell("cell-10k.burst-100k").per_layer()}
    # Burst's .drain and setup_* metrics, with the same reader and
    # arguments (the same files), but the water-fill's roofline: its
    # least time is reckoned from the widths of all dispatches, and most
    # of this cell's are exact scans.
    assert burst - mine == {"waterfill_roofline.drain"}
    assert mine - burst == MIXED
    for name in MIXED:
        spec = run.load_json("metrics", name + ".json")
        assert spec["workloads"] == [CELL]
        assert spec["moves"] == "placements_per_s"
    # The stand-in mix reports what the cell reports.
    assert {m["name"] for m in run.Cell(REHEARSAL).per_layer()} == mine


def test_the_new_counters_are_in_the_panel_under_the_names_read():
    from nomad_tpu.tpu.solver import SOLVER_PANEL

    snap = SOLVER_PANEL.snapshot()
    for name in MIXED:
        source = run.load_json("metrics", name + ".json")["source"]
        if source["reader"] != "counters":
            continue
        for key in (source["counter"], source["per"]):
            kind, _, field = key.partition(".")
            assert kind in ("panel", "window")
            if kind == "panel":
                assert isinstance(snap[field], int), key
    # A program without them (the parent commit's) leaves the metric out.
    assert counters.read({"counter": "panel.sampled_solves",
                          "per": "panel.solves"},
                         type("Ctx", (), {"counters": {"panel.solves": 3}})
                         ) is None


def test_traced_rehearsal_reports_every_metric_of_the_cell(capsys):
    rc = run.main(["--workload", REHEARSAL, "--seed", "2147483777",
                   "--seconds", "600.0", "--trace", "1",
                   "--drain-timeout", "6"])
    out = capsys.readouterr()
    assert rc == 0, out.err[-2000:]
    lines = out.out.strip().splitlines()
    result, report = json.loads(lines[-1]), json.loads(lines[-2])
    assert result["correct"] is True and result["failed"] == 0, report[
        "jobs_not_whole"]
    assert report["window_end"] == "cell_full" and report["rounds"] >= 1
    assert report["jobs_due"] == 32 * report["rounds"]
    listed = {m["name"]: m for m in run.Cell(CELL).per_layer()}
    # Off the chip there is no device trace to read.
    want = {n for n, m in listed.items() if m["source"] != "device_trace"}
    assert set(listed) - want == {"greedy_kernel_us.mixed",
                                  "device_idle_pct.drain"}
    metrics = result["metrics"]
    assert want <= set(metrics), want - set(metrics)
    for name in want:
        value = metrics[name]["value"]
        assert isinstance(value, (int, float)) and math.isfinite(value), name
        assert value >= 0.0, name
        assert metrics[name]["unit"] == listed[name]["unit"]
    # One attempt an evaluation and one more for each plan refused; both
    # solves of a job of two groups; the exact scans on their classes.
    assert 1.0 <= metrics["schedule_attempts_per_eval.mixed"]["value"] < 1.5
    assert 1.0 < metrics["solves_per_eval.mixed"]["value"] < 1.6
    assert 0.5 < metrics["sampled_solve_share.mixed"]["value"] <= 1.0
    assert metrics["widened_per_sampled_solve.mixed"]["value"] < 0.5
    assert metrics["single_program_dispatch_share.drain"]["value"] == 1.0
    assert metrics["plan_conflicts_per_plan.drain"]["value"] < 0.3
    assert metrics["staging_mask_mean_ms.mixed"]["value"] > 0.0
    assert metrics["staging_usage_job_mean_ms.mixed"]["value"] > 0.0
    assert metrics["broker_wait_mean_ms.mixed"]["value"] > 0.0
    c = report["counters"]
    assert c["panel.schedule_attempts"] >= c["pipeline.plans"]
    assert c["panel.sampled_solves"] <= c["panel.solves"]
