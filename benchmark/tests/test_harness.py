"""Tests of the harness, on the CPU against the rehearsal configuration.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q

They hold: every cell of BENCHMARK.json resolves to its files by name;
the result line has exactly the contract's keys; work.py's byte counts
against hand-worked shapes; the xplane reduction on a small recorded
trace; the traffic plan is reproducible from the seed and offers every
seed the same work; the control (the reference in the program's place
with one guarantee broken) comes out not correct; and a run whose timed
path is broken underneath comes out not correct, once for each fault a
cell can have.
"""

from __future__ import annotations

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import reference, run, work  # noqa: E402
from benchmark.generators import traffic  # noqa: E402
from benchmark.generators.fleet import node_spec  # noqa: E402
from benchmark.generators.jobs import job_spec  # noqa: E402
from benchmark.readers import xplane  # noqa: E402

HERE = os.path.join(ROOT, "benchmark")
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


# -- files by name -------------------------------------------------------------


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_cell_resolves_to_its_files(cell):
    c = run.Cell(cell)
    assert c.config["nodes"]["count"] > 0 and c.mix["sizes"]
    assert c.config["source"] == next(
        x["source"] for x in BENCH["configs"] if x["name"] == c.config_name)
    e2e = {m["name"] for m in c.end_to_end()}
    assert "setup_s" in e2e and len(e2e) >= 2
    layers = c.per_layer()
    assert layers
    for m in layers:
        spec = run.load_json("metrics", m["name"] + ".json")
        assert spec["moves"] == m["moves"] in e2e
        assert spec["layer"] == m["layer"] and spec["unit"] == m["unit"]
        assert os.path.exists(os.path.join(
            HERE, "readers", spec["source"]["reader"] + ".py"))


def test_every_metric_file_is_listed_and_every_listed_metric_has_a_file():
    listed = {m["name"] for m in BENCH["per_layer"]}
    files = {f[:-len(".json")] for f in os.listdir(
        os.path.join(HERE, "metrics"))}
    assert listed == files


def test_unlisted_cell_needs_a_rehearsal_configuration():
    with pytest.raises(SystemExit):
        run.Cell("cell-10k.rehearsal-steady")
    assert run.Cell("rehearsal-256.rehearsal-steady").config["rehearsal"]


# -- work.py against hand-worked shapes ---------------------------------------


def test_work_bytes_hand_worked():
    # 57 bytes read per padded row, 4 written: 61 x 16,384 = 999,424.
    assert work.ROW_READ_BYTES == 57
    assert work.waterfill(16384, 1)["bytes"] == 999_424
    assert work.waterfill(16384, 4)["bytes"] == 4 * 999_424
    assert work.waterfill(8192, 2)["ops"] == 8192 * 2 * 16
    # greedy: 57 x 16,384 read, 5 bytes written per task of the bucket.
    assert work.greedy(16384, 1, 64)["bytes"] == 57 * 16384 + 64 * 5
    assert work.greedy(16384, 2, 8)["ops"] == 2 * 16384 * (16 + 8)


def test_least_seconds_is_bound_by_bytes_on_v5e():
    # 999,424 B / 819e9 B/s = 1.2203 us; ops bound is 1.3 ns.
    t = work.least_seconds("waterfill", "TPU v5 lite", 16384, {1: 1})
    assert t == pytest.approx(999_424 / 819e9)
    t = work.least_seconds("waterfill", "TPU v5 lite", 16384, {2: 3, 4: 0})
    assert t == pytest.approx(3 * 2 * 999_424 / 819e9)
    assert work.least_seconds("waterfill", "TPU v5 lite", 16384, {}) is None
    with pytest.raises(KeyError):
        work.least_seconds("waterfill", "TPU v9", 16384, {1: 1})


# -- the xplane reduction on a small recorded trace ---------------------------


def recorded_rows():
    with open(os.path.join(HERE, "tests", "data", "trace_rows.json")) as f:
        return json.load(f)["rows"]


def test_xplane_reduce_hand_made():
    dev, host = "/device:TPU:0", "/host:CPU"
    rows = [
        [host, "python", xplane.MARKER, 1_000, 10],
        [dev, "XLA Modules", "jit_a(1)", 2_000, 1_000],
        [dev, "XLA Ops", "fusion.1", 2_000, 400],
        [dev, "XLA Ops", "fusion.2", 2_300, 700],   # overlaps: union 1,000
        [dev, "XLA Modules", "jit_b(2)", 10_000, 500],
        [dev, "XLA Ops", "copy.3", 10_000, 500],
    ]
    t = xplane.reduce(rows)
    assert t["devices"] == 1
    assert t["busy_s"] == pytest.approx(1_500 / 1e9)
    assert t["span_ns"] == (2_000, 10_500)
    assert t["gaps"] == [(3_000, 10_000)]
    assert t["marker_ns"] == 1_000
    assert xplane.matching(t, "jit_a") == [pytest.approx(1e-6)]
    assert xplane.reduce([[host, "python", "x", 0, 5]]) is None


def test_xplane_reduce_recorded_trace():
    rows = recorded_rows()
    t = xplane.reduce(rows)
    assert t is not None and t["devices"] >= 1 and t["busy_s"] > 0
    span = (t["span_ns"][1] - t["span_ns"][0]) / 1e9
    assert t["busy_s"] <= span
    assert sum(b - a for a, b in t["gaps"]) / 1e9 == pytest.approx(
        span - t["busy_s"], rel=1e-6)
    assert any("solve" in name for name in t["programs"])

    class Ctx:
        trace, trace_window_s = t, span
        device_kind, node_bucket = "TPU v5 lite", 16384
        trace_widths = {1: 1}

    idle = xplane.read({"kind": "idle_pct"}, Ctx)
    assert 0.0 <= idle < 100.0
    assert xplane.read({"kind": "kernel_us", "match": "no-such"}, Ctx) is None


# -- traffic from the seed -----------------------------------------------------


def steady_mix():
    return run.load_json("traffic", "steady-small.json")


def test_plan_reproducible_from_seed():
    mix = steady_mix()
    a = traffic.round_plan(mix, 2_500_000_123, 30.0, 0, "s")
    b = traffic.round_plan(mix, 2_500_000_123, 30.0, 0, "s")
    c = traffic.round_plan(mix, 2_500_000_124, 30.0, 0, "s")
    assert a == b and a != c


def test_every_seed_offers_the_same_work_in_another_order():
    mix = steady_mix()
    n = round(mix["arrivals"]["rate_per_s"] * 30.0)
    plans = [traffic.round_plan(mix, seed, 30.0, 0, "s")
             for seed in (1, 2_147_483_659, 4_000_000_007)]
    gaps = []
    for plan in plans:
        assert len(plan) == n
        offs = [p["offset"] for p in plan]
        assert offs == sorted(offs) and 0 < offs[0] and offs[-1] < 30.0
        gaps.append(sorted(round(b - a, 9) for a, b in
                           zip([0.0] + offs, offs)))
        assert sorted(p["size"] for p in plan) == sorted(
            p["size"] for p in plans[0])
    assert gaps[0] == gaps[1] == gaps[2]
    assert {p["size"] for p in plans[0]} == set(mix["sizes"])
    assert max(mix["sizes"]) <= 128    # every job rides the exact path


def test_apportion_exact_proportions():
    assert traffic.apportion([25, 15, 25, 20, 8, 7], 1200) == [
        300, 180, 300, 240, 96, 84]
    assert sum(traffic.apportion([25, 15, 25, 20, 8, 7], 1201)) == 1201


# -- the plain reference and its control --------------------------------------


def small_cluster(n=64):
    shape = run.load_json("configs", "rehearsal-256.json")
    nodes = [node_spec(shape["nodes"], i) for i in range(n)]
    jobs = [job_spec(shape["task"], f"j{k}", "batch", c)
            for k, c in enumerate([5, 300, 50, 1, 20, 700])]
    return nodes, jobs


def test_reference_on_itself_is_correct():
    nodes, jobs = small_cluster()
    placed = reference.place(nodes, jobs)
    assert [len(placed[j["id"]]) for j in jobs] == [j["count"] for j in jobs]
    rows = {j["id"]: [(f"{j['id']}/{k}", j["id"], nodes[int(i)]["id"],
                       j["cpu"], j["memory_mb"])
                      for k, i in enumerate(placed[j["id"]])] for j in jobs}
    by_node: dict = {}
    for rs in rows.values():
        for r in rs:
            by_node.setdefault(r[2], []).append(r)
    sound = reference.Answers(
        {j["id"]: j["count"] for j in jobs}, lambda jid: rows[jid],
        lambda nid: by_node.get(nid, []))
    numbers = reference.compare(nodes, jobs, sound, seed=7)
    assert reference.verdict(numbers), numbers
    assert set(numbers) == set(reference.LIMITS)


@pytest.mark.parametrize("broken,number", [
    ("capacity", "nodes_over_capacity"),
    ("eligibility", "ineligible"),
    ("commit", "store_mismatch"),
])
@pytest.mark.parametrize("seed", [3, 2_147_483_659, 4_000_000_007])
def test_control_comes_out_not_correct(broken, number, seed):
    nodes, jobs = small_cluster()
    numbers = reference.compare(
        nodes, jobs, reference.control(nodes, jobs, broken), seed)
    assert numbers[number] > 0 and not reference.verdict(numbers)


def test_reference_places_no_more_than_fits():
    nodes, jobs = small_cluster(2)      # 2 nodes x 320 tasks by cpu
    placed = reference.place(nodes, jobs)
    assert sum(len(v) for v in placed.values()) == 2 * 320
    assert len(placed["j5"]) < 700


# -- a run, and a run with the timed path broken underneath --------------------


def drive(capsys, workload="rehearsal-256.rehearsal-steady", seed=2_500_000_321,
          seconds=2.0, trace=0):
    rc = run.main(["--workload", workload, "--seed", str(seed),
                   "--seconds", str(seconds), "--trace", str(trace),
                   "--drain-timeout", "6"])
    out = capsys.readouterr()
    assert rc == 0, out.err[-2000:]
    return json.loads(out.out.strip().splitlines()[-1]), out


def test_result_line_has_the_contracts_keys(capsys):
    result, out = drive(capsys)
    assert set(result) == RESULT_KEYS | {"compared"}
    assert list(result)[-1] == "compared"
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    assert set(result["metrics"]) == {"placed_p50_ms",
                                      "setup_s"}
    for m in result["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    assert set(result["device"]) == {"platform", "kind", "count",
                                     "memory_peak_bytes"}
    assert set(result["compared"]) == set(reference.LIMITS)
    assert out.err.strip().splitlines()[-1] == "correct: True"


def test_traced_run_reports_per_layer_metrics(capsys):
    result, _ = drive(capsys, "rehearsal-256.rehearsal-burst", trace=1)
    assert result["correct"] is True
    assert {"schedule_solve_mean_ms.drain", "evals_per_dispatch.drain",
            "compiles_in_window.drain", "plan_verify_mean_ms.drain",
            "raft_log_bytes_per_placement.drain"} <= set(result["metrics"])
    assert "setup_s" not in result["metrics"]


def _break_state_unchanged(monkeypatch):
    """A commit that returns its state unchanged: the FSM acknowledges
    the plan in the stream and writes nothing to the store."""
    from nomad_tpu.state.store import StateStore

    monkeypatch.setattr(StateStore, "upsert_allocs",
                        lambda self, index, allocs: None)
    monkeypatch.setattr(StateStore, "upsert_alloc_blocks",
                        lambda self, index, batches: None)


def _break_half_left_out(monkeypatch):
    """Half of every plan's placements left out where they are written."""
    from nomad_tpu.state.store import StateStore

    real, real_blocks = StateStore.upsert_allocs, StateStore.upsert_alloc_blocks
    monkeypatch.setattr(
        StateStore, "upsert_allocs",
        lambda self, index, allocs: real(self, index, allocs[::2]))
    monkeypatch.setattr(
        StateStore, "upsert_alloc_blocks",
        lambda self, index, batches: real_blocks(self, index, batches[::2]))


def _break_answer_altered(monkeypatch):
    """An answer altered where it is produced: each plan's first
    placement is moved to a node that the job's constraint excludes."""
    from nomad_tpu.state.store import StateStore

    real = StateStore.upsert_allocs

    def moved(self, index, allocs):
        if allocs:
            allocs[0].node_id = "sim-00015"   # kernel.name = windows
        return real(self, index, allocs)

    monkeypatch.setattr(StateStore, "upsert_allocs", moved)


@pytest.mark.parametrize("fault,number", [
    (_break_state_unchanged, "store_mismatch"),
    (_break_half_left_out, "store_mismatch"),
    (_break_answer_altered, "ineligible"),
])
def test_broken_timed_path_comes_out_not_correct(
        capsys, monkeypatch, fault, number):
    # The warm-up runs sound; the fault is planted as the window opens.
    from benchmark.generators.traffic import Player

    real_play = Player.play

    def play(self, seconds, tag, *args, **kwargs):
        if tag != "warm":
            fault(monkeypatch)
        return real_play(self, seconds, tag, *args, **kwargs)

    monkeypatch.setattr(Player, "play", play)
    result, _ = drive(capsys)
    assert result["correct"] is False
    assert result["compared"][number]["value"] > 0


def test_compare_counts_a_job_left_short():
    nodes, jobs = small_cluster()
    sound = reference.control(nodes, jobs, "commit")   # rows: every other one
    sound.committed["j1"] -= 1
    numbers = reference.compare(nodes, jobs, sound, seed=11)
    assert numbers["jobs_short"] == 1 and not reference.verdict(numbers)


# -- BENCHMARK.json against the contract's limits ------------------------------

import re  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def _line(text, limit=200):
    return 1 <= len(text) <= limit and "\n" not in text and "\t" not in text


def test_benchmark_json_meets_the_contracts_limits():
    b = BENCH
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert b["paths"] == ["benchmark"] and 1 <= b["run_seconds"] <= 51
    assert all(_line(w) for w in b["command"]) and len(b["command"]) <= 32
    cells = {w["name"] for w in b["workloads"]}
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and _line(c["source"]) and _line(c["why"])
        assert c["file"].startswith("benchmark/") and len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])
        assert any(w["config"] == c["name"] for w in b["workloads"])
    pairs = set()
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and _line(w["why"])
        pairs.add((w["config"], w["traffic"]))
    assert len(pairs) == len(b["workloads"]) == len(cells)
    e2e = {m["name"]: m for m in b["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] == 0.25
    for m in b["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in b["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["moves"] in e2e and _line(m["layer"])
        # every cell that reports the metric reports the one it moves
        moved = e2e[m["moves"]].get("workloads", cells)
        assert set(m.get("workloads", moved)) <= set(moved)
    for m in b["end_to_end"] + b["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
        assert set(m.get("workloads", cells)) <= cells
    names = [m["name"] for m in b["end_to_end"] + b["per_layer"]]
    assert len(names) == len(set(names))
    for w in cells:   # setup_s, one more end-to-end, one per-layer
        assert sum(w in m.get("workloads", cells)
                   for m in b["end_to_end"]) >= 2
        assert any(w in m.get("workloads", cells) for m in b["per_layer"])
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    for dirpath, _dirs, files in os.walk(HERE):
        if "/out" in dirpath or "__pycache__" in dirpath:
            continue
        for f in files:
            assert re.match(r"^[A-Za-z0-9_.\-]+$", f), f
