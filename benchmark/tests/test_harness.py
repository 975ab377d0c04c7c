"""Tests of the harness, on the CPU against the rehearsal configuration.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q

They hold: every cell of BENCHMARK.json resolves to its files by name;
the result line has exactly the contract's keys; work.py's byte counts
against hand-worked shapes; the xplane reduction on a small recorded
trace; the traffic plan is reproducible from the seed and offers every
seed the same work; a drain window ends when its work ends (no round
offered to a cell that cannot hold it, a drained backlog closed at its
last commit); the control (the reference in the program's place with one
guarantee broken) comes out not correct; and a run whose timed path is
broken underneath comes out not correct, once for each fault a cell can
have.
"""

from __future__ import annotations

import json
import os
import sys
import threading

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


# -- a drain window ends when its work ends -------------------------------------


class FakeFleet:
    """Takes registrations over the one call the player makes. A job
    counts as placed at once where ``places(job id)`` says so."""

    def __init__(self, places=lambda job_id: True):
        self.places = places
        self.placed = 0
        self.registered = []

    def call(self, method, args):
        assert method == "Job.Register"
        job = args["job"]
        self.registered.append(job["id"])
        if self.places(job["id"]):
            self.placed += job["task_groups"][0]["count"]
        return {"eval_id": "eval-" + job["id"]}


def sixteen_nodes():
    """16 nodes, 15 eligible, 320 tasks each: 4,800 slots; the rehearsal
    burst's rounds ask for 4 x 300 = 1,200."""
    config = run.load_json("configs", "rehearsal-256.json")
    config["nodes"]["count"] = 16
    nodes = [node_spec(config["nodes"], i) for i in range(16)]
    mix = run.load_json("traffic", "rehearsal-burst.json")
    slots = reference.free_slots(
        nodes, job_spec(config["task"], "", mix["job_type"], 0))
    return config, mix, slots


def warmed(fleet, config, mix, slots):
    """The window's player, after a warm-up round as run.py plays it."""
    warm = traffic.Player(fleet, mix, config, 11, lambda: fleet.placed, slots)
    played = warm.play(30.0, "warm", mix["warmup"])
    assert played["rounds"] == 1 and played["end"] == "rounds"
    return traffic.Player(fleet, mix, config, 2_500_000_321,
                          lambda: fleet.placed, warm.slots_left)


def test_closed_loop_offers_no_round_the_cell_cannot_hold():
    config, mix, slots = sixteen_nodes()
    assert slots == 4_800
    fleet = FakeFleet()
    player = warmed(fleet, config, mix, slots)
    assert player.slots_left == 3_600
    played = player.play(30.0, "s", target_base=fleet.placed)
    assert played["end"] == "cell_full" and played["rounds"] == 3
    assert played["asked"] == 3_600 and player.slots_left == 0
    assert played["closed"] - played["opened"] < 10.0   # not the deadline
    offered = [r["spec"] for r in player.jobs.values() if "due" in r]
    assert sum(j["count"] for j in offered) == 3_600
    assert fleet.placed == 4_800         # nothing offered failed
    assert len(fleet.registered) == 4 + 12


def test_round_offered_with_room_and_not_placed_counts_as_failed(monkeypatch):
    config, mix, slots = sixteen_nodes()
    monkeypatch.setattr(traffic, "ROUND_GRACE_S", 0.2)
    fleet = FakeFleet(places=lambda job_id: "-r001-" not in job_id)
    player = warmed(fleet, config, mix, slots)
    base = fleet.placed
    played = player.play(0.5, "s", target_base=base)
    # The second round had room (2,400 slots) and is never placed: the
    # loop waits for it past the deadline and offers no third.
    assert played["end"] == "deadline" and played["rounds"] == 2
    assert played["asked"] == 2_400 and player.slots_left == 1_200
    assert played["asked"] - (fleet.placed - base) == 1_200   # run.py's failed


def test_first_round_larger_than_the_cell_is_refused():
    config, mix, slots = sixteen_nodes()
    fleet = FakeFleet()
    player = traffic.Player(fleet, mix, config, 5, lambda: fleet.placed, 1_199)
    with pytest.raises(ValueError, match="has 1199 slots left"):
        player.play(1.0, "s")
    assert fleet.registered == []


def test_each_size_is_played_once_alone():
    # An open loop's warm-up: every size is solved with no other job in
    # flight, so its lone program is compiled before the window. The fake
    # places a job 30 ms after it registers it.
    config, _mix, slots = sixteen_nodes()
    mix = run.load_json("traffic", "rehearsal-steady.json")
    placed_before = []

    def places(job_id):
        placed_before.append(fleet.placed)
        count = player.jobs[job_id]["spec"]["count"]
        threading.Timer(0.03, lambda: setattr(
            fleet, "placed", fleet.placed + count)).start()
        return False

    fleet = FakeFleet(places)
    player = traffic.Player(fleet, mix, config, 7, lambda: fleet.placed, slots)
    asked = player.play_alone("lone", 0, 5.0)
    sizes = sorted(set(mix["sizes"]))
    assert asked == sum(sizes) == fleet.placed
    assert [player.jobs[j]["spec"]["count"] for j in fleet.registered] == sizes
    # Each went out only when all before it were placed.
    assert placed_before == [sum(sizes[:k]) for k in range(len(sizes))]
    assert all("due" in rec for rec in player.jobs.values())
    assert player.slots_left == slots - asked


@pytest.mark.parametrize("name,eligible_nodes,slots", [
    ("cell-10k", 9_375, 3_000_000),      # 10,000 - 625 windows; x 320
    ("c1m-5k", 4_688, 1_500_160),        # 5,000 - 312 windows; x 320
])
def test_free_slots_hand_worked(name, eligible_nodes, slots):
    config = run.load_json("configs", name + ".json")
    shape, task = config["nodes"], config["task"]
    nodes = [node_spec(shape, i) for i in range(shape["count"])]
    job = job_spec(task, "j", "batch", slots + 1_000)
    assert sum(reference.eligible(nd, job) for nd in nodes) == eligible_nodes
    # 32,000 MHz / 100 = 320 by cpu, 65,536 MB / 128 = 512 by memory.
    assert min(shape["cpu"] // task["cpu"],
               shape["memory_mb"] // task["memory_mb"]) == 320
    assert reference.free_slots(nodes, job) == slots == eligible_nodes * 320
    assert len(reference.place(nodes, [job])["j"]) == slots


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


def report_of(out):
    """The fuller report: the line before the result."""
    return json.loads(out.out.strip().splitlines()[-2])


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
            "xla_compiles_in_window.drain", "plan_verify_mean_ms.drain",
            "raft_log_bytes_per_placement.drain"} <= set(result["metrics"])
    assert "setup_s" not in result["metrics"]


def test_burst_fills_the_cell_and_ends_there(capsys):
    # 240 eligible nodes x 320 = 76,800 slots, rounds of 4 x 300: the
    # warm-up takes one, the window the other 63, and no 65th is offered.
    result, out = drive(capsys, "rehearsal-256.rehearsal-burst", seconds=600.0)
    report = report_of(out)
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] == 63 * 1_200
    assert report["window_end"] == "cell_full" and report["rounds"] == 63
    assert report["slots_left"] == 0 and report["seconds"] < 300.0
    assert report["placed_in_window"] == 63 * 1_200


def test_backlog_drained_early_closes_at_its_last_commit(capsys):
    # 60 jobs x 200 tasks drain in a few seconds of the 120 offered.
    result, out = drive(capsys, "rehearsal-256.rehearsal-backlog", seconds=120.0)
    report = report_of(out)
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] == 12_000
    assert report["window_end"] == "drained" and report["rounds"] == 1
    assert report["slots_left"] == 240 * 320 - 800 - 12_000
    assert report["seconds"] < 60.0
    rate = result["metrics"]["placements_per_s"]["value"]
    # The drain's own rate, not 12,000 over the 120 s offered.
    assert rate * report["seconds"] == pytest.approx(12_000)


def test_backlog_not_drained_closes_at_the_deadline(capsys):
    result, out = drive(capsys, "rehearsal-256.rehearsal-backlog", seconds=0.5)
    report = report_of(out)
    assert result["correct"] is True and result["failed"] == 0
    assert report["window_end"] == "deadline"
    assert report["seconds"] == pytest.approx(0.5, abs=0.1)
    assert report["placed_in_window"] < 12_000
    assert report["slots_left"] == 240 * 320 - 800 - 12_000   # by what was asked


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
