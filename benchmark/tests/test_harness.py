"""Tests of the harness, on the CPU against the rehearsal configuration.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q

They hold: every cell of BENCHMARK.json resolves to its files by name;
the result line has exactly the contract's keys; work.py's byte counts
against hand-worked shapes; the xplane reduction on a small recorded
trace; the traffic plan is reproducible from the seed and offers every
seed the same work; a drain window ends when its work ends (no round
offered to a cell that cannot hold it, a drained backlog closed at its
last commit); jobs that end (a round with stops ends with its stops, a
committed stop gives its slots back, the reference gives the capacity
back, the standing mixes build the rounds they built before the
generator knew of stops); the control (the reference in the program's place with one
guarantee broken) comes out not correct; and a run whose timed path is
broken underneath comes out not correct, once for each fault a cell can
have.
"""

from __future__ import annotations

import hashlib
import json
import os
import sys
import threading
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import reference, run, work  # noqa: E402
from benchmark.generators import traffic  # noqa: E402
from benchmark.generators.fleet import (  # noqa: E402
    deal_shapes,
    node_count,
    node_spec,
)
from benchmark.generators.jobs import build_job, job_spec  # noqa: E402
from benchmark.readers import xplane  # noqa: E402

HERE = os.path.join(ROOT, "benchmark")
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


# -- files by name -------------------------------------------------------------


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_cell_resolves_to_its_files(cell):
    c = run.Cell(cell)
    assert node_count(c.config["nodes"]) > 0
    assert c.mix.get("sizes") or c.mix["templates"]
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
    """Takes registrations and stops over the two calls the player makes.
    A job counts as placed at once where ``places(job id)`` says so, and
    a stop's evaluation ends at once as ``stops(job id)`` says
    (``complete``, ``failed``, or None for never). A registration's
    evaluation ends at once as ``evals(job id)`` says (the same three, or
    ``refused``: the call raises and answers with no evaluation); where
    no rule is given, ``complete`` for a job placed and never for one
    that is not."""

    def __init__(self, places=lambda job_id: True,
                 stops=lambda job_id: "complete", evals=None):
        self.places = places
        self.stops = stops
        self.evals = evals
        self.placed = 0
        self.lock = threading.Lock()
        self.registered = []
        self.deregistered = []
        self.ended = {}

    def eval_done(self, eval_id):
        return self.ended.get(eval_id)

    def call(self, method, args):
        if method == "Job.Deregister":
            self.deregistered.append(args["job_id"])
            how = self.stops(args["job_id"])
            if how:
                self.ended["stop-" + args["job_id"]] = (how, 1.0)
            return {"eval_id": "stop-" + args["job_id"]}
        assert method == "Job.Register"
        job = args["job"]
        self.registered.append(job["id"])
        placed = self.places(job["id"])
        how = (self.evals(job["id"]) if self.evals
               else "complete" if placed else None)
        if how == "refused":
            raise RuntimeError("refused: " + job["id"])
        if placed:
            count = sum(g["count"] for g in job["task_groups"])
            with self.lock:       # the senders are threads
                self.placed += count
        if how:                   # after its placements, as the tail notes it
            self.ended["eval-" + job["id"]] = (how, 1.0)
        return {"eval_id": "eval-" + job["id"]}


def slots_of(nodes, config, mix, seed=1):
    """What the cell holds of the mix, in tasks, as run.py reckons it."""
    return reference.rounds_that_fit(
        nodes, traffic.rounds_of(mix, config, seed, 45.0))[1]


def sixteen_nodes():
    """16 nodes, 15 eligible, 320 tasks each: 4,800 slots; the rehearsal
    burst's rounds ask for 4 x 300 = 1,200."""
    config = run.load_json("configs", "rehearsal-256.json")
    config["nodes"]["count"] = 16
    nodes = [node_spec(config["nodes"], i) for i in range(16)]
    mix = run.load_json("traffic", "rehearsal-burst.json")
    return config, mix, slots_of(nodes, config, mix)


def warmed(fleet, config, mix, slots):
    """The window's player, after a warm-up round as run.py plays it."""
    warm = traffic.Player(fleet, mix, config, 11, lambda: fleet.placed, slots,
                          eval_done=fleet.eval_done)
    played = warm.play(30.0, "warm", mix["warmup"])
    assert played["rounds"] == 1 and played["end"] == "rounds"
    return traffic.Player(fleet, mix, config, 2_500_000_321,
                          lambda: fleet.placed, warm.slots_left,
                          eval_done=fleet.eval_done)


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
    asked = player.play_alone("lone", 0, time.time() + 5.0)
    sizes = sorted(set(mix["sizes"]))
    assert asked == sum(sizes) == fleet.placed
    assert [player.jobs[j]["spec"]["count"] for j in fleet.registered] == sizes
    # Each went out only when all before it were placed.
    assert placed_before == [sum(sizes[:k]) for k in range(len(sizes))]
    assert all("due" in rec for rec in player.jobs.values())
    assert player.slots_left == slots - asked


# -- the harness waits for a job only while it can still be placed -------------
#
# ROUND_GRACE_S stays at 120 in all of these: what ends the wait is the
# job's end, not a shorter grace.


def ending(job_part, how):
    """A fleet that places every job but those whose id holds
    ``job_part``; their evaluation ends as ``how`` says."""
    return FakeFleet(
        places=lambda jid: job_part not in jid,
        evals=lambda jid: how if job_part in jid else "complete")


@pytest.mark.parametrize("how", ["failed", "complete", "refused"])
def test_round_with_a_job_over_and_short_ends_the_loop_at_once(how):
    # The second round's third job is over with nothing placed: its
    # evaluation failed, or completed with its tasks left out, or its
    # registration was refused. The round ends on the next poll and the
    # loop offers no third, with 1,200 slots and 29 s to go.
    assert traffic.ROUND_GRACE_S == 120.0
    config, mix, slots = sixteen_nodes()
    fleet = ending("-r001-00002", how)
    player = warmed(fleet, config, mix, slots)
    base = fleet.placed
    played = player.play(30.0, "s", target_base=base)
    assert played["end"] == "round_short" and played["rounds"] == 2
    assert played["closed"] - played["opened"] < 1.0
    assert played["asked"] == 2_400 and player.slots_left == 1_200
    assert played["asked"] - (fleet.placed - base) == 300     # run.py's failed
    assert len(fleet.registered) == 4 + 8
    assert not any("-r002-" in jid for jid in fleet.registered)
    rec = player.jobs["s-r001-00002"]
    assert ("error" in rec and "eval_id" not in rec) == (how == "refused")


def test_evaluation_that_never_shows_is_waited_for_until_the_grace(monkeypatch):
    # Placed by nobody and ended by nobody (a server that hangs): the
    # round waits --seconds and the grace out, as before.
    monkeypatch.setattr(traffic, "ROUND_GRACE_S", 0.3)
    config, mix, slots = sixteen_nodes()
    fleet = FakeFleet(places=lambda jid: "s-r000-00001" not in jid)
    player = warmed(fleet, config, mix, slots)
    played = player.play(0.2, "s", target_base=fleet.placed)
    assert played["end"] == "deadline" and played["rounds"] == 1
    assert 0.5 <= played["closed"] - played["opened"] < 2.0
    # One job's end does not end the round while another can be placed.
    fleet = FakeFleet(
        places=lambda jid: "s-r000-0000" not in jid,
        evals=lambda jid: "failed" if "s-r000-00000" in jid else None)
    player = warmed(fleet, config, mix, slots)
    played = player.play(0.2, "s", target_base=fleet.placed)
    assert played["end"] == "deadline"
    assert played["closed"] - played["opened"] >= 0.5


def test_round_placed_whole_goes_on_without_waiting_for_its_evaluations():
    # Every job is placed 30 ms after it is registered and no evaluation
    # ever shows: the next round goes out on the poll that sees the
    # placements, as it did before the generator asked how a job ended.
    config, mix, slots = sixteen_nodes()
    placed_at = []

    def places(job_id):
        def commit():
            with fleet.lock:
                fleet.placed += 300
            placed_at.append(time.time())
        threading.Timer(0.03, commit).start()
        return False

    fleet = FakeFleet(places, evals=lambda jid: None)
    player = traffic.Player(fleet, mix, config, 7, lambda: fleet.placed,
                            slots, eval_done=fleet.eval_done)
    played = player.play(30.0, "s", {"rounds": 3})
    assert played["end"] == "rounds" and fleet.placed == 3_600
    for rnd in (1, 2):
        whole = max(placed_at[4 * (rnd - 1):4 * rnd])
        sent = min(player.jobs[f"s-r{rnd:03d}-{k:05d}"]["sent"]
                   for k in range(4))
        assert 0.0 <= sent - whole < 0.05


def test_lone_pass_stops_at_the_first_size_left_short():
    config, _mix, slots = sixteen_nodes()
    mix = run.load_json("traffic", "rehearsal-steady.json")
    fleet = ending("lone-00002", "failed")        # the third size: 5
    player = traffic.Player(fleet, mix, config, 7, lambda: fleet.placed,
                            slots, eval_done=fleet.eval_done)
    t0 = time.time()
    asked = player.play_alone("lone", 0, t0 + 600.0)
    assert time.time() - t0 < 1.0
    assert asked == 1 + 2 + 5 and fleet.placed == 3
    assert len(fleet.registered) == 3


def test_single_round_whose_evaluations_have_ended_ends_the_window():
    config, _mix, slots = sixteen_nodes()
    mix = dict(run.load_json("traffic", "rehearsal-backlog.json"),
               arrivals={"process": "at_once", "jobs": 6})
    fleet = ending("s-r000-00004", "failed")
    player = traffic.Player(fleet, mix, config, 7, lambda: fleet.placed,
                            slots, eval_done=fleet.eval_done)
    played = player.play(30.0, "s")
    assert played["end"] == "round_short" and played["rounds"] == 1
    assert played["closed"] - played["opened"] < 1.0 + traffic.HOLD_SETTLE_S
    assert played["asked"] - fleet.placed == mix["sizes"][0]
    # Placed whole it ends drained, as before.
    fleet = FakeFleet()
    player = traffic.Player(fleet, mix, config, 7, lambda: fleet.placed,
                            slots, eval_done=fleet.eval_done)
    assert player.play(30.0, "s")["end"] == "drained"


def test_one_clock_bounds_every_wait_of_a_play():
    # ``limit`` is the warm-up's one clock: neither the mix's seconds nor
    # the grace carry a round past it, and settle_placed ends with it.
    assert traffic.ROUND_GRACE_S == 120.0
    config, mix, slots = sixteen_nodes()
    fleet = FakeFleet(places=lambda jid: False)
    player = traffic.Player(fleet, mix, config, 7, lambda: fleet.placed,
                            slots, eval_done=fleet.eval_done)
    t0 = time.time()
    played = player.play(30.0, "warm", mix["warmup"], limit=t0 + 0.3)
    assert not player.settle_placed(played["asked"], t0 + 0.3)
    assert 0.3 <= time.time() - t0 < 1.0
    assert played["rounds"] == 1 and played["asked"] == 1_200


def test_a_run_that_makes_no_progress_ends_inside_the_drivers_limit():
    # The driver stops a run after 1,200 s (the first of a cell, which
    # compiles) and a set-up takes some 60 s before the warm-up starts.
    # A warm-up that does not finish exits 3 inside WARMUP_TIMEOUT_S of
    # its start; after one that finished, the waits that can pass with
    # no progress are the window, the grace, the drain, the quiet and the
    # tracer's join.
    driver, setup = 1200.0, 60.0
    assert run.WARMUP_TIMEOUT_S == 900.0
    assert traffic.ROUND_GRACE_S == 120.0 and traffic.HOLD_SETTLE_S == 1.0
    assert setup + run.WARMUP_TIMEOUT_S < driver
    after = (BENCH["run_seconds"] + traffic.ROUND_GRACE_S
             + run.DRAIN_TIMEOUT_S + run.QUIET_S + run.TRACE_JOIN_S)
    assert after == 45 + 120 + 60 + 10 + 120
    assert setup + after < driver
    # No mix asks its warm-up to last longer than the one clock lets it.
    for name in os.listdir(os.path.join(HERE, "traffic")):
        warmup = run.load_json("traffic", name).get("warmup", {})
        assert warmup.get("seconds", 0) <= run.WARMUP_TIMEOUT_S


# -- jobs that end ----------------------------------------------------------------


def churn_players(fleet, slots, after_rounds=2, warm_rounds=3):
    """The warm-up's player and the window's, as run.py makes them."""
    config, mix, _ = sixteen_nodes()
    mix = dict(mix, stop={"after_rounds": after_rounds},
               warmup=dict(mix["warmup"], rounds=warm_rounds))
    warm = traffic.Player(fleet, mix, config, 11, lambda: fleet.placed,
                          slots, eval_done=fleet.eval_done)
    played = warm.play(30.0, "warm", mix["warmup"])
    assert played["rounds"] == warm_rounds and played["end"] == "rounds"
    return warm, traffic.Player(
        fleet, mix, config, 2_500_000_321, lambda: fleet.placed,
        warm.slots_left, live=warm.live, eval_done=fleet.eval_done)


def test_committed_stop_gives_its_slots_back_and_the_loop_never_fills():
    # 4,800 slots, rounds of 1,200, two waves live: the warm-up's third
    # round stops its first, and every round of the window places four
    # jobs and stops the oldest four.
    fleet = FakeFleet()
    warm, player = churn_players(fleet, 4_800)
    assert warm.slots_left == 4_800 - 2 * 1_200 and len(warm.live) == 2
    assert len(warm.stops) == 4 and len(fleet.deregistered) == 4
    assert [e.get("stop", e.get("id")) for e in warm.offered[-8:]] == (
        [w for w, _n in warm.live[-1]] + fleet.deregistered)
    played = player.play(0.3, "s", target_base=fleet.placed)
    assert played["end"] == "deadline" and played["rounds"] >= 3
    assert player.slots_left == 2_400 and len(player.live) == 2
    # Oldest first: the warm-up's second wave, its third, the window's first.
    waves = [[j for j in fleet.registered if f"{tag}-r{r:03d}-" in j]
             for tag, r in (("warm", 0), ("warm", 1), ("warm", 2), ("s", 0))]
    assert fleet.deregistered[:16] == sum(waves, [])
    assert len(player.stops) == 4 * played["rounds"]
    assert all(r["status"] == "complete" for r in player.stops.values())
    assert all(set(r) == {"offered", "placed", "stopped"}
               for r in player.round_log)


def test_stop_that_never_ends_fails_its_tasks_and_frees_nothing(monkeypatch):
    monkeypatch.setattr(traffic, "ROUND_GRACE_S", 0.2)
    fleet = FakeFleet(
        stops=lambda jid: None if jid.startswith("warm-r001-00000")
        else "failed" if jid.startswith("warm-r001-00001") else "complete")
    _warm, player = churn_players(fleet, 4_800)
    played = player.play(0.3, "s", target_base=fleet.placed)
    # The first round of the window waits the grace out for the stop whose
    # evaluation never shows, and the loop ends there.
    assert played["end"] == "deadline" and played["rounds"] == 1
    done = [r for r in player.stops.values() if r.get("status") == "complete"]
    assert len(player.stops) == 4 and len(done) == 2
    assert player.slots_left == 2_400 - 1_200 + 2 * 300
    assert not player.settle_stops(0.0)


def test_stop_whose_evaluation_failed_ends_the_loop_at_once():
    assert traffic.ROUND_GRACE_S == 120.0
    fleet = FakeFleet(stops=lambda jid: "failed"
                      if jid.startswith("warm-r001-00001") else "complete")
    _warm, player = churn_players(fleet, 4_800)
    played = player.play(30.0, "s", target_base=fleet.placed)
    assert played["end"] == "round_short" and played["rounds"] == 1
    assert played["closed"] - played["opened"] < 1.0
    assert sorted(r["status"] for r in player.stops.values()) == [
        "complete"] * 3 + ["failed"]
    assert player.slots_left == 2_400 - 1_200 + 3 * 300


def test_first_round_that_does_not_fit_beside_the_live_waves_is_refused():
    # Two waves live hold 2,400 of 3,500 slots: a third does not fit
    # beside them, though it would fit the empty cell.
    fleet = FakeFleet()
    warm, player = churn_players(fleet, 3_500, warm_rounds=2)
    assert warm.slots_left == 1_100 and not warm.stops
    with pytest.raises(ValueError, match="has 1100 slots left"):
        player.play(1.0, "s")
    assert len(fleet.registered) == 8 and not fleet.deregistered


def wave_entries(n_waves, live, size=320, jobs=1):
    """``n_waves`` waves of ``jobs`` jobs, each wave stopped ``live``
    waves later: the entries as ``Player.offered`` holds them."""
    config = run.load_json("configs", "rehearsal-256.json")
    out = []
    for w in range(n_waves):
        out += [job_spec(config["task"], f"w{w}-{k}", "batch", size)
                for k in range(jobs)]
        if w >= live:
            out += [{"stop": f"w{w - live}-{k}", "round": w}
                    for k in range(jobs)]
    return out


def test_place_with_stops_gives_the_capacity_back():
    # Two nodes x 320 tasks: the cell holds two waves of 320. Five waves,
    # each stopped before the next but one, are all placed; without the
    # stops the third finds no room.
    nodes, _ = small_cluster(2)
    jobs_only = wave_entries(5, live=5)
    entries = []
    for w, job in enumerate(jobs_only):
        if w >= 2:
            entries.append({"stop": f"w{w - 2}-0", "round": w})
        entries.append(job)
    placed = reference.place(nodes, entries)
    assert [len(placed[f"w{w}-0"]) for w in range(5)] == [320] * 5
    # The generator's own order, a round's stops after its jobs, wants room
    # for the wave that comes beside the two that are live.
    placed = reference.place(nodes, wave_entries(5, live=2))
    assert [len(placed[f"w{w}-0"]) for w in range(5)] == [320, 320, 0, 320, 320]
    placed = reference.place(nodes, jobs_only)
    assert [len(placed[f"w{w}-0"]) for w in range(5)] == [320, 320, 0, 0, 0]
    # A stop of a job never offered, or offered after it, gives nothing.
    placed = reference.place(nodes, [{"stop": "w2-0", "round": 0}] + jobs_only)
    assert sum(len(v) for v in placed.values()) == 640


def rows_of(nodes, job, parts):
    """The rows of one job placed as ``reference._place`` says: each
    group's tasks with that group's cpu and memory."""
    tasks = [(int(i), g["cpu"], g["memory_mb"])
             for g, part in zip(job["groups"], parts) for i in part]
    return [(f"{job['id']}/{k}", job["id"], nodes[i]["id"], cpu, mem)
            for k, (i, cpu, mem) in enumerate(tasks)]


def sound_answers(nodes, entries):
    """The reference's own placement as a set of answers, every stop
    acknowledged and carried out."""
    placed = reference._place(nodes, entries)
    stopped = {e["stop"] for e in entries if "stop" in e}
    rows = {j["id"]: [] if j["id"] in stopped else
            rows_of(nodes, j, placed[j["id"]])
            for j in entries if "stop" not in j}
    by_node: dict = {}
    for rs in rows.values():
        for r in rs:
            by_node.setdefault(r[2], []).append(r)
    return rows, reference.Answers(
        {jid: sum(map(len, placed[jid])) for jid in rows},
        lambda jid: rows[jid],
        lambda nid: by_node.get(nid, []), stopped=stopped)


def test_one_stopped_job_left_running_is_counted():
    nodes, _ = small_cluster(8)
    entries = wave_entries(6, live=2, size=100, jobs=3)
    rows, answers = sound_answers(nodes, entries)
    numbers = reference.compare(nodes, entries[6:], answers, 7,
                                prior=entries[:6])
    assert reference.verdict(numbers), numbers
    # The fault: one stopped job's rows still read back as running.
    rows["w2-1"] = [("w2-1/0", "w2-1", nodes[0]["id"], 100, 128)]
    numbers = reference.compare(nodes, entries[6:], answers, 7,
                                prior=entries[:6])
    assert numbers["stopped_running"] == 1 and not reference.verdict(numbers)
    assert numbers["store_mismatch"] == 1     # w2-1 is among the jobs due
    # A stop the answers do not acknowledge is not judged, and not applied.
    answers.stopped = answers.stopped - {"w2-1"}
    rows["w2-1"] = [(f"w2-1/{k}", "w2-1", nodes[0]["id"], 100, 128)
                    for k in range(100)]
    numbers = reference.compare(nodes, entries[6:], answers, 7,
                                prior=entries[:6])
    assert numbers["stopped_running"] == 0 == numbers["store_mismatch"]


def test_the_last_wave_stopped_is_always_read_back(monkeypatch):
    # 30 stopped jobs of 100 against a sample of 1,000: the last wave's
    # three are in it whatever the seed draws.
    monkeypatch.setattr(reference, "MAX_SAMPLED_ALLOCS", 1_000)
    nodes, _ = small_cluster(32)
    entries = wave_entries(12, live=2, size=100, jobs=3)
    rows, answers = sound_answers(nodes, entries)
    rows["w9-2"] = [("w9-2/0", "w9-2", nodes[0]["id"], 100, 128)]
    for seed in (1, 2_147_483_659, 4_000_000_007):
        numbers = reference.compare(nodes, entries, answers, seed)
        assert numbers["stopped_running"] == 1


# Recorded from the generator as it stood before it knew of stops (PR 29's
# tree) and of shapes (PR 30's: batch-churn): sha256 over round_plan of
# rounds 0-2 on three seeds at 45 s, and over the items and payloads dealt
# to each sender (what the server is told; PR 30's tree gives the same).
STANDING_ROUNDS = {
    "burst-100k": (
        "f4d503d88cd1a0bd564a27975e0e1c382dfa4f5f1d9d55877225fb25dc28ed8f",
        "9bb3051bbf6009f6c7717ac87466ab8c3e8f540baa1e6b05d8c4972a0e4e910f"),
    "steady-small": (
        "ce26c9410153611021982526818efadff2a924febcd6c8f43e01bc287a62472b",
        "1845ac797ffc50bfe90b4a1a52314baacc4ba0db32b7fbc0c641756fca90db8e"),
    "backlog-1m": (
        "eaa84e5bdb63346d54d53ec86abc9b0ea1ca3a9a7ca1a9ea2c508001c8fb9653",
        "ac4bb94e89c8bdf87afca257a98b70f5a157c49d09ddd080055382c5c7cb5065"),
    "batch-churn": (
        "689b371bb7fa315f8bd93ddd28849fcb99b75b1560d1d411f64bcf0a066f1645",
        "e829438ac8be954bbfc2a5a0115595f9b6707f4c94b11d6a49b0dc08343e05b0"),
}


@pytest.mark.parametrize("name", sorted(STANDING_ROUNDS))
def test_standing_mixes_build_the_rounds_they_built_before(name):
    mix = run.load_json("traffic", name + ".json")
    assert "templates" not in mix and "fill_limit" not in mix
    config = run.load_json("configs", "cell-10k.json")
    h_plan, h_deal = hashlib.sha256(), hashlib.sha256()
    for seed in (7, 2_500_000_321, 4_000_000_007):
        player = traffic.Player(FakeFleet(), mix, config, seed, lambda: 0,
                                10**9)
        for rnd in range(3):
            plan = traffic.round_plan(mix, seed, 45.0, rnd, f"s{seed}")
            h_plan.update(json.dumps(plan, sort_keys=True).encode())
            ready = player._build(plan, mix)
            shares = traffic.deal(ready, max(1, int(mix.get("senders", 4))))
            h_deal.update(json.dumps(
                [[[item, payload] for item, _rec, payload in s]
                 for s in shares], sort_keys=True).encode())
    assert (h_plan.hexdigest(), h_deal.hexdigest()) == STANDING_ROUNDS[name]


def test_standing_configurations_tell_the_server_what_they_told_it():
    # Recorded from PR 30's tree: every node_spec of the three standing
    # configurations, and to_dict(build_job(...)) of their task at three
    # sizes and both types, byte for byte.
    from nomad_tpu.api.codec import to_dict

    h = hashlib.sha256()
    for name in ("cell-10k", "c1m-5k", "rehearsal-256"):
        config = run.load_json("configs", name + ".json")
        assert "shapes" not in config["nodes"]
        h.update(json.dumps(
            [node_spec(config["nodes"], i)
             for i in range(config["nodes"]["count"])],
            sort_keys=True).encode())
        for size in (1, 50, 12500):
            for jtype in ("batch", "service"):
                spec = job_spec(config["task"], f"j{size}", jtype, size)
                h.update(json.dumps(to_dict(build_job(spec)),
                                    sort_keys=True).encode())
    assert h.hexdigest() == (
        "a2e6f9ee5ce048383b89a36c0af276e77f1bef08ea353d5bc9d2884509fd1559")


@pytest.mark.parametrize("name,mix_name,eligible_nodes,rounds,slots", [
    # 10,000 - 625 windows; x 320: 30 rounds of 8 x 12,500
    ("cell-10k", "burst-100k", 9_375, 30, 3_000_000),
    # 5,000 - 312 windows; x 320: one round of 1,000 x 1,000 and a half
    ("c1m-5k", "backlog-1m", 4_688, 1, 1_500_160),
])
def test_one_shape_fits_its_slots_whatever_the_rounds(
        name, mix_name, eligible_nodes, rounds, slots):
    config = run.load_json("configs", name + ".json")
    mix = run.load_json("traffic", mix_name + ".json")
    shape, task = config["nodes"], config["task"]
    nodes = [node_spec(shape, i) for i in range(shape["count"])]
    job = job_spec(task, "j", "batch", slots + 1_000)
    assert sum(reference.eligible(nd, job) for nd in nodes) == eligible_nodes
    # 32,000 MHz / 100 = 320 by cpu, 65,536 MB / 128 = 512 by memory.
    assert min(shape["cpu"] // task["cpu"],
               shape["memory_mb"] // task["memory_mb"]) == 320
    assert slots == eligible_nodes * 320
    assert reference.rounds_that_fit(nodes, [[job]]) == (0, slots)
    assert reference.rounds_that_fit(
        nodes, traffic.rounds_of(mix, config, 7, 45.0)) == (rounds, slots)
    assert len(reference.place(nodes, [job])["j"]) == slots


# -- cells of several shapes ------------------------------------------------------


def borg():
    config = run.load_json("configs", "borg-12k.json")
    shape = config["nodes"]
    return config, [node_spec(shape, i) for i in range(node_count(shape))]


def test_shapes_are_dealt_by_largest_remainders():
    # Hand-worked: 2 : 1 : 1 over four indices. Index 0: shares 0.5, 0.25,
    # 0.25, the first is furthest behind; index 1: 1.0 - 1, 0.5, 0.5, the
    # earlier of the tie; index 2: 1.5 - 1, 0.75 - 1, 0.75; index 3: 2 - 1.
    assert deal_shapes((2, 1, 1)) == (0, 1, 2, 0)
    assert deal_shapes((3,)) == (0, 0, 0)
    config, nodes = borg()
    counts = tuple(s["count"] for s in config["nodes"]["shapes"])
    assert len(counts) == 10 and sum(counts) == 12_583 == len(nodes)
    dealt = deal_shapes(counts)
    assert [dealt.count(k) for k in range(10)] == list(counts)
    # Any 100 consecutive indices hold 52-54 of the 6,732 commonest
    # machines (53.5 in proportion) and 5-7 of the 795 largest (6.3).
    for k, lo, hi in ((0, 52, 54), (3, 5, 7)):
        held = [sum(1 for s in dealt[i:i + 100] if s == k)
                for i in range(len(dealt) - 100)]
        assert (min(held), max(held)) == (lo, hi)
    # Every prefix holds each shape's share to within a node and a half.
    seen = [0] * 10
    for i, s in enumerate(dealt):
        seen[s] += 1
        if i % 97 == 0:
            assert all(abs(seen[k] - counts[k] * (i + 1) / 12_583) < 1.5
                       for k in range(10))


def test_borg_12k_is_the_published_machine_table():
    config, nodes = borg()
    table = [(s["count"], s["attributes"]["platform"], *s["normalised"])
             for s in config["nodes"]["shapes"]]
    assert table == [
        (6732, "B", .5, .5), (3863, "B", .5, .25), (1001, "B", .5, .75),
        (795, "C", 1.0, 1.0), (126, "A", .25, .25), (52, "B", .5, .12),
        (5, "B", .5, .03), (5, "B", .5, .97), (3, "C", 1.0, .5),
        (1, "B", .5, .06)]
    assert sum(nd["cpu"] for nd in nodes) == 426_176_000
    assert sum(nd["memory_mb"] for nd in nodes) == 776_182_131
    assert nodes[0]["cpu"] == 32_000 and nodes[0]["memory_mb"] == 65_536
    assert nodes[7]["attributes"]["platform"] == "C"        # dealt eighth
    assert (nodes[7]["cpu"], nodes[7]["memory_mb"]) == (64_000, 131_072)
    assert all(nd["attributes"]["driver.exec"] == "1" for nd in nodes)
    assert config["reduced"] == ["window", "servers"]
    # Variants and datacenters work on top of the shapes.
    reh = run.load_json("configs", "rehearsal-shapes-256.json")["nodes"]
    assert node_count(reh) == 256
    spec = node_spec(reh, 15)
    assert spec["attributes"]["kernel.name"] == "windows"
    assert spec["attributes"]["platform"] in "ABC"
    assert spec["datacenter"] == "dc2"


def test_the_mixed_wave_is_the_issues_table():
    # ISSUE 31's wave, the data of the cell borg-12k.mixed-shapes, which
    # comes with the program's repair (PERF.md section 7): 64 jobs,
    # 6,940 tasks.
    config, _ = borg()
    mix = run.load_json("traffic", "mixed-shapes.json")
    plans = [traffic.round_plan(mix, seed, 45.0, rnd, "s")
             for seed, rnd in ((1, 0), (1, 1), (4_000_000_007, 0))]
    # Every seed and every round the same 64 templates in another order.
    assert all(sorted(p["template"] for p in plan) ==
               sorted(p["template"] for p in plans[0]) for plan in plans)
    assert [p["template"] for p in plans[0]] != [
        p["template"] for p in plans[1]] != [p["template"] for p in plans[2]]
    jobs = [traffic.item_spec(config, mix, item) for item in plans[0]]
    assert len(jobs) == 64 and sum(j["count"] for j in jobs) == 6_940
    sizes = sorted(j["count"] for j in jobs)
    assert sizes == [1] * 40 + [10] * 10 + [100] * 8 + [500] * 4 + [2000] * 2
    groups = [g for j in jobs for g in j["groups"]]
    assert len(groups) == 70            # 58 jobs of one group, 6 of two
    assert sum(g["count"] * g["cpu"] for g in groups) == 6_696_800
    assert sum(g["count"] * g["memory_mb"] for g in groups) == 16_508_928
    by_priority = {p: sum(1 for j in jobs if j["priority"] == p)
                   for p in (20, 50, 80)}
    assert by_priority == {20: 12, 50: 39, 80: 13}
    assert all((j["type"] == "service") == (j["priority"] == 80)
               for j in jobs)
    own = [tuple(j["constraints"][1]) for j in jobs
           if len(j["constraints"]) > 1]
    assert sorted(own) == (
        [("$attr.platform", "!=", "A")] * 6 + [("$attr.platform", "=", "C")] * 4)
    # The exact path takes 58 evaluations and the water-fill 6, whose
    # groups are all over BATCH_PLACE_THRESHOLD = 256 but the 200s.
    assert sum(1 for j in jobs if j["count"] <= 128) == 58
    assert min(g["count"] for j in jobs if j["count"] > 128
               for g in j["groups"]) == 200


def two_machines():
    base = {"datacenter": "dc1", "ready": True,
            "attributes": {"driver.exec": "1", "platform": "B"}}
    return [dict(base, id="small", cpu=1_000, memory_mb=1_000),
            dict(base, id="large", cpu=4_000, memory_mb=4_000)]


TASK = {"driver": "exec", "datacenters": ["dc1"]}


def grouped(job_id, *groups):
    return job_spec(TASK, job_id, "batch", groups=[
        {"name": f"g{k}", "count": n, "cpu": cpu, "memory_mb": mem}
        for k, (n, cpu, mem) in enumerate(groups)])


def test_place_on_two_shapes_group_by_group_and_a_stop_gives_both_back():
    nodes = two_machines()
    j1 = grouped("j1", (3, 1_000, 1_000), (2, 500, 500))
    j2 = grouped("j2", (2, 1_000, 1_000))
    j3 = grouped("j3", (5, 1_000, 1_000))
    # j1's first group: one on the small machine (it is full), two on the
    # large; its second: the small machine has no slot, both on the large
    # (1,000 / 1,000 left there). j2 finds one slot.
    parts = reference._place(nodes, [j1, j2])
    assert [p.tolist() for p in parts["j1"]] == [[0, 1, 1], [1, 1]]
    assert [p.tolist() for p in parts["j2"]] == [[1]]
    assert j1["count"] == 5 and len(reference.place(nodes, [j1])["j1"]) == 5
    # Without the stop j3 finds nothing; the stop gives back both groups
    # of j1, on both machines: one slot on the small, three on the large.
    assert len(reference.place(nodes, [j1, j2, j3])["j3"]) == 0
    placed = reference.place(
        nodes, [j1, j2, {"stop": "j1", "round": 1}, j3])
    assert placed["j3"].tolist() == [0, 1, 1, 1]
    # A node too small for a task is not ineligible: it has no slot.
    big = grouped("big", (2, 2_000, 2_000))
    assert all(reference.eligible(nd, big) for nd in nodes)
    assert reference.place(nodes, [big])["big"].tolist() == [1, 1]


@pytest.mark.parametrize("seed", [1, 2_500_000_321, 4_000_000_007])
def test_first_fit_places_45_rounds_of_the_mixed_wave_on_borg_12k(seed):
    config, nodes = borg()
    mix = run.load_json("traffic", "mixed-shapes.json")
    rounds, tasks = reference.rounds_that_fit(
        nodes, traffic.rounds_of(mix, config, seed, 45.0))
    assert rounds == 45 and 45 * 6_940 <= tasks < 46 * 6_940
    # The loop offers whole rounds inside fill_limit x tasks: 27 of them,
    # floor(0.6 x 45), the warm-up's counted.
    assert mix["fill_limit"] == 0.6
    assert int(0.6 * tasks + 1e-9) // 6_940 == 27


def test_rows_of_the_wrong_shape_are_counted_job_by_job():
    nodes = two_machines() * 1
    nodes[0] = dict(nodes[0], cpu=64_000, memory_mb=64_000)
    nodes[1] = dict(nodes[1], cpu=64_000, memory_mb=64_000)
    jobs = [grouped("j1", (3, 100, 200), (2, 400, 800)),
            grouped("j2", (4, 100, 200))]
    rows, answers = sound_answers(nodes, jobs)
    assert reference.verdict(reference.compare(nodes, jobs, answers, 7))
    # The fault: the second group's rows carry the first group's shape.
    sound = list(rows["j1"])
    rows["j1"] = [r[:3] + (100, 200) for r in sound]
    numbers = reference.compare(nodes, jobs, answers, 7)
    assert numbers["wrong_resources"] == 1 and not reference.verdict(numbers)
    # A shape no group asked for is counted too; a job left short whose
    # rows are all of shapes asked is jobs_short's and store_mismatch's.
    rows["j1"] = sound[:4] + [sound[4][:3] + (400, 801)]
    assert reference.compare(nodes, jobs, answers, 7)["wrong_resources"] == 1
    rows["j1"] = sound[:4]
    numbers = reference.compare(nodes, jobs, answers, 7)
    assert numbers["wrong_resources"] == 0 and numbers["store_mismatch"] == 1


def test_a_small_machine_is_held_to_its_own_capacity():
    nodes = two_machines()
    job = grouped("j", (3, 1_000, 1_000))
    rows, answers = sound_answers(nodes, [job])
    assert reference.verdict(reference.compare(nodes, [job], answers, 7))
    # Two tasks on the large machine are in order; the same two on the
    # small one are over its capacity.
    on_large = [r[:2] + ("large",) + r[3:] for r in rows["j"][:2]]
    on_small = [r[:2] + ("small",) + r[3:] for r in rows["j"][:2]]
    for moved, over in ((on_large, 0), (on_small, 1)):
        answers.allocs_by_job = lambda jid, m=moved: m
        answers.allocs_by_node = lambda nid, m=moved: [
            r for r in m if r[2] == nid]
        answers.committed = {"j": 2}
        numbers = reference.compare(nodes, [job], answers, 7)
        assert numbers["nodes_over_capacity"] == over


def rehearsal_wave(seed=7, rounds=1):
    """The rehearsal pair as plain data: its nodes and the jobs of its
    first rounds."""
    config = run.load_json("configs", "rehearsal-shapes-256.json")
    mix = run.load_json("traffic", "rehearsal-mixed.json")
    nodes = [node_spec(config["nodes"], i) for i in range(256)]
    jobs = [traffic.item_spec(config, mix, item) for rnd in range(rounds)
            for item in traffic.round_plan(mix, seed, 45.0, rnd, "t")]
    return nodes, jobs


@pytest.mark.parametrize("broken,number", [
    ("capacity", "nodes_over_capacity"),
    ("eligibility", "ineligible"),
    ("commit", "store_mismatch"),
    ("stop", "stopped_running"),
])
@pytest.mark.parametrize("seed", [3, 2_147_483_659, 4_000_000_007])
def test_controls_fail_on_the_rehearsal_pair(broken, number, seed):
    nodes, jobs = rehearsal_wave(seed, rounds=3)
    stops = [{"stop": j["id"], "round": 3} for j in jobs[:3]
             if j["count"] > 1]
    entries = jobs + stops
    sound = reference.compare(nodes, entries, sound_answers(
        nodes, entries)[1], seed)
    assert reference.verdict(sound), sound
    numbers = reference.compare(
        nodes, entries, reference.control(nodes, entries, broken), seed)
    assert numbers[number] >= 1 and not reference.verdict(numbers)


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
    rows = {j["id"]: rows_of(nodes, j, [placed[j["id"]]]) for j in jobs}
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
    ("stop", "stopped_running"),
])
@pytest.mark.parametrize("seed", [3, 2_147_483_659, 4_000_000_007])
def test_control_comes_out_not_correct(broken, number, seed):
    nodes, jobs = small_cluster()
    if broken == "stop":
        jobs = jobs + [{"stop": "j1", "round": 1}, {"stop": "j5", "round": 1}]
    numbers = reference.compare(
        nodes, jobs, reference.control(nodes, jobs, broken), seed)
    assert numbers[number] > 0 and not reference.verdict(numbers)


@pytest.mark.parametrize("broken", ["capacity", "eligibility", "commit"])
def test_old_controls_still_fail_where_jobs_end(broken):
    nodes, _ = small_cluster(32)     # two of them fingerprint windows
    entries = wave_entries(6, live=2, size=100, jobs=3)
    numbers = reference.compare(
        nodes, entries[6:], reference.control(nodes, entries, broken), 7,
        prior=entries[:6])
    assert numbers["stopped_running"] == 0 and not reference.verdict(numbers)


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


def test_churn_places_and_stops_a_wave_each_round(capsys):
    # Rounds of 4 x 60 with two waves live: the warm-up's third round
    # stops its first, every round of the window stops as much as it
    # places, and the cell never fills.
    result, out = drive(capsys, "rehearsal-256.rehearsal-churn", seconds=3.0)
    report = report_of(out)
    assert result["correct"] is True and result["failed"] == 0
    assert report["window_end"] == "deadline" and report["rounds"] >= 2
    assert report["live_waves"] == 2
    assert report["slots_left"] == 240 * 320 - 2 * 240
    placed = 240 * report["rounds"]
    assert report["placed_in_window"] == placed == report["asked"]
    assert report["stops"] == report["stops_asked"] == placed
    assert result["attempted"] == 2 * placed
    assert set(result["metrics"]) == {"placements_per_s", "setup_s"}
    assert result["compared"]["stopped_running"] == {"value": 0, "limit": 0}
    # The window closes with its last round's end: the rate is the
    # placements over a length that holds every round's stops too.
    assert report["round_log"][-1][2] == pytest.approx(
        report["seconds"], abs=0.1)


def test_traced_churn_reports_the_stop_metrics(capsys):
    result, _ = drive(capsys, "rehearsal-256.rehearsal-churn", seconds=3.0,
                      trace=1)
    assert result["correct"] is True
    new = {"stops_per_s.churn", "stop_job_p50_ms.churn",
           "round_stop_wait_pct.churn", "stop_schedule_mean_ms.churn",
           "stop_verify_mean_ms.churn", "stop_commit_mean_ms.churn",
           "raft_log_bytes_per_stop.churn"}
    assert new <= set(result["metrics"])
    assert all(result["metrics"][m]["value"] > 0 for m in new)
    assert {"schedule_solve_mean_ms.drain", "scalar_plans_per_plan.drain",
            "usage_rolls_per_solve.drain"} <= set(result["metrics"])
    assert "raft_log_bytes_per_placement.drain" not in result["metrics"]


MIXED = "rehearsal-shapes-256.rehearsal-mixed"


def test_mixed_shapes_fill_the_cell_to_its_limit_and_end_there(capsys):
    # The rehearsal wave is three jobs no two of which share a node (the
    # program loses placements where evaluations race for one machine:
    # PERF.md section 7): 300 tasks on platform B by two "!=", 60 + 40 in
    # two groups on C by "=", one task of the largest shape on A. First
    # fit places 9 rounds whole on the 256 machines of four shapes; the
    # fill limit of 0.6 lets the loop offer floor(0.6 x 9) = 5: the
    # warm-up's and four in the window.
    result, out = drive(capsys, MIXED, seconds=600.0)
    report = report_of(out)
    assert report["rounds_fit"] == 9 and report["fill_limit"] == 0.6
    assert report["window_end"] == "cell_full" and report["rounds"] == 4
    assert report["slots_left"] < 401 and report["seconds"] < 300.0
    assert result["correct"] is True and result["failed"] == 0, report[
        "jobs_not_whole"]
    assert result["attempted"] == 4 * 401 == report["placed_in_window"]
    assert report["jobs_due"] == 4 * 3 and report["jobs_not_whole"] == []
    assert set(result["metrics"]) == {"placements_per_s", "setup_s"}
    assert all(v["value"] == 0 for v in result["compared"].values())
    # One plan an evaluation, and none refused: no two jobs share a node.
    assert report["plans_per_eval"] == {"1": 12}
    assert report["counters"]["pipeline.conflicts"] == 0


def test_traced_mixed_shapes_report_two_solves_for_two_groups(capsys):
    result, out = drive(capsys, MIXED, seconds=600.0, trace=1)
    assert result["correct"] is True
    counters = report_of(out)["counters"]
    # Four solves for three evaluations a wave: the job of two groups
    # solves twice, with the plan's own delta between.
    assert counters["panel.solves"] == 16 and counters["window.evals"] == 12
    assert result["metrics"]["plan_conflicts_per_plan.drain"]["value"] == 0
    assert {"schedule_solve_mean_ms.drain", "scalar_plans_per_plan.drain",
            "raft_log_bytes_per_placement.drain"} <= set(result["metrics"])


def test_steady_large_reports_the_steady_metrics_but_the_greedy_kernel(capsys):
    # Jobs of 300-500 tasks: every one over EXACT_THRESHOLD = 128 and
    # BATCH_PLACE_THRESHOLD = 256, so none rides the exact greedy path.
    mix = run.load_json("traffic", "steady-large.json")
    assert min(mix["sizes"]) > 256 and mix["arrivals"]["rate_per_s"] == 7
    small = run.load_json("traffic", "steady-small.json")
    assert {k: mix[k] for k in ("senders", "node_refresh", "warmup",
                                "at_close", "job_type", "repeat")} == {
        k: small[k] for k in ("senders", "node_refresh", "warmup",
                              "at_close", "job_type", "repeat")}
    cell = run.Cell("cell-10k.steady-large")
    layers = {m["name"] for m in cell.per_layer()}
    steady = {m["name"] for m in run.Cell("cell-10k.steady-small").per_layer()}
    assert layers - steady == {"waterfill_kernel_us.steady"}
    assert steady - layers == {"greedy_kernel_us.steady"}
    result, out = drive(capsys, "rehearsal-256.rehearsal-steady-large",
                        seconds=3.0, trace=1)
    report = report_of(out)
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] == 9 == report["jobs_due"]
    assert report["asked"] == 3 * (300 + 400 + 500)
    assert {"broker_wait_mean_ms.steady", "solver_staging_mean_ms.steady",
            "plan_verify_mean_ms.steady", "generator_late_p95_ms",
            "placed_tail_p95_ms"} <= set(result["metrics"])
    assert "greedy_kernel_us.steady" not in result["metrics"]
    assert report["counters"].get("coalescer.paths.exact", 0) == 0


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


def _break_stop_unchanged(monkeypatch):
    """A stop that returns its state unchanged: the plan that takes a
    job's allocations away is acknowledged, its evaluation completes, and
    the rows stay as they were."""
    from nomad_tpu.state.store import StateStore

    real = StateStore.upsert_allocs
    monkeypatch.setattr(
        StateStore, "upsert_allocs",
        lambda self, index, allocs: real(
            self, index, [a for a in allocs if a.desired_status == "run"]))


def _break_stop_half_left_out(monkeypatch):
    """Half of every stop left out where it is written."""
    from nomad_tpu.state.store import StateStore

    real = StateStore.upsert_allocs

    def half(self, index, allocs):
        stops = [a for a in allocs if a.desired_status != "run"]
        keep = {id(a) for a in stops[::2]}
        return real(self, index, [a for a in allocs
                                  if a.desired_status == "run"
                                  or id(a) in keep])

    monkeypatch.setattr(StateStore, "upsert_allocs", half)


STEADY, CHURN = "rehearsal-256.rehearsal-steady", "rehearsal-256.rehearsal-churn"


@pytest.mark.parametrize("workload,fault,number", [
    (STEADY, _break_state_unchanged, "store_mismatch"),
    (STEADY, _break_half_left_out, "store_mismatch"),
    (STEADY, _break_answer_altered, "ineligible"),
    (CHURN, _break_state_unchanged, "store_mismatch"),
    (CHURN, _break_stop_unchanged, "stopped_running"),
    (CHURN, _break_stop_half_left_out, "stopped_running"),
])
def test_broken_timed_path_comes_out_not_correct(
        capsys, monkeypatch, workload, fault, number):
    # The warm-up runs sound; the fault is planted as the window opens.
    from benchmark.generators.traffic import Player

    real_play = Player.play

    def play(self, seconds, tag, *args, **kwargs):
        if tag != "warm":
            fault(monkeypatch)
        return real_play(self, seconds, tag, *args, **kwargs)

    monkeypatch.setattr(Player, "play", play)
    result, _ = drive(capsys, workload)
    assert result["correct"] is False
    assert result["compared"][number]["value"] > 0


# -- a program that cannot place the cell's traffic: exit 3 in seconds --------


def _evaluations_fail(monkeypatch):
    """The server's scheduler gives every attempt up: each evaluation
    ends ``failed`` with nothing placed (what the program does to the jobs
    it loses a race for, here without leaning on that fault)."""
    from nomad_tpu.scheduler.generic import GenericScheduler

    monkeypatch.setattr(GenericScheduler, "_process", lambda self: False)


def _registrations_refused(monkeypatch):
    """The front door refuses every ``Job.Register``."""
    from benchmark.generators.fleet import Fleet

    real = Fleet.call

    def call(self, method, args):
        if method == "Job.Register":
            raise RuntimeError("refused")
        return real(self, method, args)

    monkeypatch.setattr(Fleet, "call", call)


def _nothing_ends(monkeypatch):
    """Worse than failing: the workers take no evaluation, so nothing is
    placed and nothing ends. The one clock, cut to a second, ends it."""
    from benchmark.generators.traffic import Player

    real_play = Player.play

    def play(self, *args, **kwargs):
        self.hold(True)
        # As for a preloaded round: a worker already waiting in the
        # broker's dequeue would still take what comes next.
        time.sleep(traffic.HOLD_SETTLE_S)
        return real_play(self, *args, **kwargs)

    monkeypatch.setattr(Player, "play", play)
    monkeypatch.setattr(run, "WARMUP_TIMEOUT_S", 1.0)


BURST, BACKLOG = ("rehearsal-256.rehearsal-burst",
                  "rehearsal-256.rehearsal-backlog")


@pytest.mark.parametrize("workload,fault,ends", [
    (BURST, _evaluations_fail, "failed"),
    (STEADY, _evaluations_fail, "failed"),
    (BACKLOG, _evaluations_fail, "failed"),
    (CHURN, _evaluations_fail, "failed"),
    (BURST, _registrations_refused, "[]"),
    (BURST, _nothing_ends, "[]"),
    (STEADY, _nothing_ends, "[]"),
], ids=["burst-failed", "steady-failed", "backlog-failed", "churn-failed",
        "burst-refused", "burst-nothing-ends", "steady-nothing-ends"])
def test_warm_up_left_short_exits_3_at_once(
        capsys, monkeypatch, workload, fault, ends):
    # ROUND_GRACE_S is 120, the mixes' warm-ups ask for up to 900 s and
    # WARMUP_TIMEOUT_S is 900 (1 under _nothing_ends): the exit comes with
    # the last evaluation's end, not with any of them.
    assert traffic.ROUND_GRACE_S == 120.0 and run.WARMUP_TIMEOUT_S == 900.0
    fault(monkeypatch)
    t0 = time.time()
    rc = run.main(["--workload", workload, "--seed", "2500000321",
                   "--seconds", "2", "--trace", "0"])
    took = time.time() - t0
    out = capsys.readouterr()
    assert rc == 3 and took < 60.0, out.err[-2000:]
    assert out.out.strip() == ""                       # no result
    offered = float(out.err.split("first fit places")[0].rsplit(
        "bench[", 1)[1].split("s]")[0])
    gave_up = float(out.err.split("warm-up placed")[0].rsplit(
        "bench[", 1)[1].split("s]")[0])
    assert gave_up - offered < 15.0
    short = [ln for ln in out.err.splitlines() if "  short: " in ln]
    assert short and all(" short: 0/" in ln for ln in short)
    assert all(ends in ln.rsplit(": ", 1)[1] for ln in short)


def test_window_whose_round_is_left_short_closes_there(capsys, monkeypatch):
    # The warm-up runs sound; from the window's first round on every
    # evaluation fails. The run ends as any finished run, not correct.
    from benchmark.generators.traffic import Player

    real_play = Player.play

    def play(self, seconds, tag, *args, **kwargs):
        if tag != "warm":
            _evaluations_fail(monkeypatch)
        return real_play(self, seconds, tag, *args, **kwargs)

    monkeypatch.setattr(Player, "play", play)
    t0 = time.time()
    rc = run.main(["--workload", BURST, "--seed", "2500000321",
                   "--seconds", "600", "--trace", "0"])
    out = capsys.readouterr()
    assert rc == 0 and time.time() - t0 < 60.0, out.err[-2000:]
    result, report = json.loads(out.out.strip().splitlines()[-1]), report_of(out)
    assert report["window_end"] == "round_short" and report["rounds"] == 1
    assert result["correct"] is False
    assert result["attempted"] == 1_200 == result["failed"]
    assert result["compared"]["jobs_short"]["value"] == 4
    assert report["drain_s"] < 5.0 and not report["drained"]
    assert [j["evals"][0][0] for j in report["jobs_not_whole"]] == ["failed"] * 4


class _TtlServer:
    """Grants a TTL as the program does: scaled by the timers armed when
    it arms one, 50 renewals a second, never under 10 s."""

    def __init__(self):
        self.armed = set()
        self.beats = []

    def grant(self, ids):
        out = {}
        for nid in ids:
            others = len(self.armed - {nid})
            self.armed.add(nid)
            out[nid] = max(others / 50.0, 10.0)
        return out

    def call(self, method, args):
        if method == "Node.BatchRegister":
            return {"heartbeat_ttls": self.grant(
                n["id"] for n in args["nodes"])}
        assert method == "Node.BatchHeartbeat"
        self.beats.append(list(args["node_ids"]))
        return {"heartbeat_ttls": self.grant(args["node_ids"])}


def test_one_renewal_in_set_up_gives_every_node_the_whole_fleets_ttl(
        monkeypatch):
    # The fleet's first nodes are granted 10 s and would be renewed with
    # 2 s in hand, in a run's first seconds; a host that stood still that
    # long once read 52 jobs placed twice over (PERF.md, PR 36).
    from benchmark.generators.fleet import Fleet, build_node

    server = _TtlServer()
    fleet = Fleet("nowhere")
    monkeypatch.setattr(fleet, "call", server.call)
    config = run.load_json("configs", "rehearsal-256.json")
    config["nodes"]["count"] = 2_000
    fleet.register([build_node(config["nodes"], node_spec(config["nodes"], i))
                    for i in range(2_000)])
    assert fleet.ttl_range() == (10.0, 1_999 / 50.0)
    assert min(due for due, _nid in fleet._due) < time.monotonic() + 8.5
    fleet.renew_all()
    assert fleet.ttl_range() == (1_999 / 50.0, 1_999 / 50.0)
    assert [len(b) for b in server.beats] == [500] * 4
    # Each node once on the schedule, none due before 0.8 of that TTL.
    assert sorted(nid for _due, nid in fleet._due) == sorted(fleet.granted)
    assert (min(due for due, _nid in fleet._due)
            > time.monotonic() + 0.8 * 1_999 / 50.0 - 1.0)
    fleet.stop()


def test_a_lapsed_ttl_reads_as_jobs_placed_twice_and_says_so(
        capsys, monkeypatch):
    # What a run reads where the harness's process is held up past a TTL:
    # from the window's opening on no beat goes out, the rehearsal
    # fleet's TTLs of 10-20 s lapse, and the server places the tasks of
    # the nodes it marked down again. failed stays 0, since every job was
    # placed whole; the jobs that had tasks on those nodes read more
    # placements than were asked and than the store holds.
    from benchmark.generators.fleet import Fleet
    from benchmark.generators.traffic import Player

    real_play, real_beat = Player.play, Fleet._beat
    held = threading.Event()

    def play(self, seconds, tag, *args, **kwargs):
        if tag != "warm":
            held.set()
        return real_play(self, seconds, tag, *args, **kwargs)

    monkeypatch.setattr(Player, "play", play)
    monkeypatch.setattr(
        Fleet, "_beat",
        lambda self, due: None if held.is_set() else real_beat(self, due))
    rc = run.main(["--workload", STEADY, "--seed", "2500000322",
                   "--seconds", "9", "--trace", "0"])
    out = capsys.readouterr()
    assert rc == 0, out.err[-2000:]
    result, report = json.loads(out.out.strip().splitlines()[-1]), report_of(out)
    assert report["nodes_down"] > 0
    assert f"fleet: {report['nodes_down']} nodes marked down" in out.err
    assert result["correct"] is False and result["failed"] == 0
    short = result["compared"]["jobs_short"]["value"]
    assert short > 0 and result["compared"]["store_mismatch"]["value"] > 0
    assert all(j["placed"] > j["count"] for j in report["jobs_not_whole"])


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
