#!/usr/bin/env python3
"""One run of one cell of BENCHMARK.json:

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process. It acquires the chip through the program's own
``acquire_device`` (which also places the compile cache inside the
checkout), builds the configuration's server on its shipped defaults
plus what the configuration's file states, registers the fleet, warms up
by playing the cell's own traffic from a warm-up seed stream, opens the
window, plays the mix for ``--seconds`` or until its work ends (a cell
full, a backlog drained; a mix whose jobs end hands the warm-up's live
waves to the window), closes, waits for the answers that are due,
reads the peak of device memory, and holds the answers to the plain
reference (benchmark/reference.py). ``setup_s`` is process start to
window open.

The last line of stdout is the result and nothing else; the fuller
report is the line before it and a file under benchmark/out/. Without a
TPU it prints no result and exits 2, unless the configuration's file
says ``"rehearsal": true``.

Exit 3, and no result, means that the warm-up was left short: the
program did not place the cell's own traffic before the window opened,
so there is nothing to time. The harness waits for a job only while it
can still be placed (generators/traffic.py), so where the program ends
the evaluations it cannot place (``failed``, or ``complete`` with tasks
left out) or refuses the registrations, exit 3 comes as soon as the
last of them has ended, seconds after the warm-up was offered, with one
``short:`` line a job (placed / asked, how its evaluations ended). Where
it places nothing and ends nothing, exit 3 comes ``WARMUP_TIMEOUT_S``
after the warm-up's start: that one clock covers the warm-up's play,
the wait after it and the lone sizes. After a warm-up that finished, a
run can wait without progress for at most ``--seconds`` +
``ROUND_GRACE_S`` + ``--drain-timeout`` + ``QUIET_S`` + ``TRACE_JOIN_S``.

README.md says how to add a configuration, a mix, a metric or a reader
as new files.
"""

from __future__ import annotations

import time

T_START = time.time()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import logging  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
HERE = os.path.join(ROOT, "benchmark")
OUT = os.path.join(HERE, "out")

from benchmark import reference, work  # noqa: E402

TRACE_AFTER_S = 2.0     # the trace starts this long after the window opens
TRACE_SECONDS = 4.0     # and covers this much of it
WARMUP_TIMEOUT_S = 900.0  # from the warm-up's start to the last of its waits
DRAIN_TIMEOUT_S = 60.0    # --drain-timeout: after the window, for what is due
QUIET_S = 10.0            # then for the broker and the plan queue to empty
TRACE_JOIN_S = 120.0      # and for the tracer to have written its trace


def log(msg: str) -> None:
    print(f"bench[{time.time() - T_START:7.2f}s] {msg}", file=sys.stderr,
          flush=True)


def load_json(*parts: str):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


class Cell:
    """A cell resolved to its files, by the names in BENCHMARK.json."""

    def __init__(self, name: str):
        with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
            self.bench = json.load(f)
        listed = {w["name"]: w for w in self.bench["workloads"]}
        self.name = name
        if name in listed:
            self.config_name = listed[name]["config"]
            self.traffic_name = listed[name]["traffic"]
            self.chips = int(listed[name]["chips"])
            files = {c["name"]: c["file"] for c in self.bench["configs"]}
            with open(os.path.join(ROOT, files[self.config_name])) as f:
                self.config = json.load(f)
        else:
            # Not listed: only a rehearsal configuration may run so.
            self.config_name, _, self.traffic_name = name.partition(".")
            self.chips = 1
            self.config = load_json("configs", self.config_name + ".json")
            if not self.config.get("rehearsal"):
                raise SystemExit(f"{name}: not a cell of BENCHMARK.json")
        self.mix = load_json("traffic", self.traffic_name + ".json")
        # A rehearsal mix reports what the mix it stands for reports.
        stands_for = self.mix.get("metrics_of", self.traffic_name)
        self.metric_cells = {
            w["name"] for w in self.bench["workloads"]
            if w["traffic"] == stands_for} or {name}

    def _applies(self, metric: dict, moved_ok=None) -> bool:
        cells = metric.get("workloads")
        if cells is None:
            return moved_ok is None or moved_ok
        return bool(self.metric_cells & set(cells))

    def end_to_end(self):
        return [m for m in self.bench["end_to_end"] if self._applies(m)]

    def per_layer(self):
        mine = {m["name"] for m in self.end_to_end()}
        return [m for m in self.bench["per_layer"]
                if self._applies(m, m["moves"] in mine)]


class RunContext:
    """What the readers read."""

    def __init__(self):
        self.events = []          # the window's events
        self.counters = {}        # counters differenced over the window
        self.window = {}          # the harness's own numbers
        self.trace = None         # xplane.reduce() of the traced seconds
        self.trace_window_s = 0.0
        self.trace_widths = {}    # {width: dispatches} in the traced seconds
        self.stop_evals = []      # evals of the stops committed in the window
        self.device_kind = ""
        self.node_bucket = 0
        self.cache = {}


def wait_until(pred, timeout: float, poll: float = 0.02) -> bool:
    deadline = time.time() + timeout
    while time.time() < deadline:
        if pred():
            return True
        time.sleep(poll)
    return pred()


def quiet(srv) -> bool:
    stats = srv.eval_broker.snapshot_stats()
    return stats.total_unacked == 0 and srv.plan_queue.depth() == 0


def wait_quiet(srv, timeout: float, ready_too: bool = True) -> bool:
    """Nothing in flight (and nothing ready) on three looks in a row."""
    streak = [0]

    def look():
        ok = quiet(srv) and not (
            ready_too and srv.eval_broker.snapshot_stats().total_ready)
        streak[0] = streak[0] + 1 if ok else 0
        return streak[0] >= 3

    return wait_until(look, timeout, poll=0.05)


def warm_widths() -> None:
    """The warm-up played the mix, so every shape the mix solves is
    compiled at the widths that happened to form. The other coalesced
    widths of those same shapes are compiled here through the program's
    own warm calls (what ``prewarm_shapes`` runs at a server's start), so
    that no width compiles in the window whichever stack forms there."""
    from nomad_tpu.ops.coalesce import (
        GLOBAL_SOLVER,
        warm_batch_shapes,
        warm_exact_batch_shapes,
    )
    from nomad_tpu.tpu.solver import SOLVER_PANEL

    panel = SOLVER_PANEL.snapshot()
    paths = dict(GLOBAL_SOLVER.paths)
    for nb in [b["bucket"] for b in panel["node_buckets"]]:
        if paths.get("pallas") or paths.get("jnp"):
            warm_batch_shapes(nb)
        counts = [b["bucket"] for b in panel["count_buckets"]]
        if paths.get("exact") and counts:
            warm_exact_batch_shapes(nb, counts=counts)


def eval_ends(events, job_id: str) -> list:
    """How the evaluations of one job ended, as the stream tells it."""
    return [(e.payload.get("status"), e.payload.get("status_description"))
            for e in events
            if e.topic == "Eval" and e.payload.get("job_id") == job_id
            and e.payload.get("status") in ("complete", "failed")]


def plans_per_eval(events) -> dict:
    """{n: evaluations with n plans applied}, from the event stream."""
    per: dict = {}
    for e in events:
        if e.topic == "Plan" and e.type == "PlanApplied":
            per[e.key] = per.get(e.key, 0) + 1
    out: dict = {}
    for n in per.values():
        out[n] = out.get(n, 0) + 1
    return dict(sorted(out.items()))


def program_answers(snap, committed, truncated,
                    stopped=frozenset()) -> reference.Answers:
    """The program's answers as the reference reads them: what the event
    stream committed, the running allocations read back from the state
    store, and the jobs whose stop the stream shows complete."""

    def rows(allocs):
        return [(a.id, a.job_id, a.node_id, a.resources.cpu,
                 a.resources.memory_mb)
                for a in allocs if a.desired_status == "run"]

    return reference.Answers(
        committed, lambda jid: rows(snap.allocs_by_job(jid)),
        lambda nid: rows(snap.allocs_by_node(nid)), truncated, stopped)


def start_tracer(ctx: RunContext, trace_dir: str, opened_evt, spec: dict):
    """Trace a few seconds of the window from a thread of its own, which
    waits for ``opened_evt``."""
    import jax

    from benchmark.readers import xplane

    state = {"t0": None, "t1": None, "marker_wall": None, "error": None}

    def widths():
        from nomad_tpu.tpu.solver import SOLVER_PANEL

        return {int(w): row["dispatches"] for w, row in
                SOLVER_PANEL.snapshot()["batch_widths"].items()}

    def body():
        opened_evt.wait()
        time.sleep(float(spec.get("after_s", TRACE_AFTER_S)))
        try:
            shutil.rmtree(trace_dir, ignore_errors=True)
            os.makedirs(trace_dir, exist_ok=True)
            # Device operations and the marker, not every Python call:
            # the Python tracer would slow the host it is there to watch.
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=options)
            state["t0"] = time.time()
            w0 = widths()
            state["marker_wall"] = time.time()
            with jax.profiler.TraceAnnotation(xplane.MARKER):
                time.sleep(0.001)
            time.sleep(float(spec.get("seconds", TRACE_SECONDS)))
            w1 = widths()
            state["t1"] = time.time()
            jax.profiler.stop_trace()
            ctx.trace_widths = {w: n - w0.get(w, 0) for w, n in w1.items()}
            ctx.trace_window_s = state["t1"] - state["t0"]
        except Exception as e:  # the run goes on; the line will lack a trace
            state["error"] = f"{type(e).__name__}: {e}"

    thread = threading.Thread(target=body, daemon=True, name="bench-trace")
    thread.start()
    return thread, state


def breakdown(trace: dict, state: dict, ctx: RunContext) -> dict:
    """The device operations that took most time, and the idle gaps by
    what span of the program the host was in (nomad_tpu.trace, aligned to
    the profiler's clock by the marker taken when the trace started)."""
    from nomad_tpu import trace as trace_mod

    ops = sorted(((name, sum(d)) for name, d in trace["programs"].items()),
                 key=lambda kv: -kv[1])[:10]
    spans = []
    if trace["marker_ns"] is not None and state["marker_wall"] is not None:
        offset = trace["marker_ns"] / 1e9 - state["marker_wall"]
        tracer = trace_mod.get_tracer()
        evals = {e.key for e in ctx.events
                 if e.topic == "Eval" and e.type == "EvalUpdated"}
        for ev in evals:
            for s in tracer.get_trace(ev) or ():
                if s.get("end") is not None and s["end"] >= state["t0"] \
                        and s["start"] <= state["t1"]:
                    spans.append((s["start"] + offset, s["end"] + offset,
                                  s["name"]))
    spans.sort()
    by_span: dict = {}
    for g0, g1 in trace["gaps"]:
        mid = (g0 + g1) / 2e9
        name = "no_span"
        for s0, s1, sname in spans:
            if s0 > mid:
                break
            if s1 >= mid:
                name = sname  # the latest to start: the innermost
        by_span[name] = by_span.get(name, 0.0) + (g1 - g0) / 1e9
    gaps = sorted(by_span.items(), key=lambda kv: -kv[1])[:10]
    return {"device_ops": [[n, s] for n, s in ops],
            "idle_gaps": [[n, s] for n, s in gaps]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", default="",
                    help="after the run, also judge the reference put in "
                         "the program's place with these guarantees broken "
                         "(comma list or 'all'); printed, never the result")
    ap.add_argument("--drain-timeout", type=float, default=DRAIN_TIMEOUT_S)
    ap.add_argument("--rate-per-s", type=float, default=0.0,
                    help="offer a Poisson mix at this rate in place of its "
                         "file's (for the sweep that finds the knee)")
    ap.add_argument("--keep-rows", type=int, default=0,
                    help="keep this many rows of the trace under out/")
    args = ap.parse_args(argv)
    logging.basicConfig(level=logging.WARNING, stream=sys.stderr)

    cell = Cell(args.workload)
    config, mix = cell.config, cell.mix
    if args.rate_per_s:
        mix["arrivals"] = dict(mix["arrivals"], rate_per_s=args.rate_per_s)

    # Everything of the program this needs, before any output: in a
    # directory that holds the benchmark alone the import fails.
    from nomad_tpu import trace as trace_mod
    from nomad_tpu.ops.coalesce import quiesce_all
    from nomad_tpu.scheduler import acquire_device
    from nomad_tpu.server.cluster import (
        ClusterConfig,
        ClusterServer,
        wait_for_leader,
    )
    from nomad_tpu.server.server import ServerConfig

    from benchmark.generators.fleet import (
        Fleet,
        build_node,
        node_count,
        node_spec,
    )
    from benchmark.generators.traffic import Player, rounds_of
    from benchmark.generators.watcher import (
        EventTail,
        event_placed,
        placements_by_job,
        quantile,
    )
    from benchmark.readers import counters

    acquired = acquire_device()
    device = {"platform": acquired["platform"],
              "kind": acquired["device_kind"], "count": acquired["count"]}
    if device["platform"] != "tpu" and not config.get("rehearsal"):
        log(f"no accelerator (JAX platform {device['platform']!r}); "
            "only a rehearsal configuration runs without one")
        return 2
    if device["count"] < cell.chips:
        log(f"{cell.name} needs {cell.chips} chips, JAX sees "
            f"{device['count']}")
        return 2
    log(f"{cell.name} seed {args.seed} on {device}, cache "
        f"{acquired['compile_cache']}")

    import jax

    ctx = RunContext()
    ctx.device_kind = device["kind"]
    ctx.node_bucket = work.node_bucket(node_count(config["nodes"]))
    if config.get("trace_buffer_size"):
        trace_mod.configure(max_traces=int(config["trace_buffer_size"]))

    server_kwargs = dict(config["server"])
    server_kwargs["seed"] = args.seed & 0x7FFFFFFF
    srv = ClusterServer(
        ServerConfig(**server_kwargs),
        ClusterConfig(bootstrap_expect=1, bind_port=0))
    fleet = None
    tail = None
    try:
        srv.start()
        wait_for_leader([srv])
        fleet = Fleet(srv.rpc_addr)
        shape = config["nodes"]
        nodes = [node_spec(shape, i) for i in range(node_count(shape))]
        fleet.start_heartbeats()
        fleet.register([build_node(shape, nd) for nd in nodes])
        first_ttls = fleet.ttl_range()
        # The fleet's first nodes were granted TTLs of seconds; renewed
        # once now, none falls due inside the run (Fleet.renew_all).
        fleet.renew_all()
        log(f"{len(nodes)} nodes registered; TTLs granted "
            "{:.0f}-{:.0f} s, after one renewal {:.0f}-{:.0f} s".format(
                *first_ttls, *fleet.ttl_range()))

        def hold(held: bool) -> None:
            for w in srv.workers:
                w.set_pause(held)

        tail = EventTail(srv.fsm.events).start()
        # What the cell holds of the mix, from the configuration and the
        # mix alone: what first fit places of its rounds before it leaves
        # a task out, times the mix's fill limit. Each player takes from
        # it what it offers.
        fill_limit = float(mix.get("fill_limit", 1.0))
        rounds_fit, tasks_fit = reference.rounds_that_fit(
            nodes, rounds_of(mix, config, args.seed, args.seconds))
        slots = int(fill_limit * tasks_fit + 1e-9)
        log(f"first fit places {rounds_fit} rounds whole, {tasks_fit} "
            f"tasks; fill limit {fill_limit}: {slots} slots")
        # One clock for the whole warm-up: its play, the wait after it
        # and the lone sizes.
        warm_limit = time.time() + WARMUP_TIMEOUT_S

        def warm_left(most: float) -> float:
            return max(0.0, min(most, warm_limit - time.time()))

        warm = Player(fleet, mix, config, args.seed ^ 0x5EED5EED,
                      tail.placed_total, slots, hold,
                      eval_done=tail.eval_done)
        played = warm.play(args.seconds, "warm", mix.get("warmup"),
                           limit=warm_limit)
        warmed = played["asked"]
        whole = (warm.settle_placed(warmed, warm_limit)
                 and played["end"] != "round_short")
        if whole and mix["arrivals"]["process"] == "poisson":
            wait_quiet(srv, warm_left(60.0))
            warmed += warm.play_alone("lone", tail.placed_total(), warm_limit)
            whole = tail.placed_total() >= warmed
        if not whole:
            log(f"warm-up placed {tail.placed_total()}/{warmed} "
                f"({played['end']})")
            for jid, res in placements_by_job(tail.events, warm.jobs).items():
                spec = warm.jobs[jid]["spec"]
                if "due" in warm.jobs[jid] and res["placed"] < spec["count"]:
                    log(f"  short: {res['placed']}/{spec['count']} of {spec}: "
                        f"{eval_ends(tail.events, jid)}")
            for jid, rec in warm.stops.items():
                if rec.get("status") != "complete":
                    log(f"  stop short: {jid}: {rec.get('status')} "
                        f"{rec.get('error', '')}")
            return 3
        wait_quiet(srv, warm_left(60.0))
        warm_widths()
        wait_quiet(srv, warm_left(60.0))
        log(f"warm: {len(warm.jobs)} jobs, {warmed} placements")
        gc.collect()

        # -- the window ---------------------------------------------------
        player = Player(fleet, mix, config, args.seed, tail.placed_total,
                        warm.slots_left, hold, live=warm.live,
                        eval_done=tail.eval_done)
        n_warm_events = len(tail.events)
        base = tail.placed_total()
        c0 = counters.snapshot(srv)
        tracer_thread, trace_state, opened_evt = None, None, threading.Event()
        trace_dir = os.path.join(OUT, "trace", f"{cell.name}-s{args.seed}")
        if args.trace:
            tracer_thread, trace_state = start_tracer(
                ctx, trace_dir, opened_evt, mix.get("trace", {}))
        ready = {}
        mid = threading.Timer(args.seconds / 2.0, lambda: ready.update(
            mid=srv.eval_broker.snapshot_stats().total_ready))
        mid.daemon = True

        def on_open() -> None:
            opened_evt.set()
            mid.start()

        played = player.play(args.seconds, f"s{args.seed}",
                             target_base=base, on_open=on_open)
        mid.cancel()
        opened, closed = played["opened"], played["closed"]
        if (mix.get("repeat") == "when_placed"
                or played["end"] in ("drained", "round_short")):
            # A closed loop's window ends with its last round's last commit,
            # and so does a single round placed whole, or whose every
            # evaluation has ended, before the deadline.
            # A round with stops ends with the later of its last commit
            # and its last stop's evaluation.
            commits = [e.time for e in tail.events[n_warm_events:]
                       if event_placed(e)]
            commits += [rec["done_at"] for rec in player.stops.values()
                        if rec.get("status") == "complete"]
            closed = max(commits) if commits else closed
        ready_end = srv.eval_broker.snapshot_stats().total_ready
        ready_mid = ready.get("mid")
        c1 = counters.snapshot(srv)
        setup_s = opened - T_START
        log(f"window {closed - opened:.3f}s closed ({played['end']}, "
            f"{played['rounds']} rounds, {player.slots_left} slots left); "
            f"set-up {setup_s:.2f}s")

        # -- wait for what is due -------------------------------------------
        if mix.get("at_close", "finish") == "pause":
            # The backlog outlives the window: the workers finish what
            # they hold and take nothing more.
            hold(True)
            drained = wait_quiet(srv, args.drain_timeout, ready_too=False)
        else:
            drained = player.settle_placed(
                base + played["asked"], time.time() + args.drain_timeout)
            drained = player.settle_stops(
                closed + args.drain_timeout) and drained
            wait_quiet(srv, QUIET_S)
        drain_s = time.time() - closed
        if tracer_thread is not None:
            tracer_thread.join(timeout=TRACE_JOIN_S)
        tail.stop()
        peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in jax.devices())
        device["memory_peak_bytes"] = int(peak)

        # -- from events to answers -----------------------------------------
        events = tail.events[n_warm_events:]
        ctx.events = events
        by_job = placements_by_job(events, player.jobs)
        paused = mix.get("at_close", "finish") == "pause"
        if paused:
            # Due are the jobs the workers took up before they were held.
            done_evals = {e.payload.get("job_id") for e in events
                          if e.topic == "Eval" and e.type == "EvalUpdated"
                          and e.payload.get("status") in ("complete", "failed")}
            due_ids = [jid for jid, r in by_job.items()
                       if r["placed"] or jid in done_evals]
        else:
            due_ids = [jid for jid, rec in player.jobs.items()
                       if "due" in rec]
        due_ids.sort(key=lambda jid: (player.jobs[jid].get("due", 0), jid))
        due_jobs = [player.jobs[jid]["spec"] for jid in due_ids]
        committed = {jid: by_job[jid]["placed"] for jid in due_ids}
        in_window = sum(event_placed(e) for e in events
                        if opened <= e.time <= closed)
        window_s = closed - opened
        # The stops: asked by the window's player, committed where the
        # evaluation the RPC answered with shows complete.
        stops_asked = sum(rec["size"] for rec in player.stops.values())
        stops_done = [rec for rec in player.stops.values()
                      if rec.get("status") == "complete"]
        stops_in_window = [rec for rec in stops_done
                           if opened <= rec["done_at"] <= closed]
        window_stops = sum(rec["size"] for rec in stops_in_window)
        ctx.stop_evals = [rec["eval_id"] for rec in stops_in_window]

        now = time.time()
        lat_ms, late_ms, unplaced = [], [], 0
        for jid in due_ids:
            rec, res = player.jobs[jid], by_job[jid]
            late_ms.append((rec.get("sent", now) - rec["due"]) * 1000.0)
            if res["done_at"] is None:
                unplaced += 1
            lat_ms.append(((res["done_at"] or now) - rec["due"]) * 1000.0)
        lat_ms.sort()
        late_ms.sort()
        asked = sum(j["count"] for j in due_jobs)
        placed_due = sum(min(committed[j["id"]], j["count"])
                         for j in due_jobs)
        if mix["arrivals"]["process"] == "poisson":
            # An open loop's unit of work is the job a user submits.
            attempted, failed = len(due_jobs), unplaced
        else:
            attempted, failed = asked, asked - placed_due
        attempted += stops_asked
        failed += stops_asked - sum(rec["size"] for rec in stops_done)

        values = {"setup_s": setup_s}
        if lat_ms:
            values["placed_p50_ms"] = quantile(lat_ms, 0.50)
            values["placed_p95_ms"] = quantile(lat_ms, 0.95)
        if in_window:
            values["placements_per_s"] = in_window / window_s
        ctx.window = {
            "generator_late_p95_ms": quantile(late_ms, 0.95) if late_ms else None,
            "placed_p95_ms": values.get("placed_p95_ms"),
            "placed_p50_ms": values.get("placed_p50_ms"),
            "placements_per_s": values.get("placements_per_s"),
            "broker_ready_at_close": ready_end,
            "broker_ready_at_middle": ready_mid,
        }
        if stops_in_window:
            stop_ms = sorted((rec["done_at"] - rec["sent"]) * 1000.0
                             for rec in stops_in_window)
            # A round's wait for stops alone: from all its placements
            # committed to the last of its stops' evaluations.
            waited = sum(max(0.0, r["stopped"] - r["placed"])
                         for r in player.round_log if "stopped" in r)
            ctx.window.update({
                "stops_per_s": window_stops / window_s,
                "stop_job_p50_ms": quantile(stop_ms, 0.50),
                "round_stop_wait_pct": 100.0 * waited / window_s,
            })
        ctx.counters = counters.delta(c1, c0)
        ctx.counters["window.placements"] = in_window
        ctx.counters["window.evals"] = len(due_ids)
        ctx.counters["window.stops"] = window_stops

        # -- the comparison with the plain reference -------------------------
        t_cmp = time.time()
        snap = srv.state_store.snapshot()
        prior = [rec["spec"] for rec in warm.jobs.values() if "due" in rec]
        offered = due_jobs
        if "stop" in mix:
            # In the order offered, each round's stops after its jobs.
            prior, offered = warm.offered, player.offered
        stopped = {jid for p in (warm, player) for jid, rec in p.stops.items()
                   if rec.get("status") == "complete"}
        numbers = reference.compare(
            nodes, offered,
            program_answers(snap, committed, tail.truncated, stopped),
            args.seed, prior=prior)
        correct = reference.verdict(numbers) and bool(due_jobs)
        compare_s = time.time() - t_cmp
        controls = {}
        # A mix that stops nothing has no stop to break.
        wanted = ([g for g in reference.GUARANTEES
                   if g != "stop" or "stop" in mix]
                  if args.control == "all" else
                  [c for c in args.control.split(",") if c])
        for broken in wanted:
            got = reference.compare(
                nodes, offered,
                reference.control(nodes, prior + offered, broken),
                args.seed, prior=prior)
            controls[broken] = {"correct": reference.verdict(got),
                                "numbers": got}
            log(f"control {broken}: correct={controls[broken]['correct']} "
                f"{got}")

        # -- per-layer metrics (the traced run) ------------------------------
        metrics, extra = {}, {}
        if args.trace:
            from benchmark.readers import xplane

            rows = xplane.load(trace_dir)
            ctx.trace = xplane.reduce(rows)
            if args.keep_rows:
                os.makedirs(OUT, exist_ok=True)
                with open(os.path.join(
                        OUT, f"rows-{cell.name}.json"), "w") as f:
                    json.dump({"outline": xplane.outline(trace_dir),
                               "rows": rows[:args.keep_rows]}, f)
            shutil.rmtree(trace_dir, ignore_errors=True)
            if ctx.trace is None and not config.get("rehearsal"):
                log(f"the trace holds no device operation "
                    f"({trace_state['error']}); no result")
                return 4
            if ctx.trace is not None:
                device["busy_s"] = ctx.trace["busy_s"]
                device["window_s"] = ctx.trace_window_s
                extra["breakdown"] = breakdown(ctx.trace, trace_state, ctx)
            for m in cell.per_layer():
                spec = load_json("metrics", m["name"] + ".json")
                reader = importlib.import_module(
                    "benchmark.readers." + spec["source"]["reader"])
                value = reader.read(spec["source"], ctx)
                if value is not None:
                    metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        else:
            for m in cell.end_to_end():
                if values.get(m["name"]) is not None:
                    metrics[m["name"]] = {"value": values[m["name"]],
                                          "unit": m["unit"]}

        compared = {k: {"value": numbers[k], "limit": reference.LIMITS[k]}
                    for k in reference.LIMITS}
        report = {
            "workload": cell.name, "seed": args.seed, "trace": args.trace,
            "seconds": window_s, "setup_s": setup_s, "drain_s": drain_s,
            "drained": drained, "compare_s": compare_s,
            "jobs_due": len(due_jobs), "jobs_offered": len(player.jobs),
            # The first jobs due that were not placed whole: what to
            # look at where ``failed`` or ``jobs_short`` is not 0.
            "jobs_not_whole": [dict(j, placed=committed[j["id"]],
                                    evals=eval_ends(events, j["id"]))
                               for j in due_jobs
                               if committed[j["id"]] != j["count"]][:20],
            "rounds": played["rounds"], "window_end": played["end"],
            "slots_left": player.slots_left, "rounds_fit": rounds_fit,
            "fill_limit": fill_limit, "asked": asked,
            "placed_in_window": in_window, "values": values,
            "stops": window_stops, "stops_asked": stops_asked,
            "live_waves": len(player.live),
            "round_log": [[round(r.get(k, closed) - opened, 4)
                           for k in ("offered", "placed", "stopped")]
                          for r in player.round_log],
            # {plans applied for one evaluation: evaluations}: one, and one
            # more for each plan the pipeline refused in part.
            "plans_per_eval": plans_per_eval(events),
            "window": ctx.window, "counters": ctx.counters,
            "controls": controls, "compared": compared, "device": device,
            "commits": [[round(e.time - opened, 4), event_placed(e)]
                        for e in events if event_placed(e)][:2000],
            "trace_error": trace_state["error"] if trace_state else None,
            "heartbeat_errors": fleet.beat_errors,
            # A node the server marked down had its TTL lapse: the fleet
            # renews every one, so the harness's process was held up; the
            # server then places that node's tasks again, and jobs_short
            # and store_mismatch count the jobs that had tasks on it.
            "nodes_down": sum(
                1 for e in tail.events
                if e.topic == "Node" and e.type == "NodeHeartbeatExpired"),
        }
        result = {"correct": correct, "attempted": attempted,
                  "failed": failed, "metrics": metrics, "device": device}
        result.update(extra)
        result["compared"] = compared
    finally:
        if tail is not None:
            tail.stop()
        if fleet is not None:
            fleet.stop()
        srv.shutdown()
        quiesce_all(30.0)

    try:
        os.makedirs(OUT, exist_ok=True)
        with open(os.path.join(
                OUT, f"{cell.name}-s{args.seed}-t{args.trace}.json"), "w") as f:
            json.dump(report, f, indent=1, sort_keys=True, default=str)
    except OSError:
        pass
    print(json.dumps(report, default=str), flush=True)
    print(f"fleet: {report['nodes_down']} nodes marked down in the run, "
          f"{fleet.beat_errors} heartbeat errors", file=sys.stderr)
    for k, v in compared.items():
        print(f"compared {k}: {v['value']} (limit {v['limit']})",
              file=sys.stderr)
    print(f"correct: {correct}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
