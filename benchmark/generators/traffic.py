"""The one traffic generator. A mix is a data file under
``benchmark/traffic/``; this module turns (mix, seed, seconds) into a
plan of rounds and plays it against the server's RPC front door.

A mix says how jobs arrive (``arrivals``), what they are (``sizes`` and
``weights``, or ``templates``), and whether a round repeats (``repeat``):

- ``{"process": "poisson", "rate_per_s": r}``: an open loop. The round
  holds round(r x seconds) jobs. Their gaps are the round's own
  exponential quantiles and their sizes the weights' exact proportions,
  both shuffled by the seed: every seed offers the same set of gaps and
  sizes in another order, so the seed changes the order of the work and
  never its amount. Each job is timed from when it was due.
- ``{"process": "at_once", "jobs": n}``: n jobs offered as fast as the
  front door takes them. With ``"repeat": "when_placed"`` the next round
  is offered the moment the previous one is fully placed (a closed
  loop) until ``--seconds`` have passed, the cell cannot hold the next
  round whole, or a round is left short, and the window closes with the
  last commit of the last round offered (a plan of such a job commits
  thousands of placements at once, so a window cut at a fixed instant
  would count in steps of whole plans). With ``"never"`` there is one
  round, and the window closes with its last commit where all of it is
  placed, or every evaluation of it has ended, before ``--seconds`` have
  passed, and after ``--seconds`` otherwise.

The generator waits for a job only while that job can still be placed.
A job is over when its ``Job.Register`` was refused, or when the
evaluation the RPC answered with shows ``complete`` or ``failed`` in
the event stream (``eval_done``). A round ends when its placements are
committed or every one of its jobs is over; a job whose evaluation
never shows is waited for until the grace (``ROUND_GRACE_S`` past
``--seconds``) runs out. A closed loop whose round ends short before
``--seconds`` have passed ends there (``round_short``): a program that
has just left jobs unplaced is offered no further round, and every
task asked and not placed counts as failed.

What the jobs are. ``sizes`` and ``weights``: jobs of one task group of
the configuration's ``task`` (its cpu and memory), of these sizes in the
weights' exact proportions, of type ``job_type`` at priority 50.
``templates`` in their place: each ``{copies, type, priority,
constraints, groups: [{count, shape}]}`` is a job of one task group for
each of ``groups``, in order, ``shape`` a name of the configuration's
``task_shapes`` (name -> ``cpu``, ``memory_mb``); ``constraints`` are
the job's own, beside those all jobs share (the configuration's
``task``), and ``type`` and ``priority`` default to ``job_type`` and 50.
A round is every template ``copies`` times (where ``arrivals`` gives
another number of jobs: in the copies' exact proportions), shuffled by
the seed. ``sizes`` / ``weights`` are the one-group templates of the
configuration's ``task``: the same code builds both.

A drain window so ends when its work ends or when ``--seconds`` have
passed, whichever is first (``end`` in what ``play`` returns:
``cell_full``, ``drained``, ``deadline``, ``round_short``; ``rounds``
where a warm-up's ``rounds`` key ends it), and the rate is what was
committed in it over its length. What the cell can hold is reckoned
from the configuration and the mix alone
(``reference.rounds_that_fit`` over ``rounds_of``:
the mix's rounds replayed by first fit on the empty cell until the
first task is left out; for tasks of one shape that is the cell's
slots), times the mix's ``fill_limit`` (1 where it gives none), less
every placement asked so far, the warm-up's too
(``Player.slots_left``): a round offered and not placed still counts as
failed, every one of its tasks, and a round is never offered to a cell
with no room for it. An open loop plays its schedule to the end.

``fill_limit`` is under 1 where the cell's machines or the mix's tasks
differ in shape: two sound packers strand different capacity near the
top of such a cell, so the plain reference can hold the program to first
fit's per-job totals only where both place every job whole. With R
rounds placed whole by first fit and rounds of equal size, a closed loop
then offers floor(``fill_limit`` x R) rounds, the warm-up's counted (to
the round: floor(``fill_limit`` x tasks placed) over a round's tasks),
and ends ``cell_full``.

``"preload": true`` (with ``at_once``) registers the round before the
window opens, while ``hold`` keeps the server's workers from taking
evaluations, and opens the window by releasing them: the backlog is
there when the drain starts, and the front door's work of taking 1,000
registrations does not share the window with the drain.

Before an open loop's window the harness also plays each size of the mix
once alone (``Player.play_alone``), so that the lone program of every
size is compiled whichever stacks the warm-up happened to form.

``"stop": {"after_rounds": L}`` (a closed loop only) makes the jobs
end. The jobs of each round offered join a queue of live waves, oldest
first (``Player.live``; the warm-up's player hands it to the window's
together with ``slots_left``). Once L waves are live, every round is the
mix's registrations and a ``Job.Deregister`` for each job of the oldest
live wave, dealt to the round's senders in that order, registrations
first (``deal``). Such a round ends when its placements are committed
and every one of its deregistrations' evaluations (the ``eval_id`` the
RPC answers with) shows ``complete`` in the event stream
(``eval_done``); an evaluation that shows ``failed``, or none by the
grace, leaves its job's tasks failed, for a stop as for a
registration, and the loop ends there (``round_short``, or
``deadline`` where the grace ran past it). A committed stop gives its job's
size back to ``slots_left``, so such a loop never ends ``cell_full``,
and its window closes when its last round ends: with the later of the
last commit and the last stop's ``complete``. The stops of a round are a
function of the rounds before it; ``round_plan`` does not know of them.
Per stop ``Player.stops`` records when it was sent, the evaluation, and
how it ended; per round ``Player.round_log`` records when it was
offered, placed and stopped; ``Player.offered`` is what the plain
reference replays: each round's job specs, then its stop entries
(``{"stop": job id, "round": n}``). A mix without ``stop`` takes none of
this.

``node_refresh`` re-registers ``count`` nodes, drawn from the seed,
every ``every_s`` seconds. ``warmup`` holds the keys that differ while
the same generator plays the mix before the window (``seconds``,
``rounds``, ``arrivals``).
"""

from __future__ import annotations

import itertools
import math
import random
import threading
import time
from typing import (
    Callable,
    Dict,
    Iterator,
    List,
    Optional,
    Sequence,
    Tuple,
)

from nomad_tpu.api.codec import to_dict

from benchmark.generators import jobs as jobs_mod
from benchmark.generators.fleet import build_node, node_count, node_spec


HOLD_SETTLE_S = 1.0
ROUND_GRACE_S = 120.0   # the most a closed loop's last round may outlast --seconds


def apportion(weights: List[float], n: int) -> List[int]:
    """n split by the weights' exact proportions (largest remainders)."""
    total = float(sum(weights))
    raw = [w * n / total for w in weights]
    out = [int(math.floor(r)) for r in raw]
    order = sorted(range(len(raw)), key=lambda i: (out[i] - raw[i], i))
    for i in order[:n - sum(out)]:
        out[i] += 1
    return out


def round_plan(mix: Dict, seed: int, seconds: float, round_no: int,
               tag: str) -> List[Dict]:
    """One round of the mix: [{"offset", "id", "size"}], ordered by
    offset, with ``"template"`` (its index) where the mix gives
    templates. The same (mix, seed, seconds, round_no) gives the same
    plan."""
    rng = random.Random((int(seed) * 1_000_003 + round_no) & (2**63 - 1))
    arrivals = mix["arrivals"]
    if arrivals["process"] == "poisson":
        rate = float(arrivals["rate_per_s"])
        n = max(1, int(round(rate * seconds)))
        gaps = [-math.log(1.0 - (i + 0.5) / n) / rate for i in range(n)]
        rng.shuffle(gaps)
        scale = seconds / (sum(gaps) + 1.0 / rate)
        offsets, t = [], 0.0
        for g in gaps:
            t += g * scale
            offsets.append(t)
    elif arrivals["process"] == "at_once":
        n = int(arrivals["jobs"])
        offsets = [0.0] * n
    else:
        raise ValueError(f"unknown arrival process {arrivals['process']!r}")
    ids = [f"{tag}-r{round_no:03d}-{k:05d}" for k in range(n)]
    if "templates" in mix:
        picks: List[int] = []
        copies = [int(t.get("copies", 1)) for t in mix["templates"]]
        for pick, k in enumerate(apportion(copies, n)):
            picks.extend([pick] * k)
        rng.shuffle(picks)
        return [{"offset": off, "id": jid, "template": pick,
                 "size": template_size(mix["templates"][pick])}
                for off, jid, pick in zip(offsets, ids, picks)]
    sizes: List[int] = []
    for size, k in zip(mix["sizes"], apportion(mix["weights"], n)):
        sizes.extend([int(size)] * k)
    rng.shuffle(sizes)
    return [{"offset": off, "id": jid, "size": size}
            for off, jid, size in zip(offsets, ids, sizes)]


def template_size(template: Dict) -> int:
    return sum(int(g["count"]) for g in template["groups"])


def item_spec(config: Dict, mix: Dict, item: Dict) -> Dict:
    """The job one item of a round's plan asks for, as plain data."""
    if "template" in item:
        return jobs_mod.template_spec(
            config, mix["templates"][item["template"]], item["id"],
            mix["job_type"])
    return jobs_mod.job_spec(config["task"], item["id"], mix["job_type"],
                             item["size"])


def rounds_of(mix: Dict, config: Dict, seed: int,
              seconds: float) -> Iterator[List[Dict]]:
    """The mix's rounds without end, each as its jobs in the order
    offered: what ``reference.rounds_that_fit`` replays on the empty
    cell. A function of mix, configuration and seed alone. Jobs of one
    size or template are one spec here, whatever their ids: the replay
    reads none."""
    specs: Dict[int, Dict] = {}
    for round_no in itertools.count():
        jobs = []
        for item in round_plan(mix, seed, seconds, round_no, "fit"):
            kind = item.get("template", item["size"])
            if kind not in specs:
                specs[kind] = item_spec(config, mix, item)
            jobs.append(specs[kind])
        yield jobs


def lone_plan(mix: Dict, tag: str) -> List[Dict]:
    """Each kind of job of the mix once (each size, or each template),
    all due at once."""
    if "templates" in mix:
        return [{"offset": 0.0, "id": f"{tag}-{k:05d}", "template": k,
                 "size": template_size(t)}
                for k, t in enumerate(mix["templates"])]
    return [{"offset": 0.0, "id": f"{tag}-{k:05d}", "size": int(size)}
            for k, size in enumerate(sorted(set(mix["sizes"])))]


def _sizes(ready: List) -> int:
    """The placements a built round asks for."""
    return sum(item["size"] for item, _rec, _payload in ready)


def deal(actions: List, n_senders: int) -> List[List]:
    """A round's actions dealt to its senders: each sender owns every
    n-th one, so a job due costs one wake-up and a slow call delays only
    its own sender's next ones (which then count as late)."""
    return [actions[i::n_senders] for i in range(n_senders)]


class Player:
    """Plays a mix against a server through the fleet's RPC pools and
    records, per job: when it was due, when its register was sent, the
    eval id the server answered with, or the error. For a mix with
    ``stop`` also per stop (``stops``) and per round (``round_log``).

    ``eval_done(eval id)`` is (status, stamp) once the event stream shows
    the evaluation ``complete`` or ``failed``, and None until then;
    ``live`` are the waves the player before this one left running."""

    def __init__(self, fleet, mix: Dict, config: Dict, seed: int,
                 placed_total: Callable[[], int], slots_left: int,
                 hold: Callable[[bool], None] = lambda held: None,
                 live: Optional[List[List[Tuple[str, int]]]] = None,
                 eval_done: Callable[[str], Optional[Tuple[str, float]]]
                 = lambda eval_id: None):
        self.hold = hold
        # Less every round offered, plus every stop committed.
        self.slots_left = int(slots_left)
        self.live = live if live is not None else []  # [[(job id, size)]]
        self.eval_done = eval_done
        self.stops: Dict[str, Dict] = {}  # job id -> record of its stop
        self.offered: List[Dict] = []     # specs and stop entries, in order
        self.round_log: List[Dict] = []   # offered, placed, stopped stamps
        self.fleet = fleet
        self.mix = mix
        self.config = config
        self.seed = int(seed)
        self.placed_total = placed_total
        self.jobs: Dict[str, Dict] = {}   # job id -> record

    def _call(self, method: str, rec: Dict, payload: Dict) -> None:
        """``Job.Register`` or ``Job.Deregister``, its stamps and the
        evaluation it was answered with noted in ``rec``."""
        rec["sent"] = time.time()
        try:
            out = self.fleet.call(method, payload)
            rec["eval_id"] = out["eval_id"]
        except Exception as e:  # refused or failed: a failed job, or stop
            rec["error"] = f"{type(e).__name__}: {e}"
        rec["acked"] = time.time()

    def _refresh_loop(self, stop: threading.Event) -> None:
        spec = self.mix.get("node_refresh")
        if not spec:
            return
        rng = random.Random(self.seed ^ 0x6E6F6465)
        shape = self.config["nodes"]
        n = node_count(shape)
        while not stop.wait(float(spec["every_s"])):
            pick = rng.sample(range(n), min(int(spec["count"]), n))
            nodes = [build_node(shape, node_spec(shape, i)) for i in pick]
            try:
                self.fleet.register(nodes)
            except Exception:
                pass  # the next tick re-registers others

    def _send(self, opened: float, share: List) -> None:
        """One sender's share of a round, in order: sleep until each job
        is due, then register it; a stop goes out as its turn comes."""
        for item, rec, payload in share:
            if "stop" in item:
                self._call("Job.Deregister", rec, payload)
                continue
            if item["offset"]:
                due = opened + item["offset"]
                wait = due - time.time()
                if wait > 0:
                    time.sleep(wait)
            else:
                due = time.time()
            rec["due"] = due
            self._call("Job.Register", rec, payload)

    def play(self, seconds: float, tag: str, overrides: Optional[Dict] = None,
             target_base: int = 0,
             on_open: Callable[[], None] = lambda: None,
             limit: float = math.inf) -> Dict:
        """Play the mix for at most ``seconds``. Returns {"opened",
        "closed", "rounds", "asked", "end"}; per-job records accumulate
        in ``self.jobs``. ``target_base`` is the watcher's placed total
        before this play; ``on_open`` is called as the window opens; no
        round is waited for past ``limit`` (the warm-up's one clock)."""
        mix = dict(self.mix)
        mix.update(overrides or {})
        seconds = float(mix.get("seconds", seconds))
        max_rounds = mix.get("rounds")
        closed_loop = mix.get("repeat", "never") == "when_placed"
        n_senders = max(1, int(mix.get("senders", 4)))
        if "stop" in mix and not closed_loop:
            raise ValueError(f"{tag}: only a closed loop's jobs can end")
        stop_after = (int(mix["stop"]["after_rounds"])
                      if "stop" in mix else None)
        stop = threading.Event()
        refresher = threading.Thread(
            target=self._refresh_loop, args=(stop,), daemon=True,
            name="bench-refresh")
        # The first round's payloads are built before the window opens.
        round_no = 0
        ready = self._build(
            round_plan(mix, self.seed, seconds, round_no, tag), mix)
        first = _sizes(ready)
        if first > self.slots_left:
            raise ValueError(
                f"{tag}: the first round asks for {first} placements and "
                f"the cell has {self.slots_left} slots left")
        asked = target_base

        def send(opened, stops=()):
            threads = [threading.Thread(
                target=self._send, args=(opened, share),
                daemon=True, name=f"bench-send-{i}")
                for i, share in enumerate(
                    deal(ready + list(stops), n_senders))]
            for t in threads:
                t.start()
            return threads

        preloaded = bool(mix.get("preload"))
        if preloaded:
            self.hold(True)
            # A worker already waiting in the broker's dequeue (it waits
            # half a second at a time) would still take what comes next.
            time.sleep(HOLD_SETTLE_S)
            for t in send(0.0):
                t.join()
        opened = time.time()
        on_open()
        if preloaded:
            for _item, rec, _p in ready:
                rec["due"] = opened
            self.hold(False)
        refresher.start()
        deadline = opened + seconds
        rounds, end = 0, "deadline"
        while True:
            rounds += 1
            stops = []
            if stop_after is not None:
                if len(self.live) >= stop_after:
                    stops = self._build_stops(self.live.pop(0))
                self.live.append(
                    [(item["id"], item["size"]) for item, _r, _p in ready])
                self.offered.extend(rec["spec"] for _i, rec, _p in ready)
                self.offered.extend(
                    {"stop": item["stop"], "round": rounds}
                    for item, _r, _p in stops)
                self.round_log.append({"offered": time.time()})
            senders = ([] if preloaded and rounds == 1
                       else send(opened, stops))
            sent = [rec for _i, rec, _p in ready]
            offered = _sizes(ready)
            asked += offered
            self.slots_left -= offered
            if closed_loop:
                # The next round goes out when this one is placed; it is
                # built while this one is being placed.
                round_no += 1
                ready = self._build(
                    round_plan(mix, self.seed, seconds, round_no, tag), mix)
            for t in senders:
                t.join()
            if not closed_loop:
                break
            # The round in flight when the time is up is played out: the
            # window of a closed loop ends on a round's last commit, so
            # that the rate is not cut to whole plans of 12,500.
            give_up = min(deadline + ROUND_GRACE_S, limit)
            whole = self._placed_by(asked, give_up, sent)
            if stop_after is not None:
                self.round_log[-1]["placed"] = time.time()
                whole = self._stopped_by([rec for _i, rec, _p in stops],
                                         give_up) and whole
                self.round_log[-1]["stopped"] = time.time()
            if time.time() >= deadline:
                break
            if not whole:
                # A program that has just left jobs unplaced, or a stop
                # failed, is offered no further round.
                end = "round_short"
                break
            if max_rounds is not None and rounds >= int(max_rounds):
                end = "rounds"
                break
            if _sizes(ready) > self.slots_left:
                end = "cell_full"
                break
        if not closed_loop and max_rounds is None:
            if mix["arrivals"]["process"] == "at_once":
                # One round: placed whole before the time is up, it ends
                # the window, and so does one whose every evaluation has
                # ended with jobs left short; the caller closes it at the
                # last commit.
                if self._placed_by(asked, min(deadline, limit), sent):
                    end = "drained"
                elif time.time() < deadline:
                    end = "round_short"
            else:
                time.sleep(max(0.0, deadline - time.time()))
        closed = time.time()
        stop.set()
        refresher.join(timeout=5.0)
        return {"opened": opened, "closed": closed, "rounds": rounds,
                "asked": asked - target_base, "end": end}

    def play_alone(self, tag: str, target_base: int, limit: float) -> int:
        """Each size (or template) of the mix once, one job at a time,
        the next when the last is placed; the placements asked. An open
        loop's jobs meet in a coalesced solve or do not by chance, and a
        size the warm-up only solved beside another would compile its
        lone program inside the window. The pass stops at the first size
        left short, and at ``limit``."""
        asked = target_base
        for item in lone_plan(self.mix, tag):
            ready = self._build([item], self.mix)
            self._send(0.0, ready)
            asked += item["size"]
            self.slots_left -= item["size"]
            if not self._placed_by(asked, limit, [ready[0][1]]):
                break
        return asked - target_base

    def _over(self, rec: Dict) -> bool:
        """Whether this job can no longer be placed: its ``Job.Register``
        was refused, or the evaluation it was answered with shows
        ``complete`` or ``failed`` in the event stream."""
        if "eval_id" in rec:
            return self.eval_done(rec["eval_id"]) is not None
        return "error" in rec

    def _placed_by(self, asked: int, limit: float,
                   recs: Sequence[Dict] = ()) -> bool:
        """Wait until ``asked`` placements are committed, or every job of
        ``recs`` is over, or the clock passes ``limit``, whichever is
        first; whether they are committed. The harness waits for a job
        only while it can still be placed; one whose evaluation never
        shows is waited for until ``limit``.

        The total is read once more after the last job is seen over, and
        that is enough: the tail takes the events in order and adds a
        batch's placements to its total before it notes the evaluations
        that ended in that batch (``watcher.EventTail._take``), and an
        evaluation's plans commit before it ends, so the total then holds
        all that those evaluations will ever commit."""
        seen = 0    # recs[:seen] are over; all have to be, so in order
        while self.placed_total() < asked and time.time() < limit:
            while seen < len(recs) and self._over(recs[seen]):
                seen += 1
            if recs and seen == len(recs):
                break
            time.sleep(0.005)
        return self.placed_total() >= asked

    def settle_placed(self, asked: int, limit: float) -> bool:
        """After a play: wait until ``limit`` for the jobs it offered,
        as a round waits for its own; whether ``asked`` placements are
        committed."""
        return self._placed_by(
            asked, limit, [r for r in self.jobs.values() if "due" in r])

    def _build_stops(self, wave: List[Tuple[str, int]]) -> List:
        """The stops of one live wave as a round's actions, shaped as
        ``_build`` shapes registrations."""
        out = []
        for job_id, size in wave:
            rec = self.stops[job_id] = {"size": int(size)}
            out.append(({"stop": job_id}, rec, {"job_id": job_id}))
        return out

    def _stopped_by(self, recs: List[Dict], limit: float) -> bool:
        """Wait until each of these stops has its evaluation ``complete``
        or ``failed`` in the event stream, or the clock passes ``limit``;
        whether all are complete. A committed stop gives its job's size
        back to ``slots_left``."""
        pending = [rec for rec in recs
                   if "eval_id" in rec and "status" not in rec]
        while pending and time.time() < limit:
            for rec in list(pending):
                done = self.eval_done(rec["eval_id"])
                if done is None:
                    continue
                rec["status"], rec["done_at"] = done
                pending.remove(rec)
                if rec["status"] == "complete":
                    self.slots_left += rec["size"]
            if pending:
                time.sleep(0.005)
        return all(rec.get("status") == "complete" for rec in recs)

    def settle_stops(self, limit: float) -> bool:
        """After the window: wait until ``limit`` for the stops that had
        not ended when their round gave up; whether every stop asked is
        complete."""
        return self._stopped_by(list(self.stops.values()), limit)

    def _build(self, plan: List[Dict], mix: Dict):
        ready = []
        for item in plan:
            spec = item_spec(self.config, mix, item)
            rec = {"spec": spec, "offset": item["offset"]}
            self.jobs[spec["id"]] = rec
            ready.append(
                (item, rec, {"job": to_dict(jobs_mod.build_job(spec))}))
        return ready
