"""The one traffic generator. A mix is a data file under
``benchmark/traffic/``; this module turns (mix, seed, seconds) into a
plan of rounds and plays it against the server's RPC front door.

A mix says how jobs arrive (``arrivals``), how large they are (``sizes``
and ``weights``), and whether a round repeats (``repeat``):

- ``{"process": "poisson", "rate_per_s": r}``: an open loop. The round
  holds round(r x seconds) jobs. Their gaps are the round's own
  exponential quantiles and their sizes the weights' exact proportions,
  both shuffled by the seed: every seed offers the same set of gaps and
  sizes in another order, so the seed changes the order of the work and
  never its amount. Each job is timed from when it was due.
- ``{"process": "at_once", "jobs": n}``: n jobs offered as fast as the
  front door takes them. With ``"repeat": "when_placed"`` the next round
  is offered the moment the previous one is fully placed (a closed
  loop) until ``--seconds`` have passed or the cell cannot hold the
  next round whole, and the window closes with the last commit of the
  last round offered (a plan of such a job commits thousands of
  placements at once, so a window cut at a fixed instant would count in
  steps of whole plans). With ``"never"`` there is one round, and the
  window closes with its last commit where all of it is placed before
  ``--seconds`` have passed, and after ``--seconds`` otherwise.

A drain window so ends when its work ends or when ``--seconds`` have
passed, whichever is first (``end`` in what ``play`` returns:
``cell_full``, ``drained``, ``deadline``; ``rounds`` where a warm-up's
``rounds`` key ends it), and the rate is what was committed in it over
its length. What the cell can hold is reckoned from the configuration
alone (``reference.free_slots``) less every placement asked so far, the
warm-up's too (``Player.slots_left``): a round offered and not placed
still counts as failed, every one of its tasks, and a round is never
offered to a cell with no room for it. An open loop plays its schedule
to the end.

``"preload": true`` (with ``at_once``) registers the round before the
window opens, while ``hold`` keeps the server's workers from taking
evaluations, and opens the window by releasing them: the backlog is
there when the drain starts, and the front door's work of taking 1,000
registrations does not share the window with the drain.

Before an open loop's window the harness also plays each size of the mix
once alone (``Player.play_alone``), so that the lone program of every
size is compiled whichever stacks the warm-up happened to form.

``node_refresh`` re-registers ``count`` nodes, drawn from the seed,
every ``every_s`` seconds. ``warmup`` holds the keys that differ while
the same generator plays the mix before the window (``seconds``,
``rounds``, ``arrivals``).
"""

from __future__ import annotations

import math
import random
import threading
import time
from typing import Callable, Dict, List, Optional

from nomad_tpu.api.codec import to_dict

from benchmark.generators import jobs as jobs_mod
from benchmark.generators.fleet import build_node, node_spec


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
    offset. The same (mix, seed, seconds, round_no) gives the same plan."""
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
    sizes: List[int] = []
    for size, k in zip(mix["sizes"], apportion(mix["weights"], n)):
        sizes.extend([int(size)] * k)
    rng.shuffle(sizes)
    return [{"offset": off, "id": f"{tag}-r{round_no:03d}-{k:05d}",
             "size": size}
            for k, (off, size) in enumerate(zip(offsets, sizes))]


def _sizes(ready: List) -> int:
    """The placements a built round asks for."""
    return sum(item["size"] for item, _rec, _payload in ready)


class Player:
    """Plays a mix against a server through the fleet's RPC pools and
    records, per job: when it was due, when its register was sent, the
    eval id the server answered with, or the error."""

    def __init__(self, fleet, mix: Dict, config: Dict, seed: int,
                 placed_total: Callable[[], int], slots_left: int,
                 hold: Callable[[bool], None] = lambda held: None):
        self.hold = hold
        self.slots_left = int(slots_left)   # less every round offered
        self.fleet = fleet
        self.mix = mix
        self.config = config
        self.seed = int(seed)
        self.placed_total = placed_total
        self.jobs: Dict[str, Dict] = {}   # job id -> record

    def _register(self, rec: Dict, payload: Dict) -> None:
        rec["sent"] = time.time()
        try:
            out = self.fleet.call("Job.Register", payload)
            rec["eval_id"] = out["eval_id"]
        except Exception as e:  # a refused or failed register is a failed job
            rec["error"] = f"{type(e).__name__}: {e}"
        rec["acked"] = time.time()

    def _refresh_loop(self, stop: threading.Event) -> None:
        spec = self.mix.get("node_refresh")
        if not spec:
            return
        rng = random.Random(self.seed ^ 0x6E6F6465)
        shape = self.config["nodes"]
        n = int(shape["count"])
        while not stop.wait(float(spec["every_s"])):
            pick = rng.sample(range(n), min(int(spec["count"]), n))
            nodes = [build_node(shape, node_spec(shape, i)) for i in pick]
            try:
                self.fleet.register(nodes)
            except Exception:
                pass  # the next tick re-registers others

    def _send(self, opened: float, share: List) -> None:
        """One sender's share of a round, in order: sleep until each job
        is due, then register it."""
        for item, rec, payload in share:
            if item["offset"]:
                due = opened + item["offset"]
                wait = due - time.time()
                if wait > 0:
                    time.sleep(wait)
            else:
                due = time.time()
            rec["due"] = due
            self._register(rec, payload)

    def play(self, seconds: float, tag: str, overrides: Optional[Dict] = None,
             target_base: int = 0,
             on_open: Callable[[], None] = lambda: None) -> Dict:
        """Play the mix for at most ``seconds``. Returns {"opened",
        "closed", "rounds", "asked", "end"}; per-job records accumulate
        in ``self.jobs``. ``target_base`` is the watcher's placed total
        before this play; ``on_open`` is called as the window opens."""
        mix = dict(self.mix)
        mix.update(overrides or {})
        seconds = float(mix.get("seconds", seconds))
        max_rounds = mix.get("rounds")
        closed_loop = mix.get("repeat", "never") == "when_placed"
        n_senders = max(1, int(mix.get("senders", 4)))
        task = self.config["task"]
        stop = threading.Event()
        refresher = threading.Thread(
            target=self._refresh_loop, args=(stop,), daemon=True,
            name="bench-refresh")
        # The first round's payloads are built before the window opens.
        round_no = 0
        ready = self._build(
            round_plan(mix, self.seed, seconds, round_no, tag), task, mix)
        first = _sizes(ready)
        if first > self.slots_left:
            raise ValueError(
                f"{tag}: the first round asks for {first} placements and "
                f"the cell has {self.slots_left} slots left")
        asked = target_base

        def send(opened):
            # Each sender owns every n-th job of the round, so a job due
            # costs one wake-up and a slow register delays only its own
            # sender's next jobs (which then count as late).
            threads = [threading.Thread(
                target=self._send, args=(opened, ready[i::n_senders]),
                daemon=True, name=f"bench-send-{i}")
                for i in range(n_senders)]
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
            senders = [] if preloaded and rounds == 1 else send(opened)
            offered = _sizes(ready)
            asked += offered
            self.slots_left -= offered
            if closed_loop:
                # The next round goes out when this one is placed; it is
                # built while this one is being placed.
                round_no += 1
                ready = self._build(
                    round_plan(mix, self.seed, seconds, round_no, tag),
                    task, mix)
            for t in senders:
                t.join()
            if not closed_loop:
                break
            # The round in flight when the time is up is played out: the
            # window of a closed loop ends on a round's last commit, so
            # that the rate is not cut to whole plans of 12,500.
            self._placed_by(asked, deadline + ROUND_GRACE_S)
            if time.time() >= deadline:
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
                # the window; the caller closes it at the last commit.
                if self._placed_by(asked, deadline):
                    end = "drained"
            else:
                time.sleep(max(0.0, deadline - time.time()))
        closed = time.time()
        stop.set()
        refresher.join(timeout=5.0)
        return {"opened": opened, "closed": closed, "rounds": rounds,
                "asked": asked - target_base, "end": end}

    def play_alone(self, tag: str, target_base: int, timeout: float) -> int:
        """Each size of the mix once, one job at a time, the next when
        the last is placed; the placements asked. An open loop's jobs
        meet in a coalesced solve or do not by chance, and a size the
        warm-up only solved beside another would compile its lone
        program inside the window."""
        asked = target_base
        for k, size in enumerate(sorted(set(self.mix["sizes"]))):
            plan = [{"offset": 0.0, "id": f"{tag}-{k:05d}", "size": int(size)}]
            self._send(0.0, self._build(plan, self.config["task"], self.mix))
            asked += int(size)
            self.slots_left -= int(size)
            if not self._placed_by(asked, time.time() + timeout):
                break
        return asked - target_base

    def _placed_by(self, asked: int, limit: float) -> bool:
        """Wait until ``asked`` placements are committed or the clock
        passes ``limit``; whether they are."""
        while self.placed_total() < asked and time.time() < limit:
            time.sleep(0.005)
        return self.placed_total() >= asked

    def _build(self, plan: List[Dict], task: Dict, mix: Dict):
        ready = []
        for item in plan:
            spec = jobs_mod.job_spec(task, item["id"], mix["job_type"],
                                     item["size"])
            rec = {"spec": spec, "offset": item["offset"]}
            self.jobs[spec["id"]] = rec
            ready.append(
                (item, rec, {"job": to_dict(jobs_mod.build_job(spec))}))
        return ready
