"""The plain reference: what a cluster scheduler owes its users, worked
out from the configuration's and the traffic's plain data alone.

Imports nothing of the program and takes nothing the program made but its
answers. The same nodes and the same jobs give the same verdict whatever
placed them. It does four things:

- ``place``: a straightforward first-fit placement of the jobs on the
  nodes, job by job and, within a job, group by group in the job's
  order, each group's tasks taking that group's cpu and memory. Its
  per-job totals are the answers the program's are held to (how many
  tasks of each job can be placed at all). Between the jobs, in the
  order offered, may stand stop entries (``{"stop": job id, "round":
  n}``): a stop gives back what each group of the job took, to the
  nodes ``place`` put it on. A node too small for a task is not
  ineligible: it has no slot.
- ``rounds_that_fit``: what the empty cell holds of a mix, by the
  arithmetic ``place`` fills it with: the mix's rounds replayed by first
  fit until the first task is left out, as the whole rounds placed and
  the tasks placed in all. For tasks of one shape the tasks are the
  cell's slots, whatever the rounds. The generator offers a closed
  loop's next round only where it fits whole in the mix's
  ``fill_limit`` of those tasks.
- ``compare``: holds a set of answers to the configuration's guarantees:
  every job due has all its placements committed, the state store reads
  back what the event stream committed, every placement sits on a node
  the job's datacenters, driver and constraints admit and carries a
  unique id, a job's placements are, shape by shape, no more than its
  groups asked for, no node holds more than its own capacity, and a job
  whose stop was committed reads back no allocation desired ``run``.
  Every number is a count with the limit 0.
- ``control``: ``place`` with one guarantee broken, put in the program's
  place. ``compare`` has to fail it. With ``stop`` broken it
  acknowledges every stop and leaves every second row of a stopped job
  running.
"""

from __future__ import annotations

import random
from collections import Counter
from typing import (
    AbstractSet,
    Callable,
    Dict,
    Iterable,
    Iterator,
    List,
    Sequence,
    Tuple,
)

import numpy as np

# Each row of an answer: (alloc id, job id, node id, cpu, memory_mb).
Row = Tuple[str, str, str, int, int]

GUARANTEES = ("capacity", "eligibility", "commit", "stop")
LIMITS = {
    "jobs_short": 0, "store_mismatch": 0, "ineligible": 0,
    "wrong_resources": 0, "duplicate_ids": 0, "nodes_over_capacity": 0,
    "events_truncated": 0, "stopped_running": 0,
}
MAX_SAMPLED_ALLOCS = 40_000
SAMPLED_NODES = 256
FULLEST_NODES = 64


def _constraint_holds(node: Dict, constraint: Sequence[str]) -> bool:
    l_target, operand, r_target = constraint
    if not l_target.startswith("$attr."):
        raise ValueError(f"reference knows no target {l_target!r}")
    have = node["attributes"].get(l_target[len("$attr."):])
    if operand in ("=", "==", "is"):
        return have == r_target
    if operand in ("!=", "not"):
        return have != r_target
    raise ValueError(f"reference knows no operand {operand!r}")


def eligible(node: Dict, job: Dict) -> bool:
    """Whether ``job`` may run on ``node``: the node is ready and in one
    of the job's datacenters, fingerprints the task's driver, and meets
    every constraint."""
    return (node["ready"]
            and node["datacenter"] in job["datacenters"]
            and node["attributes"].get(f"driver.{job['driver']}")
            in ("1", "true", True)
            and all(_constraint_holds(node, c) for c in job["constraints"]))


def is_stop(entry: Dict) -> bool:
    return "stop" in entry


class _Cell:
    """The cell as first fit fills it: what every node has free."""

    def __init__(self, nodes: List[Dict]):
        self.nodes = nodes
        self.free_cpu = np.array([nd["cpu"] for nd in nodes], dtype=np.int64)
        self.free_mem = np.array([nd["memory_mb"] for nd in nodes],
                                 dtype=np.int64)
        self._masks: Dict[tuple, np.ndarray] = {}
        self._start = 0     # where the control's round robin goes on

    def mask(self, job: Dict) -> np.ndarray:
        key = job_key(job)
        if key not in self._masks:
            self._masks[key] = np.array(
                [eligible(nd, job) for nd in self.nodes], dtype=bool)
        return self._masks[key]

    def take(self, job: Dict, group: Dict, broken: str = "") -> np.ndarray:
        """The node index of each task of ``group`` that first fit
        places, taken off what is free. ``broken`` names a guarantee to
        ignore (the control)."""
        mask, n = self.mask(job), len(self.nodes)
        count, cpu, mem = group["count"], group["cpu"], group["memory_mb"]
        if broken == "capacity":
            # No capacity check: bin-packing's favourite node takes all.
            return np.full(count if mask.any() else 0, int(np.argmax(mask)))
        order = np.arange(n)
        if broken == "eligibility":
            # No feasibility check: round robin over every node.
            mask = np.ones(n, dtype=bool)
            order = (order + self._start) % n
            self._start = (self._start + count) % n
        # Per node, the tasks of cpu x mem that still fit; 0 off the mask.
        slots = np.minimum(self.free_cpu // max(cpu, 1),
                           self.free_mem // max(mem, 1))
        slots = np.where(mask, np.maximum(slots, 0), 0)[order]
        if broken == "eligibility":
            # One task per node per lap, as a spreading scheduler would.
            slots = np.minimum(slots, -(-count // n))
        take = np.minimum(
            slots, np.maximum(count - (np.cumsum(slots) - slots), 0))
        placed = np.repeat(order, take)
        self.give(placed, group, -1)
        return placed

    def give(self, placed: np.ndarray, group: Dict, sign: int = 1) -> None:
        np.add.at(self.free_cpu, placed, sign * group["cpu"])
        np.add.at(self.free_mem, placed, sign * group["memory_mb"])


def job_key(job: Dict) -> tuple:
    """What decides which nodes admit a job."""
    return (tuple(job["datacenters"]), job["driver"],
            tuple(map(tuple, job["constraints"])))


def _place(nodes: List[Dict], jobs: List[Dict],
           broken: str = "") -> Dict[str, List[np.ndarray]]:
    """{job id: for each group, the node index of each task placed}."""
    cell = _Cell(nodes)
    out: Dict[str, List[np.ndarray]] = {}
    held: Dict[str, Dict] = {}    # jobs whose resources are taken
    for job in jobs:
        if is_stop(job):
            gone = held.pop(job["stop"], None)
            if gone is not None:
                for group, placed in zip(gone["groups"], out[gone["id"]]):
                    cell.give(placed, group)
            continue
        out[job["id"]] = [cell.take(job, g, broken) for g in job["groups"]]
        if broken != "capacity":
            held[job["id"]] = job
    return out


def place(nodes: List[Dict], jobs: List[Dict],
          broken: str = "") -> Dict[str, np.ndarray]:
    """First fit, job by job in the order given and group by group in the
    job's order: {job id: node index of each task placed}. A stop entry
    gives back what each group of its job took. ``broken`` names a
    guarantee to ignore (the control)."""
    return {jid: np.concatenate(parts)
            for jid, parts in _place(nodes, jobs, broken).items()}


def _runs(jobs: List[Dict]) -> Iterator[Tuple[Dict, Dict]]:
    """(job, group) for each group of ``jobs`` in order, with neighbours
    that the same nodes admit and that ask the same cpu and memory run
    together: first fit fills the same slots for them one by one or as
    one."""
    last = None
    for job in jobs:
        admits = job_key(job)
        for group in job["groups"]:
            key = (admits, group["cpu"], group["memory_mb"])
            if last is not None and last[0] == key:
                last[2]["count"] += group["count"]
                continue
            if last is not None:
                yield last[1], last[2]
            last = (key, job, dict(group))
    if last is not None:
        yield last[1], last[2]


def rounds_that_fit(nodes: List[Dict],
                    rounds: Iterable[List[Dict]]) -> Tuple[int, int]:
    """What the empty cell holds of a mix: ``rounds`` (each a list of
    jobs, in the order offered) replayed by first fit until the first
    task is left out. (R, tasks): the rounds placed whole before it, and
    every task placed, those of the round that was cut short too. Where
    all tasks have one shape and the same nodes admit them, ``tasks`` is
    the cell's slots of that shape whatever the rounds: over the nodes
    that admit it, min(cpu // task cpu, memory // task memory)."""
    cell = _Cell(nodes)
    whole = tasks = 0
    for jobs in rounds:
        asked = placed = 0
        for job, group in _runs(jobs):
            asked += group["count"]
            placed += len(cell.take(job, group))
        tasks += placed
        if placed < asked:
            break
        whole += 1
    return whole, tasks


class Answers:
    """A set of answers as ``compare`` reads them. The program's come
    from its event stream and its state store; the control's from
    ``place`` with a guarantee broken."""

    def __init__(self, committed: Dict[str, int],
                 allocs_by_job: Callable[[str], List[Row]],
                 allocs_by_node: Callable[[str], List[Row]],
                 events_truncated: bool = False,
                 stopped: AbstractSet[str] = frozenset()):
        self.committed = committed          # job id -> placements committed
        self.allocs_by_job = allocs_by_job  # running allocs read back
        self.allocs_by_node = allocs_by_node
        self.events_truncated = events_truncated
        self.stopped = stopped              # jobs whose stop was committed


def control(nodes: List[Dict], jobs: List[Dict], broken: str) -> Answers:
    """The reference in the program's place, with one guarantee broken.
    It acknowledges every stop among ``jobs``; with ``stop`` broken it
    leaves every second row of a stopped job running."""
    if broken not in GUARANTEES:
        raise ValueError(f"unknown guarantee {broken!r}")
    placed = _place(nodes, jobs,
                    broken="" if broken in ("commit", "stop") else broken)
    stopped = {e["stop"] for e in jobs if is_stop(e)}
    by_job: Dict[str, List[Row]] = {}
    by_node: Dict[str, List[Row]] = {}
    committed: Dict[str, int] = {}
    for job in jobs:
        if is_stop(job):
            continue
        tasks = [(int(i), g["cpu"], g["memory_mb"])
                 for g, part in zip(job["groups"], placed[job["id"]])
                 for i in part]
        rows = [(f"{job['id']}/{k}", job["id"], nodes[i]["id"], cpu, mem)
                for k, (i, cpu, mem) in enumerate(tasks)]
        committed[job["id"]] = len(rows)
        if job["id"] in stopped:
            rows = rows[::2] if broken == "stop" else []
        elif broken == "commit":
            # Acknowledged in the stream, never readable from the store.
            rows = rows[::2]
        by_job[job["id"]] = rows
        for row in rows:
            by_node.setdefault(row[2], []).append(row)
    return Answers(committed, lambda jid: by_job.get(jid, []),
                   lambda nid: by_node.get(nid, []), stopped=stopped)


def sample_jobs(jobs: List[Dict], seed: int,
                always: Sequence[Dict] = ()) -> List[Dict]:
    """The jobs whose placements are read back one by one: all of them
    where that is at most MAX_SAMPLED_ALLOCS placements, else ``always``
    (where none is given, the largest) and, drawn from the seed, as many
    more as fit."""
    if sum(j["count"] for j in jobs) <= MAX_SAMPLED_ALLOCS:
        return list(jobs)
    rest = sorted(jobs, key=lambda j: (-j["count"], j["id"]))
    picked = list(always) or [rest[0]]
    taken = {j["id"] for j in picked}
    budget = MAX_SAMPLED_ALLOCS - sum(j["count"] for j in picked)
    rest = [j for j in rest if j["id"] not in taken]
    random.Random(int(seed) ^ 0x73616D70).shuffle(rest)
    for job in rest:
        if job["count"] <= budget:
            picked.append(job)
            budget -= job["count"]
    return picked


def compare(nodes: List[Dict], jobs: List[Dict], answers: Answers,
            seed: int, prior: Sequence[Dict] = ()) -> Dict[str, int]:
    """Hold ``answers`` to the reference: {number: value}; LIMITS has the
    limit of each. ``jobs`` are the jobs due, in the order offered, with
    the stops asked between them; ``prior`` are the jobs placed and the
    stops asked before them (the warm-up's). Only a stop the answers say
    was committed (``answers.stopped``) is applied and judged: one asked
    and never committed is the run's failure, not a wrong answer."""
    offered = [e for e in list(prior) + list(jobs)
               if not is_stop(e) or e["stop"] in answers.stopped]
    expected = place(nodes, offered)
    by_id = {nd["id"]: nd for nd in nodes}
    specs = {j["id"]: j for j in offered if not is_stop(j)}
    stops = [e for e in jobs if is_stop(e) and e["stop"] in answers.stopped
             and e["stop"] in specs]
    jobs = [j for j in jobs if not is_stop(j)]
    out = dict.fromkeys(LIMITS, 0)
    out["events_truncated"] = int(bool(answers.events_truncated))
    for job in jobs:
        if answers.committed.get(job["id"], 0) != len(expected[job["id"]]):
            out["jobs_short"] += 1
    seen_ids: set = set()
    per_node: Dict[str, int] = {}
    n_rows = 0
    for job in sample_jobs(jobs, seed):
        rows = answers.allocs_by_job(job["id"])
        want = (0 if job["id"] in answers.stopped
                else answers.committed.get(job["id"], 0))
        if len(rows) != want:
            out["store_mismatch"] += 1
        for alloc_id, _jid, nid, _cpu, _mem in rows:
            n_rows += 1
            seen_ids.add(alloc_id)
            per_node[nid] = per_node.get(nid, 0) + 1
            node = by_id.get(nid)
            if node is None or not eligible(node, job):
                out["ineligible"] += 1
        # Shape by shape, no more rows than the job's groups asked for
        # (with store_mismatch at 0 and the job whole: exactly those).
        asked: Counter = Counter()
        for g in job["groups"]:
            asked[(g["cpu"], g["memory_mb"])] += g["count"]
        got = Counter((cpu, mem) for _a, _j, _n, cpu, mem in rows)
        if any(k > asked[shape] for shape, k in got.items()):
            out["wrong_resources"] += 1
    out["duplicate_ids"] = n_rows - len(seen_ids)
    # The stopped jobs read back: the last wave stopped always, and as
    # many more, drawn from the seed, as the sample holds.
    last = max((e["round"] for e in stops), default=0)
    for job in sample_jobs(
            [specs[e["stop"]] for e in stops], seed,
            always=[specs[e["stop"]] for e in stops
                    if e["round"] == last]):
        if answers.allocs_by_job(job["id"]):
            out["stopped_running"] += 1
    fullest = sorted(per_node, key=lambda k: (-per_node[k], k))
    drawn = random.Random(int(seed) ^ 0x6E6F6465).sample(
        sorted(by_id), min(SAMPLED_NODES, len(by_id)))
    for nid in dict.fromkeys(fullest[:FULLEST_NODES] + drawn):
        rows = answers.allocs_by_node(nid)
        if (sum(r[3] for r in rows) > by_id[nid]["cpu"]
                or sum(r[4] for r in rows) > by_id[nid]["memory_mb"]):
            out["nodes_over_capacity"] += 1
    return out


def verdict(numbers: Dict[str, int]) -> bool:
    return all(numbers[k] <= LIMITS[k] for k in LIMITS)
