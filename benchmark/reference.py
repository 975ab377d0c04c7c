"""The plain reference: what a cluster scheduler owes its users, worked
out from the configuration's and the traffic's plain data alone.

Imports nothing of the program and takes nothing the program made but its
answers. The same nodes and the same jobs give the same verdict whatever
placed them. It does four things:

- ``place``: a straightforward first-fit placement of the jobs on the
  nodes. Its per-job totals are the answers the program's are held to
  (how many tasks of each job can be placed at all).
- ``free_slots``: how many tasks of one shape the empty cell holds, by
  the arithmetic ``place`` fills it with. The generator offers a closed
  loop's next round only where it fits whole.
- ``compare``: holds a set of answers to the configuration's guarantees:
  every job due has all its placements committed, the state store reads
  back what the event stream committed, every placement sits on a node
  the job's datacenters, driver and constraints admit, carries the
  resources the job asked for and a unique id, and no node holds more
  than its capacity. Every number is a count with the limit 0.
- ``control``: ``place`` with one guarantee broken, put in the program's
  place. ``compare`` has to fail it.
"""

from __future__ import annotations

import random
from typing import Callable, Dict, List, Sequence, Tuple

import numpy as np

# Each row of an answer: (alloc id, job id, node id, cpu, memory_mb).
Row = Tuple[str, str, str, int, int]

GUARANTEES = ("capacity", "eligibility", "commit")
LIMITS = {
    "jobs_short": 0, "store_mismatch": 0, "ineligible": 0,
    "wrong_resources": 0, "duplicate_ids": 0, "nodes_over_capacity": 0,
    "events_truncated": 0,
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


def _slots(free_cpu: np.ndarray, free_mem: np.ndarray, mask: np.ndarray,
           cpu: int, mem: int) -> np.ndarray:
    """Per node, the tasks of cpu x mem that still fit; 0 off the mask."""
    slots = np.minimum(free_cpu // max(cpu, 1), free_mem // max(mem, 1))
    return np.where(mask, np.maximum(slots, 0), 0)


def free_slots(nodes: List[Dict], job: Dict) -> int:
    """The tasks of ``job``'s shape that the empty cell holds: over the
    nodes that admit the job, min(cpu // job cpu, memory // job memory).
    ``job["count"]`` is not read."""
    mask = np.array([eligible(nd, job) for nd in nodes], dtype=bool)
    return int(_slots(
        np.array([nd["cpu"] for nd in nodes], dtype=np.int64),
        np.array([nd["memory_mb"] for nd in nodes], dtype=np.int64),
        mask, job["cpu"], job["memory_mb"]).sum())


def place(nodes: List[Dict], jobs: List[Dict],
          broken: str = "") -> Dict[str, np.ndarray]:
    """First fit, job by job in the order given: {job id: node index of
    each task placed}. ``broken`` names a guarantee to ignore (the
    control)."""
    n = len(nodes)
    free_cpu = np.array([nd["cpu"] for nd in nodes], dtype=np.int64)
    free_mem = np.array([nd["memory_mb"] for nd in nodes], dtype=np.int64)
    masks: Dict[tuple, np.ndarray] = {}
    out: Dict[str, np.ndarray] = {}
    start = 0
    for job in jobs:
        key = (tuple(job["datacenters"]), job["driver"],
               tuple(map(tuple, job["constraints"])))
        if key not in masks:
            masks[key] = np.array([eligible(nd, job) for nd in nodes])
        mask = masks[key]
        count, cpu, mem = job["count"], job["cpu"], job["memory_mb"]
        if broken == "capacity":
            # No capacity check: bin-packing's favourite node takes all.
            first = int(np.argmax(mask))
            out[job["id"]] = np.full(count if mask.any() else 0, first)
            continue
        if broken == "eligibility":
            # No feasibility check: round robin over every node.
            mask = np.ones(n, dtype=bool)
            order = (np.arange(n) + start) % n
            start = (start + count) % n
        else:
            order = np.arange(n)
        slots = _slots(free_cpu, free_mem, mask, cpu, mem)[order]
        if broken == "eligibility":
            # One task per node per lap, as a spreading scheduler would.
            laps = -(-count // n)
            slots = np.minimum(slots, laps)
        take = np.minimum(slots, np.maximum(count - (np.cumsum(slots) - slots), 0))
        placed_nodes = np.repeat(order, take)
        out[job["id"]] = placed_nodes
        np.subtract.at(free_cpu, placed_nodes, cpu)
        np.subtract.at(free_mem, placed_nodes, mem)
    return out


class Answers:
    """A set of answers as ``compare`` reads them. The program's come
    from its event stream and its state store; the control's from
    ``place`` with a guarantee broken."""

    def __init__(self, committed: Dict[str, int],
                 allocs_by_job: Callable[[str], List[Row]],
                 allocs_by_node: Callable[[str], List[Row]],
                 events_truncated: bool = False):
        self.committed = committed          # job id -> placements committed
        self.allocs_by_job = allocs_by_job  # running allocs read back
        self.allocs_by_node = allocs_by_node
        self.events_truncated = events_truncated


def control(nodes: List[Dict], jobs: List[Dict], broken: str) -> Answers:
    """The reference in the program's place, with one guarantee broken."""
    if broken not in GUARANTEES:
        raise ValueError(f"unknown guarantee {broken!r}")
    placed = place(nodes, jobs, broken="" if broken == "commit" else broken)
    by_job: Dict[str, List[Row]] = {}
    by_node: Dict[str, List[Row]] = {}
    for job in jobs:
        rows = [(f"{job['id']}/{k}", job["id"], nodes[int(i)]["id"],
                 job["cpu"], job["memory_mb"])
                for k, i in enumerate(placed[job["id"]])]
        if broken == "commit":
            # Acknowledged in the stream, never readable from the store.
            rows = rows[::2]
        by_job[job["id"]] = rows
        for row in rows:
            by_node.setdefault(row[2], []).append(row)
    committed = {j["id"]: len(placed[j["id"]]) for j in jobs}
    return Answers(committed, lambda jid: by_job.get(jid, []),
                   lambda nid: by_node.get(nid, []))


def sample_jobs(jobs: List[Dict], seed: int) -> List[Dict]:
    """The jobs whose placements are read back one by one: all of them
    where that is at most MAX_SAMPLED_ALLOCS placements, else the largest
    and, drawn from the seed, as many more as fit."""
    if sum(j["count"] for j in jobs) <= MAX_SAMPLED_ALLOCS:
        return list(jobs)
    rest = sorted(jobs, key=lambda j: (-j["count"], j["id"]))
    picked, budget = [rest[0]], MAX_SAMPLED_ALLOCS - rest[0]["count"]
    rest = rest[1:]
    random.Random(int(seed) ^ 0x73616D70).shuffle(rest)
    for job in rest:
        if job["count"] <= budget:
            picked.append(job)
            budget -= job["count"]
    return picked


def compare(nodes: List[Dict], jobs: List[Dict], answers: Answers,
            seed: int, prior: Sequence[Dict] = ()) -> Dict[str, int]:
    """Hold ``answers`` to the reference: {number: value}; LIMITS has the
    limit of each. ``jobs`` are the jobs due, in the order offered;
    ``prior`` are jobs placed before them (the warm-up's)."""
    expected = place(nodes, list(prior) + list(jobs))
    by_id = {nd["id"]: nd for nd in nodes}
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
        if len(rows) != answers.committed.get(job["id"], 0):
            out["store_mismatch"] += 1
        for alloc_id, _jid, nid, cpu, mem in rows:
            n_rows += 1
            seen_ids.add(alloc_id)
            per_node[nid] = per_node.get(nid, 0) + 1
            node = by_id.get(nid)
            if node is None or not eligible(node, job):
                out["ineligible"] += 1
            if (cpu, mem) != (job["cpu"], job["memory_mb"]):
                out["wrong_resources"] += 1
    out["duplicate_ids"] = n_rows - len(seen_ids)
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
