"""The client's view of the server's event stream: a tail that collects
every event from a cursor on, and the reduction from events to per-job
placement records. The benchmark's own copy of the watcher in
nomad_tpu/simcluster/scenario.py, with nothing judged inside the window:
the tail appends and keeps one running total, the rest is read after."""

from __future__ import annotations

import math
import threading
import time
from typing import Dict, List


def quantile(sorted_vals: List[float], p: float) -> float:
    """The value at rank ceil(p * n) of an ascending list."""
    n = len(sorted_vals)
    return sorted_vals[max(0, min(n - 1, math.ceil(p * n - 1e-9) - 1))]


def event_placed(e) -> int:
    """Placements one AllocUpserted event commits."""
    if e.topic != "Alloc" or e.type != "AllocUpserted":
        return 0
    if e.payload.get("columnar"):
        return int(e.payload.get("count", 0))
    return 1 if e.payload.get("desired_status") == "run" else 0


class EventTail:
    def __init__(self, broker, poll_s: float = 0.02):
        self.broker = broker
        self.poll_s = poll_s
        self.events: List = []
        self.truncated = False
        self.placed = 0
        self._cursor = broker.get_index()
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self._run, daemon=True, name="bench-events")

    def start(self) -> "EventTail":
        self._thread.start()
        return self

    def _take(self) -> None:
        latest, evs, truncated = self.broker.events_after(self._cursor)
        if truncated:
            self.truncated = True
        if evs:
            self.events.extend(evs)
            self.placed += sum(event_placed(e) for e in evs)
            self._cursor = latest

    def _run(self) -> None:
        while not self._stop.is_set():
            self._take()
            time.sleep(self.poll_s)
        self._take()

    def placed_total(self) -> int:
        return self.placed

    def stop(self) -> None:
        self._stop.set()
        self._thread.join(timeout=10.0)


def placements_by_job(events, jobs: Dict[str, Dict]) -> Dict[str, Dict]:
    """Per job: how many placements the stream committed, and the stamp of
    the PlanApplied that committed the last one asked for.
    {job id: {"placed", "done_at" or None}}."""
    eval_job: Dict[str, str] = {}
    out = {jid: {"placed": 0, "done_at": None} for jid in jobs}
    for e in events:
        if e.topic == "Eval" and e.type == "EvalUpdated":
            jid = e.payload.get("job_id")
            if jid in out:
                eval_job[e.key] = jid
        elif e.topic == "Alloc" and e.type == "AllocUpserted":
            n = event_placed(e)
            jid = (eval_job.get(e.key) if e.payload.get("columnar")
                   else e.payload.get("job_id"))
            if n and jid in out:
                out[jid]["placed"] += n
        elif e.topic == "Plan" and e.type == "PlanApplied":
            jid = eval_job.get(e.key)
            if jid in out:
                rec = out[jid]
                if (rec["done_at"] is None
                        and rec["placed"] >= jobs[jid]["spec"]["count"]):
                    rec["done_at"] = e.time
    return out
