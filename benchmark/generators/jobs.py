"""Job shapes: a mock.Job()-shaped job built from a configuration's
``task`` group. The benchmark's own copy of ``build_job`` from
nomad_tpu/simcluster/workload.py, with the shape read from data."""

from __future__ import annotations

from typing import Dict

from nomad_tpu.structs import (
    Constraint,
    Job,
    Resources,
    RestartPolicy,
    Task,
    TaskGroup,
)


def job_spec(task: Dict, job_id: str, jtype: str, count: int) -> Dict:
    """One job as plain data: what is asked of the server, and what the
    plain reference holds the answer to."""
    return {"id": job_id, "type": jtype, "count": int(count),
            "cpu": int(task["cpu"]), "memory_mb": int(task["memory_mb"]),
            "driver": task["driver"],
            "datacenters": list(task["datacenters"]),
            "constraints": [list(c) for c in task.get("constraints", ())]}


def build_job(spec: Dict) -> Job:
    return Job(
        region="global", id=spec["id"], name=spec["id"], type=spec["type"],
        priority=50, datacenters=list(spec["datacenters"]),
        constraints=[Constraint(l_target=lt, operand=op, r_target=rt)
                     for lt, op, rt in spec["constraints"]],
        task_groups=[TaskGroup(
            name="web", count=spec["count"],
            restart_policy=RestartPolicy(
                attempts=1, interval=600.0, delay=5.0),
            tasks=[Task(
                name="web", driver=spec["driver"],
                resources=Resources(cpu=spec["cpu"],
                                    memory_mb=spec["memory_mb"]),
            )],
        )],
    )
