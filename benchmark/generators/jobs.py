"""Job shapes: a mock.Job()-shaped job built from a configuration's
``task`` group and, where a mix gives ``templates``, its ``task_shapes``.
The benchmark's own copy of ``build_job`` from
nomad_tpu/simcluster/workload.py, with the shapes read from data.

A spec of a mix that updates its jobs carries two keys more: ``update``
(``{stagger_s, max_parallel}``, the job's rolling update strategy) and
``version`` (0 when first registered, one more with each update). The
version is stamped into every task's ``env`` under ``VERSION_ENV``: a
change of ``env`` is a destructive update to the server, which evicts
and places the job's tasks anew, and the stamp is how a row read back
from the state store tells its version (``version_of``). A spec without
the keys builds the job it built before they existed, byte for byte."""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence

from nomad_tpu.structs import (
    Constraint,
    Job,
    Resources,
    RestartPolicy,
    Task,
    TaskGroup,
    UpdateStrategy,
)

PRIORITY = 50
VERSION_ENV = "JOB_VERSION"


def job_spec(task: Dict, job_id: str, jtype: str, count: int = 0,
             groups: Optional[List[Dict]] = None, priority: int = PRIORITY,
             constraints: Sequence[Sequence[str]] = ()) -> Dict:
    """One job as plain data: what is asked of the server, and what the
    plain reference holds the answer to. ``task`` is what all jobs of a
    configuration share (driver, datacenters, constraints); ``groups``
    are the job's task groups in order, each ``{name, count, cpu,
    memory_mb}``, and where none are given the job is one group ``web``
    of ``count`` tasks of ``task``'s own cpu and memory. ``count`` is the
    groups' sum; ``constraints`` are the job's own, after the shared."""
    if groups is None:
        groups = [{"name": "web", "count": count, "cpu": task["cpu"],
                   "memory_mb": task["memory_mb"]}]
    groups = [{"name": g["name"], "count": int(g["count"]),
               "cpu": int(g["cpu"]), "memory_mb": int(g["memory_mb"])}
              for g in groups]
    return {"id": job_id, "type": jtype, "priority": int(priority),
            "count": sum(g["count"] for g in groups), "groups": groups,
            "driver": task["driver"],
            "datacenters": list(task["datacenters"]),
            "constraints": [list(c) for c in task.get("constraints", ())]
            + [list(c) for c in constraints]}


def template_spec(config: Dict, template: Dict, job_id: str,
                  jtype: str) -> Dict:
    """The job a mix's template asks for: ``{type, priority,
    constraints, groups: [{count, shape}]}``, each ``shape`` a name of
    the configuration's ``task_shapes``. ``jtype`` is the mix's
    ``job_type``, for a template that names none."""
    shapes = config["task_shapes"]
    groups = [dict(shapes[g["shape"]], count=g["count"],
                   name=g.get("name", f"g{k}-{g['shape']}"))
              for k, g in enumerate(template["groups"])]
    return job_spec(config["task"], job_id, template.get("type", jtype),
                    groups=groups,
                    priority=template.get("priority", PRIORITY),
                    constraints=template.get("constraints", ()))


def build_job(spec: Dict) -> Job:
    """One ``TaskGroup`` of one ``Task`` for each group, in order; the
    update strategy and the version's stamp where the spec has them."""
    env = ({VERSION_ENV: str(int(spec["version"]))} if "version" in spec
           else {})
    job = Job(
        region="global", id=spec["id"], name=spec["id"], type=spec["type"],
        priority=spec["priority"], datacenters=list(spec["datacenters"]),
        constraints=[Constraint(l_target=lt, operand=op, r_target=rt)
                     for lt, op, rt in spec["constraints"]],
        task_groups=[TaskGroup(
            name=g["name"], count=g["count"],
            restart_policy=RestartPolicy(
                attempts=1, interval=600.0, delay=5.0),
            tasks=[Task(
                name=g["name"], driver=spec["driver"],
                env=dict(env),
                resources=Resources(cpu=g["cpu"],
                                    memory_mb=g["memory_mb"]),
            )],
        ) for g in spec["groups"]],
    )
    if "update" in spec:
        job.update = UpdateStrategy(
            stagger=float(spec["update"]["stagger_s"]),
            max_parallel=int(spec["update"]["max_parallel"]))
    return job


def version_of(job: Optional[Job], group: str) -> Optional[int]:
    """The version stamped into ``job``'s group ``group`` (the job an
    allocation embeds), or None where it carries no stamp."""
    tg = job.lookup_task_group(group) if job is not None else None
    stamp = tg.tasks[0].env.get(VERSION_ENV) if tg and tg.tasks else None
    return int(stamp) if stamp is not None else None
