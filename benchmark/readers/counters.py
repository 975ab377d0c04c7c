"""Reader ``counters``: the program's own counters, differenced over the
window. ``snapshot`` flattens them to {name: number}; a metric names one
(``counter``) and optionally another to divide by (``per``).

Names: ``coalescer.dispatches|coalesced|batch_retries``,
``coalescer.paths.<path>``, ``panel.<key>`` (SOLVER_PANEL's numeric
keys), ``mirror.<key>``, ``pipeline.<key>``,
``raft.<msg_type>.bytes`` and ``raft.<msg_type>.entries``, and the
harness's own ``window.placements`` and ``window.evals``."""

from __future__ import annotations

from typing import Dict


def snapshot(srv) -> Dict[str, float]:
    from nomad_tpu.ops.coalesce import GLOBAL_SOLVER
    from nomad_tpu.tpu.mirror import GLOBAL_MIRROR_CACHE
    from nomad_tpu.tpu.solver import SOLVER_PANEL

    out: Dict[str, float] = {
        "coalescer.dispatches": GLOBAL_SOLVER.dispatches,
        "coalescer.coalesced": GLOBAL_SOLVER.coalesced,
        "coalescer.batch_retries": GLOBAL_SOLVER.batch_retries,
    }
    for path, n in dict(GLOBAL_SOLVER.paths).items():
        out[f"coalescer.paths.{path}"] = n
    panel = SOLVER_PANEL.snapshot()
    for key, v in panel.items():
        # Running totals only: a ratio does not difference.
        if (isinstance(v, (int, float)) and not isinstance(v, bool)
                and "_per_" not in key and not key.endswith("_waste")):
            out[f"panel.{key}"] = v
    for width, row in panel["batch_widths"].items():
        out[f"panel.width.{width}"] = row["dispatches"]
    for key, v in GLOBAL_MIRROR_CACHE.stats().items():
        if isinstance(v, (int, float)) and not isinstance(v, bool):
            out[f"mirror.{key}"] = v
    for key, v in srv.plan_applier.stats().items():
        if isinstance(v, (int, float)) and not isinstance(v, bool):
            out[f"pipeline.{key}"] = v
    srv.raft_observatory.refresh()
    for msg, book in srv.raft_observatory.snapshot()["write_path"].items():
        out[f"raft.{msg}.bytes"] = book["bytes_total"]
        out[f"raft.{msg}.entries"] = book["count"]
    return out


def delta(after: Dict[str, float], before: Dict[str, float]) -> Dict:
    return {k: v - before.get(k, 0) for k, v in after.items()}


def read(args, ctx):
    value = ctx.counters.get(args["counter"])
    if value is None:
        return None
    if "per" not in args:
        return float(value)
    base = ctx.counters.get(args["per"])
    if not base:
        return None
    return value / base
