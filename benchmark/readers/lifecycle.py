"""Reader ``lifecycle``: means per evaluation of the program's own stages
and spans over the window's evaluations (nomad_tpu.lifecycle stitches
them from the tracer's spans and the event stream; read-only).

source args: ``{"stage": "<lifecycle stage>"}`` for one stage of the
submit-to-placed partition, or ``{"span": "<span name>"}`` for the summed
duration of one span name per evaluation. Milliseconds."""

from __future__ import annotations


def timelines(ctx):
    if "timelines" not in ctx.cache:
        from nomad_tpu import lifecycle

        stitched = lifecycle.stitch(ctx.events)
        ctx.cache["timelines"] = [
            t for t in stitched.values()
            if t.submit_to_placed_ms is not None]
    return ctx.cache["timelines"]


def span_sums(ctx):
    """{span name: [summed ms per traced evaluation]}."""
    if "span_sums" not in ctx.cache:
        from nomad_tpu import trace

        tracer = trace.get_tracer()
        sums: dict = {}
        for t in timelines(ctx):
            spans = tracer.get_trace(t.eval_id)
            if not spans:
                continue
            per: dict = {}
            for s in spans:
                if s.get("end") is not None:
                    per[s["name"]] = per.get(s["name"], 0.0) + (
                        s["end"] - s["start"]) * 1000.0
            for name, ms in per.items():
                sums.setdefault(name, []).append(ms)
        ctx.cache["span_sums"] = sums
    return ctx.cache["span_sums"]


def read(args, ctx):
    if "stage" in args:
        tls = timelines(ctx)
        if not tls:
            return None
        return sum(t.stage_ms.get(args["stage"], 0.0) for t in tls) / len(tls)
    vals = span_sums(ctx).get(args["span"])
    if not vals:
        return None
    return sum(vals) / len(vals)
