"""The cuts inside solver.staging and solver.execute, the front-door
span, XLA's compile events and the scalar-plan reasons (ISSUE 26): each
producer against the tracer, on the CPU backend."""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from nomad_tpu import mock, structs, trace
from nomad_tpu.trace import StageTimer, Tracer


def _numeric(v) -> bool:
    return isinstance(v, (int, float)) and not isinstance(v, bool)


# -- trace.py: CPU beside wall, nested cuts -----------------------------------


def test_nested_stage_cuts_carry_cpu_and_parent():
    tracer = Tracer()
    st = StageTimer()
    with st.stage("staging", cpu=True):
        with st.stage("staging.mask", cpu=True) as cut:
            cut.annotate("cached", True)
            sum(range(20000))            # CPU the thread really spends
        with st.stage("staging.upload", cpu=True):
            time.sleep(0.002)            # wall the thread does not
        with st.stage("staging.usage_job"):
            pass                         # no CPU reading unless asked for
        st.add("staging.elsewhere", trace.now() - 0.001, trace.now(), rows=3)
    with st.stage("execute"):
        pass
    wall, cpu = st.wall_cpu_ms("staging")
    assert wall >= 2.0 and 0.0 < cpu < wall
    assert set(st.durations_ms()) == {
        "staging", "staging.mask", "staging.upload", "staging.usage_job",
        "staging.elsewhere", "execute"}

    parent = tracer.start_span("t", "worker.invoke_scheduler", root=True)
    st.emit_spans(parent)
    parent.finish()
    spans = {s["name"]: s for s in tracer.get_trace("t")}
    staging = spans["solver.staging"]
    assert staging["parent_id"] == parent.span_id
    assert spans["solver.execute"]["parent_id"] == parent.span_id
    for child in ("mask", "upload", "usage_job", "elsewhere"):
        s = spans["solver.staging." + child]
        assert s["parent_id"] == staging["span_id"]
        assert staging["start"] <= s["start"] <= s["end"] <= staging["end"]
    # The stamps are served as measured, equal ones too.
    measured = {"solver." + c[0]: (c[1], c[2]) for c in st.stages}
    assert {n: (s["start"], s["end"]) for n, s in spans.items()
            if n in measured} == measured
    assert spans["solver.staging.mask"]["annotations"]["cached"] is True
    assert spans["solver.staging.mask"]["annotations"]["cpu_ms"] > 0.0
    up = spans["solver.staging.upload"]
    assert up["annotations"]["cpu_ms"] < up["duration_ms"]
    assert spans["solver.staging.usage_job"]["annotations"] == {}
    assert spans["solver.execute"]["annotations"] == {}
    # A cut measured elsewhere has no CPU reading of this thread's.
    assert spans["solver.staging.elsewhere"]["annotations"] == {"rows": 3}
    assert staging["annotations"]["cpu_ms"] <= staging["duration_ms"] + 0.5


def test_an_open_cut_is_left_out_and_the_rest_emitted():
    tracer = Tracer()
    st = StageTimer()
    with pytest.raises(RuntimeError):
        with st.stage("staging"):
            with st.stage("staging.mask"):
                pass
            st.stage("staging.upload").__enter__()   # never closed
            raise RuntimeError("staging died")
    parent = tracer.start_span("t", "worker.invoke_scheduler", root=True)
    st.emit_spans(parent)
    names = sorted(s["name"] for s in tracer.get_trace("t")
                   if s["name"].startswith("solver."))
    assert names == ["solver.staging", "solver.staging.mask"]


# -- mirror: how the usage base was served ------------------------------------


N_NODES = 1100   # enough for one commit to dirty over 1,024 rows, once the limit of a roll


@pytest.fixture(scope="module")
def usage_store():
    from nomad_tpu.state import StateStore

    store = StateStore()
    nodes = []
    for i in range(N_NODES):
        node = mock.node()
        nodes.append(node)
        store.upsert_node(i + 1, node)
    return store, nodes


def _allocs_on(nodes):
    job = mock.job()
    out = []
    for i, node in enumerate(nodes):
        out.append(structs.Allocation(
            id=structs.generate_uuid(), eval_id=structs.generate_uuid(),
            name=f"{job.name}.web[{i}]", node_id=node.id, job_id=job.id,
            job=job, task_group="web",
            resources=structs.Resources(cpu=10, memory_mb=16),
            desired_status=structs.ALLOC_DESIRED_STATUS_RUN))
    return out


def test_base_usage_counts_hit_roll_and_rebuild(usage_store):
    from nomad_tpu.scheduler.context import EvalContext
    from nomad_tpu.scheduler.util import ready_nodes_in_dcs
    from nomad_tpu.state.store import ALLOC_LOG_HORIZON
    from nomad_tpu.tpu.mirror import GLOBAL_MIRROR_CACHE, NodeMirror

    store, nodes = usage_store
    index = N_NODES   # the store's last write
    mirror = NodeMirror(ready_nodes_in_dcs(store.snapshot(), ["dc1"]))
    assert mirror.n == N_NODES

    def staged(paths_wanted):
        """One build_usage under a stage timer: the usage_base cut's
        annotations, and what GLOBAL_MIRROR_CACHE.stats() added."""
        before = GLOBAL_MIRROR_CACHE.stats()
        st = StageTimer()
        ctx = EvalContext(store.snapshot(), structs.Plan(
            eval_id=structs.generate_uuid()))
        with trace.use_stages(st), st.stage("staging"):
            mirror.build_usage(ctx, "job-x", "web")
        after = GLOBAL_MIRROR_CACHE.stats()
        cuts = {c[0]: c for c in st.stages}
        assert {"staging.usage_base"} <= set(cuts)
        base = cuts["staging.usage_base"]
        assert base[4] == 0, "usage_base is cut inside staging"
        if paths_wanted != "clean":
            assert {"staging.usage_job", "staging.upload"} <= set(cuts)
            assert "plan_batches" in cuts["staging.usage_job"][5]
        added = {k: after[k] - before[k]
                 for k in ("usage_rolls", "usage_rebuilds", "usage_shared")}
        return base[5], added

    ann, added = staged("clean")
    assert ann == {"path": "clean"}
    assert not any(added.values())

    # First fill: nothing cached to roll from.
    store.upsert_allocs(index + 1, _allocs_on(nodes[:3]))
    ann, added = staged("rebuild")
    assert ann["path"] == "rebuild" and "blocks" in ann
    assert added == {"usage_rolls": 0, "usage_rebuilds": 1,
                     "usage_shared": 0}

    ann, added = staged("hit")
    assert ann == {"path": "hit"}
    assert not any(added.values())

    store.upsert_allocs(index + 2, _allocs_on(nodes[10:15]))
    ann, added = staged("roll")
    assert ann["path"] == "roll" and ann["dirty_rows"] == 5
    assert ann["blocks"] == 0
    assert added == {"usage_rolls": 1, "usage_rebuilds": 0,
                     "usage_shared": 0}

    # One commit that dirties more than half the rows is a roll like any
    # other: the advance costs what the commit touched.
    store.upsert_allocs(index + 3, _allocs_on(nodes[:1030]))
    ann, added = staged("roll")
    assert ann["path"] == "roll" and ann["dirty_rows"] == 1030
    assert added == {"usage_rolls": 1, "usage_rebuilds": 0,
                     "usage_shared": 0}

    # Past the log's horizon the log cannot say what came and went.
    for k in range(2 * ALLOC_LOG_HORIZON + 1):
        store.upsert_allocs(index + 4 + k, _allocs_on(nodes[k % 7:k % 7 + 1]))
    ann, added = staged("rebuild")
    assert ann["path"] == "rebuild" and ann["blocks"] == 0
    assert "dirty_rows" not in ann
    assert added == {"usage_rolls": 0, "usage_rebuilds": 1,
                     "usage_shared": 0}


# -- coalescer: the dispatcher's stamps and the rider's cuts -------------------


def _wf_args(count, n=64):
    total = np.zeros((n, 4), dtype=np.int32)
    total[:, 0], total[:, 1], total[:, 2], total[:, 3] = 4000, 8192, 102400, 150
    return (
        jnp.asarray(total), jnp.asarray(total[:, :2].astype(np.float32)),
        jnp.zeros((n, 4), dtype=jnp.int32), jnp.zeros((n,), dtype=jnp.int32),
        jnp.zeros((n,), dtype=jnp.int32),
        jnp.full((n,), 1000, dtype=jnp.int32),
        jnp.zeros((n,), dtype=jnp.int32), jnp.ones((n,), dtype=bool),
        jnp.array([100, 128, 0, 0], dtype=jnp.int32), jnp.int32(0),
        count, 10.0)


@pytest.mark.parametrize("width", [1, 3])
def test_coalescer_stamps_are_ordered_and_cut_the_riders_wait(width):
    from nomad_tpu.ops.coalesce import CoalescingSolver

    engine = CoalescingSolver()
    engine.submit(*_wf_args(50))()            # dispatcher up, width 1 compiled
    tracer = Tracer()
    d0 = engine.dispatches
    if width > 1:
        engine.hint_burst(width, window_s=5.0, gap_s=5.0)
    riders = []
    submitted = trace.now()
    for i in range(width):
        st = StageTimer()
        engine.burst_begin()
        with trace.use_stages(st):
            fetch = engine.submit(*_wf_args(100 + i))
        riders.append((st, fetch))
    for st, fetch in riders:
        with trace.use_stages(st):
            counts, unplaced = fetch()
        assert int(counts.sum()) + unplaced == fetch.__self__.args[10]
    assert engine.dispatches == d0 + 1

    first_fetchers = 0
    for i, (st, fetch) in enumerate(riders):
        e = fetch.__self__
        assert e.group.width == width and e.group.path in ("jnp", "pallas")
        assert submitted <= e.t_taken <= e.t_launched <= e.group.t_ready

        parent = tracer.start_span(f"rider-{i}", "worker.invoke_scheduler",
                                   root=True)
        st.emit_spans(parent)
        by_name = {}
        for s in tracer.get_trace(f"rider-{i}"):
            by_name.setdefault(s["name"], []).append(s)
        waits = by_name["solver.execute"]
        wait = waits[0]
        cuts = [by_name["solver.execute." + n][0]
                for n in ("hold", "launch", "wake")]
        # hold + launch + wake are the rider's wait, end to end; the
        # hold starts at a stamp of its own, inside the wait's.
        assert 0.0 <= cuts[0]["start"] - wait["start"] < 1e-3
        assert cuts[0]["end"] == cuts[1]["start"]
        assert cuts[1]["end"] == cuts[2]["start"]
        assert wait["start"] <= cuts[2]["end"] <= wait["end"]
        assert all(c["parent_id"] == wait["span_id"] for c in cuts)
        assert cuts[0]["annotations"] == {}
        launch = dict(cuts[1]["annotations"])
        assert launch.pop("cpu_ms") >= 0.0   # the dispatcher thread's
        assert launch == {"width": width, "kind": "wf", "path": e.group.path}
        if "solver.execute.device_wait" in by_name:
            first_fetchers += 1
            dw = by_name["solver.execute.device_wait"][0]
            assert len(waits) == 2 and dw["parent_id"] == waits[1]["span_id"]
            assert dw["annotations"] == {}   # a wait: no CPU reading
    assert first_fetchers == 1, "one rider blocks on the device for the group"


def test_an_untraced_batch_is_not_stamped(monkeypatch):
    """With no stage timer on the rider the dispatcher reads no clock for
    it: the only thread that talks to the chip pays nothing for tracing
    that is off."""
    from nomad_tpu.ops import coalesce

    engine = coalesce.CoalescingSolver()
    engine.submit(*_wf_args(50))()            # dispatcher up, compiled
    reads = []
    real = time.thread_time
    monkeypatch.setattr(coalesce.time, "thread_time",
                        lambda: reads.append(1) or real())
    fetch = engine.submit(*_wf_args(60))
    counts, unplaced = fetch()
    e = fetch.__self__
    assert int(counts.sum()) + unplaced == 60
    assert not e.traced and e.t_taken is None and e.t_launched is None
    assert reads == []
    with trace.use_stages(StageTimer()):
        fetch = engine.submit(*_wf_args(70))
        fetch()
    assert fetch.__self__.t_launched is not None and len(reads) == 2


# -- XLA's own compile events --------------------------------------------------


def test_xla_listener_counts_a_new_shape_once():
    from nomad_tpu.scheduler import acquire_device
    from nomad_tpu.tpu.solver import SOLVER_PANEL

    acquire_device()
    acquire_device()   # the listener is registered once a process

    @jax.jit
    def odd_one_out(x):
        return (x * 3 + 1).sum()

    x = np.arange(1237, dtype=np.float32)   # no other test has this shape
    tracer = Tracer()
    span = tracer.start_span("t", "worker.invoke_scheduler", root=True)
    before = SOLVER_PANEL.snapshot()
    with trace.use_span(span):
        odd_one_out(x).block_until_ready()
    first = SOLVER_PANEL.snapshot()
    odd_one_out(x).block_until_ready()
    again = SOLVER_PANEL.snapshot()
    span.finish()

    assert first["xla_compiles"] - before["xla_compiles"] == 1
    assert first["xla_compile_ms"] > before["xla_compile_ms"]
    assert again["xla_compiles"] == first["xla_compiles"]
    assert again["xla_compile_ms"] == first["xla_compile_ms"]
    # The CPU backend keeps no persistent cache: nothing is loaded.
    assert again["xla_cache_loads"] == before["xla_cache_loads"]
    assert span.annotations["compiled"] is True


def test_xla_listener_tells_a_cache_load_from_a_compile():
    """A load from the persistent cache fires the retrieval event and then
    the enclosing backend-compile event on the same thread: one load, no
    compile."""
    from nomad_tpu import scheduler as sched
    from nomad_tpu.tpu.solver import SOLVER_PANEL

    before = SOLVER_PANEL.snapshot()
    sched._on_xla_duration(sched.XLA_CACHE_LOAD_EVENT, 0.004)
    sched._on_xla_duration(sched.XLA_COMPILE_EVENT, 0.005)
    sched._on_xla_duration("/jax/core/compile/jaxpr_trace_duration", 9.0)
    loaded = SOLVER_PANEL.snapshot()
    assert loaded["xla_cache_loads"] - before["xla_cache_loads"] == 1
    assert loaded["xla_cache_load_ms"] - before["xla_cache_load_ms"] == \
        pytest.approx(4.0, abs=0.01)
    assert loaded["xla_compiles"] == before["xla_compiles"]
    sched._on_xla_duration(sched.XLA_COMPILE_EVENT, 0.25)
    compiled = SOLVER_PANEL.snapshot()
    assert compiled["xla_compiles"] - before["xla_compiles"] == 1
    # A load whose enclosing event never fired on this thread does not
    # swallow the next compile: its stamp lies before that compile began.
    sched._on_xla_duration(sched.XLA_CACHE_LOAD_EVENT, 0.004)
    sched._xla_tls.loaded_at -= 5.0
    sched._on_xla_duration(sched.XLA_COMPILE_EVENT, 0.25)
    assert SOLVER_PANEL.snapshot()["xla_compiles"] \
        - before["xla_compiles"] == 2
    assert compiled["xla_compile_ms"] - before["xla_compile_ms"] == \
        pytest.approx(250.0, abs=0.01)


# -- plan pipeline: why a plan left the fused pass -----------------------------


def _columnar_plan(job, node_ids, cpu=20):
    plan = structs.Plan(eval_id=structs.generate_uuid())
    plan.alloc_batches.append(structs.AllocBatch(
        eval_id=plan.eval_id, job=job, tg_name="web",
        resources=structs.Resources(cpu=cpu, memory_mb=32),
        task_resources={"t": structs.Resources(cpu=cpu, memory_mb=32)},
        metrics=None, node_ids=list(node_ids),
        node_counts=[1] * len(node_ids),
        name_idx=np.arange(len(node_ids)), ids_seed=7))
    return plan


def test_scalar_reasons_sum_to_scalar_plans():
    from nomad_tpu.server.plan_pipeline import _PipelineTotals, evaluate_plans
    from nomad_tpu.state import StateStore

    store = StateStore()
    nodes = [mock.node() for _ in range(4)]
    for i, node in enumerate(nodes):
        node.reserved.networks = []   # reserved ports keep a node scalar
        store.upsert_node(i + 1, node)
    job = mock.job()
    ids = [n.id for n in nodes]
    totals = _PipelineTotals()

    def run(plans, snap=None):
        evaluate_plans(snap or store.snapshot(), plans, totals=totals)
        return totals.stats()

    # A batch of one never tries the fused pass.
    s = run([_columnar_plan(job, ids[:2])])
    assert (s["scalar_plans"], s["scalar_lone"]) == (1, 1)
    # Two columnar plans that fit: fused, nothing scalar.
    s = run([_columnar_plan(job, ids[:2]), _columnar_plan(job, ids[2:])])
    assert (s["fused_plans"], s["scalar_plans"]) == (2, 1)
    # The first asks for more than a node has: the prefix does not fit;
    # the plan left after it is alone.
    s = run([_columnar_plan(job, ids[:1], cpu=10 ** 6),
             _columnar_plan(job, ids[1:2])])
    assert (s["scalar_unfit"], s["scalar_lone"]) == (1, 2)
    # An object-row placement is not fused-eligible.
    objects = structs.Plan(eval_id=structs.generate_uuid())
    objects.node_allocation[ids[0]] = [structs.Allocation(
        id=structs.generate_uuid(), eval_id=objects.eval_id, name="o[0]",
        node_id=ids[0], job_id=job.id, job=job, task_group="web",
        resources=structs.Resources(cpu=10, memory_mb=16),
        desired_status=structs.ALLOC_DESIRED_STATUS_RUN)]
    snap = store.snapshot()
    s = run([objects, _columnar_plan(job, ids[1:2])], snap)
    assert s["scalar_ineligible"] == 1
    # That placement left an object row in the snapshot: now every plan of
    # a batch verifies scalar.
    s = run([_columnar_plan(job, ids[1:2]), _columnar_plan(job, ids[2:3])],
            snap)
    assert s["scalar_object_rows"] >= 1
    assert s["scalar_plans"] == (s["scalar_lone"] + s["scalar_ineligible"]
                                 + s["scalar_object_rows"]
                                 + s["scalar_unfit"])
    assert s["plans"] == 0   # counted by the pipeline thread, not here


# -- the books the benchmark snapshots ----------------------------------------


@pytest.mark.parametrize("book,keys", [
    ("panel", ("staging_wall_ms", "staging_cpu_ms", "staging_blocked_ms",
               "xla_compiles", "xla_compile_ms", "xla_cache_loads",
               "xla_cache_load_ms")),
    ("mirror", ("usage_rolls", "usage_rebuilds", "usage_shared")),
    ("pipeline", ("scalar_lone", "scalar_ineligible", "scalar_object_rows",
                  "scalar_unfit")),
])
def test_new_counters_are_top_level_and_numeric(book, keys):
    from nomad_tpu.server.plan_pipeline import PIPELINE_TOTALS
    from nomad_tpu.tpu.mirror import GLOBAL_MIRROR_CACHE
    from nomad_tpu.tpu.solver import SOLVER_PANEL

    snap = {"panel": SOLVER_PANEL.snapshot,
            "mirror": GLOBAL_MIRROR_CACHE.stats,
            "pipeline": PIPELINE_TOTALS.stats}[book]()
    for key in keys:
        assert key in snap and _numeric(snap[key]), key
        assert snap[key] >= 0


def test_staging_blocked_is_wall_less_cpu():
    from nomad_tpu.tpu.solver import SolverPanel

    panel = SolverPanel()
    panel.record_staging(10.0, 4.0)
    panel.record_staging(2.0, 2.5)    # clock grain: CPU can read past wall
    s = panel.snapshot()
    assert (s["staging_wall_ms"], s["staging_cpu_ms"]) == (12.0, 6.5)
    assert s["staging_blocked_ms"] == 5.5
    assert SolverPanel().snapshot()["staging_blocked_ms"] == 0.0
