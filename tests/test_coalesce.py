"""Coalescing solve engine: concurrent evals stack into one vmapped
dispatch (the device half of the broker's coalescing dequeue,
SURVEY.md §7 'Batched evals'; concurrency semantics mirror the
reference's optimistic worker parallelism, nomad/worker.go:45-125)."""

import threading

import jax.numpy as jnp
import numpy as np
import pytest

from nomad_tpu.ops.binpack import solve_waterfill
from nomad_tpu.ops.coalesce import CoalescingSolver

N = 64


def _inputs(ask_cpu, count, n=N):
    total = np.zeros((n, 4), dtype=np.int32)
    total[:, 0] = 4000
    total[:, 1] = 8192
    total[:, 2] = 100 * 1024
    total[:, 3] = 150
    return dict(
        total=jnp.asarray(total),
        sched_cap=jnp.asarray(total[:, :2].astype(np.float32)),
        used0=jnp.zeros((n, 4), dtype=jnp.int32),
        job_count0=jnp.zeros((n,), dtype=jnp.int32),
        tg_count0=jnp.zeros((n,), dtype=jnp.int32),
        bw_avail=jnp.full((n,), 1000, dtype=jnp.int32),
        bw_used0=jnp.zeros((n,), dtype=jnp.int32),
        eligible=jnp.ones((n,), dtype=bool),
        ask=jnp.array([ask_cpu, 128, 0, 0], dtype=jnp.int32),
        bw_ask=jnp.int32(0),
        count=count,
        penalty=10.0,
    )


def _direct(inp):
    counts, remaining = solve_waterfill(
        inp["total"], inp["sched_cap"], inp["used0"], inp["job_count0"],
        inp["tg_count0"], inp["bw_avail"], inp["bw_used0"], inp["eligible"],
        inp["ask"], inp["bw_ask"], jnp.int32(inp["count"]),
        jnp.float32(inp["penalty"]), False, False,
    )
    return np.asarray(counts), int(remaining)


def _submit(engine, inp):
    return engine.submit(
        inp["total"], inp["sched_cap"], inp["used0"], inp["job_count0"],
        inp["tg_count0"], inp["bw_avail"], inp["bw_used0"], inp["eligible"],
        inp["ask"], inp["bw_ask"], inp["count"], inp["penalty"],
    )


def test_single_submission_matches_direct():
    engine = CoalescingSolver()
    inp = _inputs(100, 500)
    counts, unplaced = _submit(engine, inp)()
    d_counts, d_unplaced = _direct(inp)
    assert unplaced == d_unplaced
    np.testing.assert_array_equal(counts, d_counts)


def test_concurrent_submissions_coalesce_and_match():
    """K threads submitting while the dispatcher is busy coalesce into
    vmapped dispatches; every result matches its individual solve."""
    engine = CoalescingSolver()
    specs = [(50 + 10 * i, 200 + 37 * i) for i in range(12)]
    inputs = [_inputs(c, n) for c, n in specs]
    results = [None] * len(inputs)
    errors = []

    def worker(i):
        try:
            results[i] = _submit(engine, inputs[i])()
        except Exception as e:  # pragma: no cover
            errors.append(e)

    threads = [
        threading.Thread(target=worker, args=(i,))
        for i in range(len(inputs))
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    assert not errors
    for i, inp in enumerate(inputs):
        counts, unplaced = results[i]
        d_counts, d_unplaced = _direct(inp)
        assert unplaced == d_unplaced, i
        np.testing.assert_array_equal(counts, d_counts, err_msg=f"eval {i}")
    # With 12 concurrent submissions at least some must have coalesced
    assert engine.dispatches >= 1
    assert engine.dispatches + engine.coalesced >= len(inputs)


def test_mixed_shapes_group_separately():
    """Different padded node counts can't share a program: they dispatch
    as separate groups but all complete correctly."""
    engine = CoalescingSolver()
    inp_a = _inputs(100, 100)

    total_b = np.zeros((128, 4), dtype=np.int32)
    total_b[:, 0] = 2000
    total_b[:, 1] = 4096
    inp_b = dict(
        total=jnp.asarray(total_b),
        sched_cap=jnp.asarray(total_b[:, :2].astype(np.float32)),
        used0=jnp.zeros((128, 4), dtype=jnp.int32),
        job_count0=jnp.zeros((128,), dtype=jnp.int32),
        tg_count0=jnp.zeros((128,), dtype=jnp.int32),
        bw_avail=jnp.full((128,), 1000, dtype=jnp.int32),
        bw_used0=jnp.zeros((128,), dtype=jnp.int32),
        eligible=jnp.ones((128,), dtype=bool),
        ask=jnp.array([100, 64, 0, 0], dtype=jnp.int32),
        bw_ask=jnp.int32(0),
        count=50,
        penalty=5.0,
    )

    fetches = [_submit(engine, inp_a), _submit(engine, inp_b)]
    (ca, ua), (cb, ub) = fetches[0](), fetches[1]()
    da, dua = _direct(inp_a)
    np.testing.assert_array_equal(ca, da)
    assert ua == dua
    assert cb.shape == (128,)
    assert int(cb.sum()) + ub == 50


def _entries(inputs, kind="wf"):
    from nomad_tpu.ops.binpack import bucket
    from nomad_tpu.ops.coalesce import _Entry

    return [
        _Entry((
            inp["total"], inp["sched_cap"], inp["used0"], inp["job_count0"],
            inp["tg_count0"], inp["bw_avail"], inp["bw_used0"],
            inp["eligible"], inp["ask"], inp["bw_ask"], inp["count"],
            inp["penalty"], False, False,
        ), kind=kind, k=bucket(inp["count"]) if kind == "exact" else 0)
        for inp in inputs
    ]


def _panel_single():
    from nomad_tpu.tpu.solver import SOLVER_PANEL

    return SOLVER_PANEL.snapshot()["single_program_dispatches"]


def test_batch_failure_falls_open_to_individual_solves(monkeypatch):
    """A batch-level dispatch error retries each entry individually — the
    same entry at B = 1 — and fetch() returns the full [N] counts vector,
    matching the direct solve. The failed chunk is a dispatch that did
    not go out as one call: the single-program counter falls behind."""
    from nomad_tpu.ops import coalesce

    engine = CoalescingSolver()
    inputs = [_inputs(100, 100), _inputs(120, 200)]
    entries = _entries(inputs)
    real = coalesce.solve_waterfill_rows
    widths = []

    def stacked_fails(rows, *args, **kwargs):
        widths.append(len(rows))
        if len(rows) > 1:
            raise RuntimeError("batched program failed")
        return real(rows, *args, **kwargs)

    monkeypatch.setattr(coalesce, "solve_waterfill_rows", stacked_fails)
    single0 = _panel_single()
    engine._dispatch(entries)
    assert widths == [2, 1, 1]
    assert (engine.dispatches, engine.batch_retries) == (1, 1)
    assert _panel_single() == single0
    for entry, inp in zip(entries, inputs):
        counts, unplaced = entry.result()
        d_counts, d_unplaced = _direct(inp)
        assert counts.shape == d_counts.shape
        np.testing.assert_array_equal(counts, d_counts)
        assert unplaced == d_unplaced


def test_total_failure_raises_instead_of_hanging(monkeypatch):
    """If the per-entry retry also fails, waiters get the exception through
    the real submit() fetch path — not a hang or an AttributeError on a
    never-set group."""
    from nomad_tpu.ops import coalesce

    engine = CoalescingSolver()

    def boom(*args, **kwargs):
        raise ValueError("device is gone")

    monkeypatch.setattr(coalesce, "solve_waterfill_rows", boom)
    fetches = [
        _submit(engine, _inputs(100, 100)), _submit(engine, _inputs(120, 200))
    ]
    for fetch in fetches:
        with pytest.raises(RuntimeError, match="coalesced solve failed") as ei:
            fetch()
        assert isinstance(ei.value.__cause__, ValueError)


def test_async_device_fault_raises_at_fetch(monkeypatch):
    """A device fault that surfaces at the result fetch raises to the
    fetching eval and changes nothing about later selection."""
    from nomad_tpu.ops import coalesce

    def boom(_):
        raise RuntimeError("async mosaic fault")

    monkeypatch.setattr(coalesce.jax, "device_get", boom)
    g = coalesce._Group("counts", "remaining")
    with pytest.raises(RuntimeError, match="async mosaic fault"):
        g.fetch(0)


def test_hint_burst_holds_dispatch_for_full_burst():
    """An announced burst stacks into ONE dispatch even when the submits
    arrive staggered (the batch-worker posture: K eval threads' host prep
    lands their solves a few ms apart)."""
    import time

    engine = CoalescingSolver()
    # Warm the dispatcher thread + compile both shapes outside the burst.
    _submit(engine, _inputs(50, 100))()
    engine.hint_burst(4, window_s=2.0, gap_s=1.0)
    d0 = engine.dispatches
    inputs = [_inputs(50 + 10 * i, 100 + 17 * i) for i in range(4)]
    fetches = []
    for i, inp in enumerate(inputs):
        # Each submit plays one announced eval thread (burst_begin re-arms
        # the thread-local membership between sequential submits).
        engine.burst_begin()
        fetches.append(_submit(engine, inp))
        time.sleep(0.01)  # staggered, but within the inter-arrival gap
    results = [f() for f in fetches]
    assert engine.dispatches == d0 + 1, "burst must land as one dispatch"
    for inp, (counts, unplaced) in zip(inputs, results):
        d_counts, d_unplaced = _direct(inp)
        assert unplaced == d_unplaced
        np.testing.assert_array_equal(counts, d_counts)


def test_hint_burst_expires_without_full_burst():
    """An expectation that never fills (announced evals that submit no
    solve) costs at most the window: the partial burst dispatches at the
    deadline and later lone submits don't inherit any wait."""
    import time

    engine = CoalescingSolver()
    _submit(engine, _inputs(50, 100))()
    engine.hint_burst(8, window_s=0.1)
    t0 = time.monotonic()
    counts, unplaced = _submit(engine, _inputs(60, 120))()
    waited = time.monotonic() - t0
    # At most the hard window plus solve time + margin — the documented
    # cost ceiling of an expectation that never fills.
    assert waited < 0.5
    d_counts, d_unplaced = _direct(_inputs(60, 120))
    assert unplaced == d_unplaced
    np.testing.assert_array_equal(counts, d_counts)
    # Residual expectation cleared: a lone submit returns promptly.
    t0 = time.monotonic()
    _submit(engine, _inputs(70, 130))()
    assert time.monotonic() - t0 < 0.09


def test_hint_burst_dead_residue_does_not_stack():
    """A burst whose evals never submit ANY solve leaves its expectation
    behind (the dispatcher is parked on an empty queue and can't clear
    it); the next hint must replace the dead residue, not stack on it."""
    import time

    engine = CoalescingSolver()
    engine.hint_burst(8, window_s=0.01)
    time.sleep(0.03)  # deadline passes with zero submits
    engine.hint_burst(2, window_s=1.0, gap_s=1.0)
    with engine._lock:
        assert engine._burst_outstanding == 2
    d0 = engine.dispatches
    engine.burst_begin()
    f1 = _submit(engine, _inputs(50, 100))
    engine.burst_begin()
    f2 = _submit(engine, _inputs(60, 110))
    f1(), f2()
    assert engine.dispatches == d0 + 1


def test_burst_done_releases_hold_without_submits():
    """Announced evals that finish WITHOUT ever reaching the coalescer
    (exact-path small counts, scale-downs) resolve their slots via
    burst_done: the hold releases the moment the last one reports, not
    at the give-up gap or window."""
    import time

    engine = CoalescingSolver()
    _submit(engine, _inputs(50, 100))()
    # Gap and window far beyond the assertion bound: only precise
    # accounting can release the hold this fast.
    engine.hint_burst(3, window_s=30.0, gap_s=30.0)
    d0 = engine.dispatches
    engine.burst_begin()
    fetch = _submit(engine, _inputs(60, 120))  # member 1: real solve
    for _ in range(2):  # members 2, 3: no solve, completion resolves
        engine.burst_begin()
        engine.burst_done()
    t0 = time.monotonic()
    counts, unplaced = fetch()
    assert time.monotonic() - t0 < 5.0
    assert engine.dispatches == d0 + 1
    d_counts, d_unplaced = _direct(_inputs(60, 120))
    assert unplaced == d_unplaced
    np.testing.assert_array_equal(counts, d_counts)


def test_dispatcher_survives_unexpected_batch_error(monkeypatch):
    """A failure OUTSIDE the per-chunk fail-open (a bug in grouping, an
    allocation failure) must fail that batch's waiters and leave the
    dispatcher loop alive for subsequent submits — a dead dispatcher
    parks every future eval forever."""
    engine = CoalescingSolver()

    orig = engine._dispatch
    calls = {"n": 0}

    def boom_once(batch):
        calls["n"] += 1
        if calls["n"] == 1:
            raise MemoryError("unexpected batch-level failure")
        return orig(batch)

    monkeypatch.setattr(engine, "_dispatch", boom_once)
    with pytest.raises(RuntimeError) as ei:
        _submit(engine, _inputs(100, 200))()
    assert isinstance(ei.value.__cause__, MemoryError)
    # Loop survived: the next submit dispatches normally.
    counts, unplaced = _submit(engine, _inputs(110, 210))()
    d_counts, d_unplaced = _direct(_inputs(110, 210))
    np.testing.assert_array_equal(counts, d_counts)
    assert unplaced == d_unplaced


def _submit_exact(engine, inp):
    return engine.submit_exact(
        inp["total"], inp["sched_cap"], inp["used0"], inp["job_count0"],
        inp["tg_count0"], inp["bw_avail"], inp["bw_used0"], inp["eligible"],
        inp["ask"], inp["bw_ask"], inp["count"], inp["penalty"],
    )


def _direct_exact(inp):
    from nomad_tpu.ops.binpack import bucket, solve_greedy

    k = bucket(inp["count"])
    active = jnp.arange(k) < inp["count"]
    idxs, oks, _ = solve_greedy(
        inp["total"], inp["sched_cap"], inp["used0"], inp["job_count0"],
        inp["tg_count0"], inp["bw_avail"], inp["bw_used0"], inp["eligible"],
        inp["ask"], inp["bw_ask"], active, jnp.float32(inp["penalty"]),
        k, False, False,
    )
    return (np.asarray(idxs)[: inp["count"]],
            np.asarray(oks)[: inp["count"]])


def test_exact_submissions_stack_into_one_dispatch():
    """Announced-burst exact solves of one (node, count-bucket) shape
    stack into ONE solve_greedy_rows dispatch, each row bit-equal to
    its lone dispatch; the solver panel's batch-width axis records the
    stacked width."""
    from nomad_tpu.tpu.solver import SOLVER_PANEL

    engine = CoalescingSolver()
    # Warm the dispatcher + both shapes outside the burst.
    _submit_exact(engine, _inputs(50, 40))()
    engine.hint_burst(4, window_s=2.0, gap_s=1.0)
    d0 = engine.dispatches
    with SOLVER_PANEL._lock:
        w0 = dict(
            (w, list(v)) for w, v in SOLVER_PANEL._batch_widths.items()
        )
    # Counts 33..48 share the 64 bucket; asks differ per entry. One
    # SHARED set of node tensors across the burst (the production shape:
    # burst members solve against one mirror) — stacking is keyed on
    # mirror identity.
    base = _inputs(60, 33)
    inputs = []
    for i in range(4):
        inp = dict(base)
        inp["ask"] = jnp.array([60 + 10 * i, 128, 0, 0], dtype=jnp.int32)
        inp["count"] = 33 + 5 * i
        inputs.append(inp)
    fetches = []
    for inp in inputs:
        engine.burst_begin()
        fetches.append(_submit_exact(engine, inp))
    results = [f() for f in fetches]
    assert engine.dispatches == d0 + 1, "burst must land as one dispatch"
    for inp, (idxs, oks) in zip(inputs, results):
        d_idxs, d_oks = _direct_exact(inp)
        np.testing.assert_array_equal(idxs, d_idxs)
        np.testing.assert_array_equal(oks, d_oks)
    with SOLVER_PANEL._lock:
        row = SOLVER_PANEL._batch_widths.get(4)
        prev = w0.get(4, [0, 0, 0.0])
    assert row is not None and row[0] >= prev[0] + 1, (
        "width-4 dispatch not recorded on the panel's batch-width axis"
    )


def test_exact_and_waterfill_entries_never_share_a_dispatch():
    """Mixed-kind pending entries group by program family: a wf entry
    and an exact entry in one drain dispatch separately, both correct."""
    engine = CoalescingSolver()
    wf_inp = _inputs(100, 300)
    ex_inp = _inputs(80, 50)
    entries = _entries([wf_inp]) + _entries([ex_inp], "exact")
    d0 = engine.dispatches
    engine._dispatch(entries)
    assert engine.dispatches == d0 + 2
    counts, unplaced = entries[0].result()
    d_counts, d_unplaced = _direct(wf_inp)
    np.testing.assert_array_equal(counts, d_counts)
    assert unplaced == d_unplaced
    idxs, oks = entries[1].result()
    d_idxs, d_oks = _direct_exact(ex_inp)
    np.testing.assert_array_equal(np.asarray(idxs)[: ex_inp["count"]],
                                  d_idxs)
    np.testing.assert_array_equal(np.asarray(oks)[: ex_inp["count"]],
                                  d_oks)


def test_warm_exact_batch_shapes_compiles():
    from nomad_tpu.ops.coalesce import warm_exact_batch_shapes

    # 2 count buckets x 3 widths at one node bucket.
    assert warm_exact_batch_shapes(64, counts=(8, 16)) == 6


def test_burst_generation_scopes_accounting():
    """A straggler from an earlier (given-up or over-announced) burst
    must not decrement a successor burst's expectation — member
    accounting is scoped by the generation token hint_burst returns."""
    import time

    engine = CoalescingSolver()
    tok_a = engine.hint_burst(2, window_s=0.01)
    time.sleep(0.03)  # burst A's window passes unresolved
    tok_b = engine.hint_burst(2, window_s=5.0, gap_s=5.0)
    assert tok_b != tok_a
    # Straggler member of burst A reports done AFTER B was announced:
    engine.burst_begin(tok_a)
    engine.burst_done()
    with engine._lock:
        assert engine._burst_outstanding == 2, (
            "stale-generation burst_done must not release B's hold"
        )
    # B's own members resolve it normally.
    for _ in range(2):
        engine.burst_begin(tok_b)
        engine.burst_done()
    with engine._lock:
        assert engine._burst_outstanding == 0


# -- one program a dispatch (ISSUE 34) ----------------------------------------


def _family_entries(family, n, width, salt):
    """``width`` entries of one dispatch group on an n-row bucket: asks,
    counts and penalties differ by entry and by ``salt``; the node
    tensors are ONE set (exact entries stack by mirror identity)."""
    base = _inputs(0, 0, n)
    base["eligible"] = jnp.asarray(np.arange(n) % 7 != 3)
    inputs = []
    for i in range(width):
        inp = dict(base)
        inp["ask"] = jnp.array([60 + 10 * i + salt, 128, 0, 0],
                               dtype=jnp.int32)
        # exact: 33..64 share the 64 bucket; wf: any count.
        inp["count"] = (33 + 3 * i + salt if family == "exact"
                        else 300 + 41 * i + 7 * salt)
        inp["penalty"] = 10.0 if (i + salt) % 2 else 5.0
        inputs.append(inp)
    return inputs, _entries(inputs, family)


def _assert_bit_equal(family, inputs, entries):
    for inp, e in zip(inputs, entries):
        if family == "exact":
            idxs, oks = e.result()
            d_idxs, d_oks = _direct_exact(inp)
            np.testing.assert_array_equal(idxs[: inp["count"]], d_idxs)
            np.testing.assert_array_equal(oks[: inp["count"]], d_oks)
            assert not oks[inp["count"]:].any()
        else:
            counts, unplaced = e.result()
            d_counts, d_unplaced = _direct(inp)
            np.testing.assert_array_equal(counts, d_counts)
            assert unplaced == d_unplaced


@pytest.mark.parametrize("width", [1, 2, 3, 8])
@pytest.mark.parametrize("family", ["wf", "exact"])
def test_rows_entry_is_bit_equal_to_lone_solves(family, width):
    """Every width of both families through the one launch: each rider's
    counts / remaining (indices / oks) are those of its lone
    solve_waterfill (solve_greedy), padding rows included."""
    engine = CoalescingSolver()
    inputs, entries = _family_entries(family, N, width, salt=0)
    engine._dispatch(entries)
    assert engine.dispatches == 1
    assert entries[0].group.width == width
    assert engine.paths == {"exact" if family == "exact" else "jnp": 1}
    _assert_bit_equal(family, inputs, entries)


# One node bucket a width that no other test solves on: a program first
# met here compiles here.
_FRESH_BUCKETS = {1: 2048, 3: 4096, 8: 8192}


@pytest.mark.parametrize("width", [1, 3, 8])
@pytest.mark.parametrize("family", ["wf", "exact"])
def test_dispatch_is_one_program(family, width):
    """The first dispatch of a shape compiles exactly ONE program (an
    eager op beside the solve, on a new shape, would compile too), the
    second none, and another count and penalty on the same shapes none
    (they ride as typed host arrays, not as constants); every such
    dispatch counts as a single-program dispatch."""
    from nomad_tpu.scheduler import acquire_device
    from nomad_tpu.tpu.solver import SOLVER_PANEL

    acquire_device()   # registers the listener for XLA's compile events
    n = _FRESH_BUCKETS[width]
    engine = CoalescingSolver()
    batches = [_family_entries(family, n, width, salt)
               for salt in (0, 0, 5)]
    single0 = _panel_single()
    compiles = [SOLVER_PANEL.snapshot()["xla_compiles"]]
    for _inp, entries in batches:
        engine._dispatch(entries)
        for e in entries:
            e.result()
        compiles.append(SOLVER_PANEL.snapshot()["xla_compiles"])
    assert [b - a for a, b in zip(compiles, compiles[1:])] == [1, 0, 0]
    assert engine.dispatches == 3
    assert _panel_single() - single0 == 3
    assert engine.batch_retries == 0
    for inputs, entries in batches:
        _assert_bit_equal(family, inputs, entries)


@pytest.mark.parametrize("family", ["wf", "exact"])
def test_warmed_set_is_the_dispatched_set(family):
    """The warm calls and the dispatcher share one launch: after a
    bucket is warmed, no real dispatch of any width compiles."""
    from nomad_tpu.ops.coalesce import (
        warm_batch_shapes,
        warm_exact_batch_shapes,
    )
    from nomad_tpu.scheduler import acquire_device
    from nomad_tpu.tpu.solver import SOLVER_PANEL

    acquire_device()
    n = 512 if family == "wf" else 1024   # fresh buckets again
    if family == "wf":
        assert warm_batch_shapes(n) == 4
    else:   # counts 33..64 -> the 64 bucket, at every width
        assert warm_exact_batch_shapes(
            n, counts=(64,), buckets=(1, 2, 4, 8)) == 4
    engine = CoalescingSolver()
    batches = [_family_entries(family, n, width, salt=width)
               for width in (1, 2, 3, 5, 8)]
    compiled0 = SOLVER_PANEL.snapshot()["xla_compiles"]
    for _inp, entries in batches:
        engine._dispatch(entries)
        for e in entries:
            e.result()
    assert SOLVER_PANEL.snapshot()["xla_compiles"] == compiled0
    for inputs, entries in batches:
        _assert_bit_equal(family, inputs, entries)
