"""Differential tests: the pallas water-fill kernel vs the jnp path.

The kernel must be bit-identical to ops/binpack.solve_waterfill (which is
itself differential-fuzzed against the host oracle), so the pallas path
inherits the whole oracle-parity chain. Runs in interpret mode on the CPU
backend; tests/test_pallas_lowering.py lowers it for TPU without a device
and the benchmark's drain cells run it on the chip."""

import numpy as np
import pytest

import jax.numpy as jnp

from functools import partial

from nomad_tpu.ops import pallas_solve
from nomad_tpu.ops.binpack import solve_waterfill
from nomad_tpu.ops.coalesce import solve_waterfill_rows
from nomad_tpu.ops.pallas_solve import solve_waterfill_pallas_batched


@pytest.fixture(autouse=True)
def _interpreted_kernel(monkeypatch):
    """The kernel as the coalescer's entry traces it, run by the
    interpreter on this CPU-pinned suite. Every trace of
    ``kernel="pallas"`` in this process is made under it: nothing else
    selects the kernel on the CPU backend."""
    monkeypatch.setattr(
        pallas_solve, "solve_waterfill_pallas_batched",
        partial(solve_waterfill_pallas_batched, interpret=True))


def _scalars(rows):
    return (np.asarray([int(r[10]) for r in rows], dtype=np.int32),
            np.asarray([float(r[11]) for r in rows], dtype=np.float32))


def solve_waterfill_pallas(*args):
    """One eval through the coalescer's entry at B = 1 on the kernel:
    same contract as binpack.solve_waterfill."""
    *row, jd, td = args
    counts, remaining = solve_waterfill_rows(
        (tuple(row[:10]),), *_scalars([row]), jd, td, kernel="pallas")
    return counts[0], remaining[0]


def random_instance(rng, n, d=4):
    total = rng.integers(100, 5000, size=(n, d)).astype(np.int32)
    used = (total * rng.uniform(0, 0.9, size=(n, d))).astype(np.int32)
    sched_cap = total[:, :2].astype(np.float32)
    jc = rng.integers(0, 3, size=n).astype(np.int32)
    tc = rng.integers(0, 2, size=n).astype(np.int32)
    bw_avail = rng.integers(0, 1000, size=n).astype(np.int32)
    bw_used = (bw_avail * rng.uniform(0, 1.0, size=n)).astype(np.int32)
    elig = rng.random(n) < 0.8
    ask = rng.integers(0, 500, size=d).astype(np.int32)
    bw_ask = int(rng.integers(0, 100))
    count = int(rng.integers(0, 3 * n))
    penalty = float(rng.choice([0.0, 5.0, 10.0]))
    return (
        jnp.asarray(total), jnp.asarray(sched_cap), jnp.asarray(used),
        jnp.asarray(jc), jnp.asarray(tc), jnp.asarray(bw_avail),
        jnp.asarray(bw_used), jnp.asarray(elig), jnp.asarray(ask),
        jnp.int32(bw_ask), jnp.int32(count), jnp.float32(penalty),
    )


def assert_match(args, jd, td):
    c0, r0 = solve_waterfill(*args, jd, td)
    c1, r1 = solve_waterfill_pallas(*args, jd, td)
    np.testing.assert_array_equal(np.asarray(c0), np.asarray(c1))
    assert int(r0) == int(r1)


def test_differential_random():
    rng = np.random.default_rng(7)
    for _ in range(4):
        assert_match(random_instance(rng, 64), False, False)


def test_differential_distinct_flags():
    rng = np.random.default_rng(8)
    assert_match(random_instance(rng, 64), True, False)
    assert_match(random_instance(rng, 64), False, True)


def test_edge_cases():
    rng = np.random.default_rng(9)
    args = list(random_instance(rng, 64))
    # count=0: nothing places
    args[10] = jnp.int32(0)
    assert_match(tuple(args), False, False)
    # demand exceeding total capacity: all capacity used, rest unplaced
    args[10] = jnp.int32(10_000_000)
    assert_match(tuple(args), False, False)
    # nothing eligible
    args[7] = jnp.zeros_like(args[7])
    args[10] = jnp.int32(50)
    assert_match(tuple(args), False, False)


def test_tie_break_matches_stable_argsort():
    # Identical nodes -> identical scores: the partial round must pick
    # the lowest node indices, like the jnp path's stable argsort.
    n = 64
    total = jnp.full((n, 4), 1000, dtype=jnp.int32)
    args = (
        total, total[:, :2].astype(jnp.float32),
        jnp.zeros((n, 4), jnp.int32),
        jnp.zeros((n,), jnp.int32), jnp.zeros((n,), jnp.int32),
        jnp.full((n,), 100, jnp.int32), jnp.zeros((n,), jnp.int32),
        jnp.ones((n,), bool), jnp.asarray([10, 10, 0, 0], jnp.int32),
        jnp.int32(0), jnp.int32(7), jnp.float32(0.0),
    )
    c0, r0 = solve_waterfill(*args, False, False)
    c1, r1 = solve_waterfill_pallas(*args, False, False)
    np.testing.assert_array_equal(np.asarray(c0), np.asarray(c1))
    assert int(np.asarray(c1).sum()) == 7
    assert np.asarray(c1)[:7].sum() == 7  # lowest indices won the tie


@pytest.mark.parametrize("width", [2, 3, 8])
def test_batched_matches_vmapped(width):
    """The two water-fills behind the one entry agree row for row at
    every stacked width (width 1 is every test above)."""
    rng = np.random.default_rng(11)
    rows = [random_instance(rng, 64) for _ in range(width)]
    rows10 = tuple(r[:10] for r in rows)
    counts, pens = _scalars(rows)
    c0, r0 = solve_waterfill_rows(rows10, counts, pens, False, False)
    c1, r1 = solve_waterfill_rows(
        rows10, counts, pens, False, False, kernel="pallas")
    np.testing.assert_array_equal(np.asarray(c0), np.asarray(c1))
    np.testing.assert_array_equal(np.asarray(r0), np.asarray(r1))
    for i, r in enumerate(rows):
        c, rem = solve_waterfill(*r, False, False)
        np.testing.assert_array_equal(np.asarray(c), np.asarray(c1[i]))
        assert int(rem) == int(r1[i])


def _select_interpreted_kernel(monkeypatch):
    """Make the coalescer's selection pick the kernel on this CPU-pinned
    suite: the selection itself reads only the backend and the node
    bucket (pallas_solve.selected)."""
    monkeypatch.setattr(pallas_solve, "selected", lambda n: True)


def test_coalescer_dispatches_selected_kernel(monkeypatch):
    from nomad_tpu.ops.coalesce import CoalescingSolver

    _select_interpreted_kernel(monkeypatch)
    rng = np.random.default_rng(12)
    args = random_instance(rng, 64)
    solver = CoalescingSolver()
    fetch = solver.submit(*args[:10], int(args[10]), float(args[11]))
    counts, unplaced = fetch()
    c0, r0 = solve_waterfill(*args, False, False)
    np.testing.assert_array_equal(np.asarray(c0), counts)
    assert int(r0) == unplaced
    # The pallas path must have actually run — the jnp solver produces
    # identical results, so only the path book tells them apart.
    assert solver.paths == {"pallas": 1}


def test_coalescer_stacks_selected_kernel(monkeypatch):
    """Width > 1 rides the batched kernel (the dispatch the (1, .) SMEM
    blocks could not lower for), through the launch the dispatcher and
    the warm calls share."""
    from nomad_tpu.ops.coalesce import _launch_rows

    _select_interpreted_kernel(monkeypatch)
    rng = np.random.default_rng(13)
    rows = [random_instance(rng, 64) for _ in range(3)]
    entries = [
        (*r[:10], int(r[10]), float(r[11]), False, False) for r in rows
    ]
    counts, remaining, path, single = _launch_rows(
        entries, "wf", 0, False, False)
    assert path == "pallas" and single
    assert counts.shape == (4, 64)  # padded to the width bucket
    assert int(counts[3].sum()) == 0  # the padding row: count 0
    for i, r in enumerate(rows):
        c0, r0 = solve_waterfill(*r, False, False)
        np.testing.assert_array_equal(np.asarray(c0), np.asarray(counts[i]))
        assert int(r0) == int(remaining[i])


def test_kernel_failure_propagates_and_flips_no_latch(monkeypatch):
    """A failure of the selected kernel is an error the eval surfaces:
    the fetch raises, nothing routes to the jnp water-fill, and the next
    dispatch selects the kernel again."""
    from nomad_tpu.ops import coalesce
    from nomad_tpu.ops.coalesce import CoalescingSolver

    _select_interpreted_kernel(monkeypatch)
    calls = {"kernel": 0}

    def boom(*a, **k):
        calls["kernel"] += 1
        raise RuntimeError("Mosaic failed to compile TPU kernel")

    def never(*a, **k):
        raise AssertionError("a kernel failure selected the jnp path")

    monkeypatch.setattr(pallas_solve, "solve_waterfill_pallas_batched", boom)
    monkeypatch.setattr(coalesce, "solve_waterfill", never)
    rng = np.random.default_rng(14)
    # A node count no other test traces the kernel at: the entry meets
    # the failing kernel while tracing, as a compile failure would be met.
    args = random_instance(rng, 96)
    solver = CoalescingSolver()
    for attempt in (1, 2):
        fetch = solver.submit(*args[:10], int(args[10]), float(args[11]))
        with pytest.raises(RuntimeError, match="coalesced solve failed") as ei:
            fetch()
        assert "Mosaic" in str(ei.value.__cause__)
        # dispatch + its one-at-a-time retry, both through the kernel
        assert calls["kernel"] == 2 * attempt
    assert solver.batch_retries == 2
    assert solver.paths == {}


def test_selection_reads_backend_and_bucket(monkeypatch):
    # tests pin the cpu backend: never selected here, whatever the env.
    monkeypatch.setenv("NOMAD_TPU_PALLAS", "1")
    assert pallas_solve.selected(16384) is False
    monkeypatch.setattr(pallas_solve.jax, "default_backend", lambda: "tpu")
    assert pallas_solve.selected(16384) is True
    assert pallas_solve.selected(pallas_solve.PALLAS_MAX_NODES) is True
    assert pallas_solve.selected(2 * pallas_solve.PALLAS_MAX_NODES) is False


# The fuzz corpus: the same randomized instances the waterfill/rounds/
# greedy three-way agreement runs on (test_fuzz_differential.py), so the
# pallas kernel joins the oracle-parity chain at its widest point.
N_PALLAS_FUZZ_SEEDS = int(__import__("os").environ.get(
    "NOMAD_TPU_PALLAS_FUZZ_SEEDS", 16))


@pytest.mark.parametrize("seed", range(N_PALLAS_FUZZ_SEEDS))
def test_fuzz_pallas_vs_waterfill(seed):
    from test_fuzz_differential import _random_solve_inputs

    rng = np.random.default_rng(10_000 + seed)  # same corpus as threeway
    s = _random_solve_inputs(rng)
    sched_cap = s["total"][:, :2].astype(np.float32)
    args = (
        jnp.asarray(s["total"]), jnp.asarray(sched_cap),
        jnp.asarray(s["used"]), jnp.asarray(s["job_count"]),
        jnp.asarray(s["tg_count"]), jnp.asarray(s["bw_avail"]),
        jnp.asarray(s["bw_used"]), jnp.asarray(s["eligible"]),
        jnp.asarray(s["ask"]), jnp.int32(s["bw_ask"]),
        jnp.int32(s["count"]), jnp.float32(s["penalty"]),
    )
    c0, r0 = solve_waterfill(*args, s["jd"], s["td"])
    c1, r1 = solve_waterfill_pallas(*args, s["jd"], s["td"])
    np.testing.assert_array_equal(
        np.asarray(c0), np.asarray(c1),
        err_msg=f"pallas != waterfill (seed {seed})",
    )
    assert int(r0) == int(r1), seed
