"""Greedy bin-pack solvers over the node axis.

Two device paths, both jitted with bucketed shapes to avoid recompilation
storms (SURVEY.md §7 "Hard parts: dynamic shapes"):

- ``solve_greedy``: lax.scan of k masked-argmax placements, preserving the
  reference's one-at-a-time Select semantics (/root/reference/scheduler/
  stack.go:131-159): each step recomputes fit + BestFit score + anti-affinity
  penalty against the utilization carried from earlier placements.

- ``solve_rounds_fused``: every round places up to one task per node on the
  best-scoring nodes, and all rounds run inside one lax.while_loop dispatch.
  In the anti-affinity regime (penalty 10/5 dominates the per-placement
  BestFit delta, stack.go:10-19) greedy provably round-robins across fitting
  nodes, so the rounds reproduce greedy's outcome in a single device call +
  a single transfer — this is what makes 100k-task evals ~100ms instead of
  100k dispatches.

The node axis is shardable: see nomad_tpu.parallel.mesh for the pjit
wrapping used on multi-chip meshes.
"""

from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax

from nomad_tpu import trace
from nomad_tpu.ops.fit import NEG_INF, score_fit


# Counts at or below this route through the exact greedy scan (padded to
# a power-of-two count bucket); larger counts take the count-independent
# water-fill. THE one threshold — solve_many_async defaults to it and
# the solver panel's kind/count-bucket attribution reads it, so the two
# can never drift.
EXACT_THRESHOLD = 128


def bucket(n: int, floor: int = 8) -> int:
    """Next power-of-two bucket for padding jit shapes."""
    b = floor
    while b < n:
        b *= 2
    return b


_DEVICE_CONST_CACHE: dict = {}


def device_const(kind: str, value):
    """Small device-resident constants ("ask" vectors, "i32" bandwidth
    asks). Every host->device transfer pays a fixed dispatch cost, so even
    16-byte uploads are worth caching across evals. (Counts and penalties
    ride the dispatch itself as host arrays: ops/coalesce.py.)"""
    key = (kind, value)
    cached = _DEVICE_CONST_CACHE.get(key)
    if cached is None:
        if kind == "ask":
            cached = jnp.asarray(list(value), dtype=jnp.int32)
        else:
            cached = jnp.int32(value)
        if len(_DEVICE_CONST_CACHE) > 512:
            _DEVICE_CONST_CACHE.clear()
        _DEVICE_CONST_CACHE[key] = cached
    return cached


def _monotone_u32(score: jnp.ndarray) -> jnp.ndarray:
    """Map float32 -> uint32 preserving total order (IEEE-754 trick:
    flip all bits of negatives, flip only the sign bit of positives).
    Lets kth-largest selection run as integer threshold search instead
    of a sort. THE shared definition: ops/pallas_solve.py imports this
    for its in-kernel selection — a change here changes both paths
    together (the differential suite pins their equality)."""
    bits = lax.bitcast_convert_type(score, jnp.uint32)
    neg = bits >> 31 == 1
    return jnp.where(neg, ~bits, bits | jnp.uint32(0x80000000))




@partial(jax.jit, static_argnames=("job_distinct", "tg_distinct"))
def _greedy_step_state(
    total, sched_cap, used, job_count, tg_count, bw_avail, bw_used,
    eligible, ask, bw_ask, penalty, job_distinct, tg_distinct,
):
    """Compute (score, fit) for one placement given current utilization.

    job_distinct/tg_distinct mirror the two distinct_hosts scopes of
    ProposedAllocConstraintIterator (feasible.go:218-247): a job-level
    constraint rejects any same-job alloc, a tg-level one rejects only
    same-job+same-tg collisions.
    """
    used_plus = used + ask[None, :]
    fit = jnp.all(used_plus <= total, axis=-1)
    fit = fit & ((bw_used + bw_ask) <= bw_avail)
    fit = fit & eligible
    if job_distinct:
        fit = fit & (job_count == 0)
    if tg_distinct:
        fit = fit & (tg_count == 0)
    score = score_fit(sched_cap, used_plus[:, :2].astype(jnp.float32))
    score = score - penalty * job_count.astype(jnp.float32)
    score = jnp.where(fit, score, NEG_INF)
    return score, fit


@partial(jax.jit, static_argnames=("k", "job_distinct", "tg_distinct"))
def solve_greedy(
    total: jnp.ndarray,       # [N, D] int32 node totals
    sched_cap: jnp.ndarray,   # [N, 2] float32 schedulable cpu/mem
    used0: jnp.ndarray,       # [N, D] int32 utilization incl. reserved
    job_count0: jnp.ndarray,  # [N] int32 proposed same-job allocs
    tg_count0: jnp.ndarray,   # [N] int32 proposed same-job+tg allocs
    bw_avail: jnp.ndarray,    # [N] int32 NIC bandwidth
    bw_used0: jnp.ndarray,    # [N] int32 used bandwidth
    eligible: jnp.ndarray,    # [N] bool feasibility mask
    ask: jnp.ndarray,         # [D] int32 task-group resource ask
    bw_ask: jnp.ndarray,      # [] int32 task-group bandwidth ask
    active: jnp.ndarray,      # [k] bool - False entries are shape padding
    penalty: jnp.ndarray,     # [] float32 anti-affinity penalty
    k: int,
    job_distinct: bool,
    tg_distinct: bool,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Place k copies of one ask sequentially; returns (node_idx[k], ok[k],
    score[k]). Exact greedy semantics of the reference's Select loop."""
    n = total.shape[0]
    arange = jnp.arange(n)

    def step(carry, is_active):
        used, job_count, tg_count, bw_used = carry
        score, _fit = _greedy_step_state(
            total, sched_cap, used, job_count, tg_count, bw_avail, bw_used,
            eligible, ask, bw_ask, penalty, job_distinct, tg_distinct,
        )
        idx = jnp.argmax(score)
        ok = (score[idx] > NEG_INF) & is_active
        onehot = (arange == idx) & ok
        used = used + onehot[:, None] * ask[None, :]
        job_count = job_count + onehot
        tg_count = tg_count + onehot
        bw_used = bw_used + onehot * bw_ask
        return (used, job_count, tg_count, bw_used), (idx, ok, score[idx])

    _, (idxs, oks, scores) = lax.scan(
        step, (used0, job_count0, tg_count0, bw_used0), active
    )
    return idxs, oks, scores


@partial(jax.jit, static_argnames=("job_distinct", "tg_distinct"))
def solve_rounds_fused(
    total: jnp.ndarray,
    sched_cap: jnp.ndarray,
    used0: jnp.ndarray,
    job_count0: jnp.ndarray,
    tg_count0: jnp.ndarray,
    bw_avail: jnp.ndarray,
    bw_used0: jnp.ndarray,
    eligible: jnp.ndarray,
    ask: jnp.ndarray,
    bw_ask: jnp.ndarray,
    count: jnp.ndarray,       # [] int32 total tasks to place
    penalty: jnp.ndarray,
    job_distinct: bool,
    tg_distinct: bool,
):
    """All rounds in one dispatch via lax.while_loop: returns per-node
    placement counts [N]. One device round-trip regardless of count — the
    transfer-latency killer for 100k-task evals."""
    n = total.shape[0]

    def cond(carry):
        _used, _jc, _tc, _bw, remaining, _counts, progressed = carry
        return (remaining > 0) & progressed

    def body(carry):
        used, job_count, tg_count, bw_used, remaining, counts, _ = carry
        score, fit = _greedy_step_state(
            total, sched_cap, used, job_count, tg_count, bw_avail, bw_used,
            eligible, ask, bw_ask, penalty, job_distinct, tg_distinct,
        )
        n_fit = fit.sum().astype(jnp.int32)

        def take_topk(_):
            # Partial round: keep only the `remaining` best-scoring fits.
            # Non-fit scores are NEG_INF, so fit nodes sort first.
            order = jnp.argsort(-score)
            rank = jnp.zeros(n, dtype=jnp.int32).at[order].set(
                jnp.arange(n, dtype=jnp.int32)
            )
            return fit & (rank < remaining)

        # All full rounds skip the argsort: every fitting node is selected.
        selected = lax.cond(
            n_fit <= remaining, lambda _: fit, take_topk, None
        )
        n_placed = selected.sum().astype(jnp.int32)
        used = used + selected[:, None] * ask[None, :]
        job_count = job_count + selected
        tg_count = tg_count + selected
        bw_used = bw_used + selected * bw_ask
        counts = counts + selected.astype(jnp.int32)
        return (
            used, job_count, tg_count, bw_used,
            remaining - n_placed, counts, n_placed > 0,
        )

    init = (
        used0, job_count0, tg_count0, bw_used0, count,
        jnp.zeros(n, dtype=jnp.int32), jnp.bool_(True),
    )
    _u, _jc, _tc, _bw, remaining, counts, _p = lax.while_loop(cond, body, init)
    return counts, remaining


@partial(jax.jit, static_argnames=("job_distinct", "tg_distinct"))
def solve_waterfill(
    total: jnp.ndarray,
    sched_cap: jnp.ndarray,
    used0: jnp.ndarray,
    job_count0: jnp.ndarray,
    tg_count0: jnp.ndarray,
    bw_avail: jnp.ndarray,
    bw_used0: jnp.ndarray,
    eligible: jnp.ndarray,
    ask: jnp.ndarray,
    bw_ask: jnp.ndarray,
    count: jnp.ndarray,       # [] int32 total tasks to place
    penalty: jnp.ndarray,
    job_distinct: bool,
    tg_distinct: bool,
):
    """Closed-form equivalent of ``solve_rounds_fused`` in one shot.

    Every *full* round of the round solver selects ALL fitting nodes (the
    argsort-free branch), so after L full rounds node i holds
    ``min(cap_i, L)`` placements, where cap_i is its total capacity for this
    ask. The final partial round takes the ``remaining`` best-scoring nodes
    among those with cap > L. So: binary-search L, then one scored top-k —
    no sequential state updates at all. Returns (counts[N], unplaced).
    """
    big = jnp.int32(2**30)

    # Per-node capacity for this ask, in copies.
    avail = total - used0
    nonneg = jnp.all(avail >= 0, axis=-1) & (bw_used0 <= bw_avail)
    safe_ask = jnp.maximum(ask, 1)[None, :]
    dim_cap = jnp.where(ask[None, :] > 0, avail // safe_ask, big)
    cap = jnp.min(dim_cap, axis=-1)
    bw_cap = jnp.where(bw_ask > 0, (bw_avail - bw_used0) // jnp.maximum(bw_ask, 1), big)
    cap = jnp.minimum(cap, bw_cap)
    if job_distinct:
        cap = jnp.minimum(cap, jnp.where(job_count0 == 0, 1, 0))
    if tg_distinct:
        cap = jnp.minimum(cap, jnp.where(tg_count0 == 0, 1, 0))
    cap = jnp.where(eligible & nonneg, jnp.clip(cap, 0, count), 0).astype(jnp.int32)

    # Largest L with sum(min(cap, L)) <= count.
    def placed_at(level):
        return jnp.minimum(cap, level).sum()

    def bs_cond(c):
        lo, hi = c
        return lo < hi

    def bs_body(c):
        lo, hi = c
        mid = (lo + hi + 1) // 2
        ok = placed_at(mid) <= count
        return (jnp.where(ok, mid, lo), jnp.where(ok, hi, mid - 1))

    # Search [0, min(count, max cap)]: any L >= max(cap) saturates
    # min(cap, L), so base and candidates — the only consumers of
    # ``level`` — come out identical, and the tighter interval cuts the
    # O(N) sum passes from ~log2(count) to ~log2(max cap) (a 100k-task
    # burst: 14 -> 6).
    hi0 = jnp.minimum(count, jnp.max(cap))
    level, _ = lax.while_loop(bs_cond, bs_body, (jnp.int32(0), hi0))

    base = jnp.minimum(cap, level)
    remaining = count - base.sum()

    # Partial round: top-`remaining` by score among nodes with headroom.
    score, fit = _greedy_step_state(
        total, sched_cap, used0 + base[:, None] * ask[None, :],
        job_count0 + base, tg_count0 + base, bw_avail,
        bw_used0 + base * bw_ask, eligible, ask, bw_ask, penalty,
        job_distinct, tg_distinct,
    )
    candidates = fit & (cap > level)
    # Rank bisection instead of argsort (sorts are the weak op on the
    # TPU vector unit; the pallas kernel uses the identical scheme):
    # map scores to order-preserving uint32 keys, binary-search the
    # remaining-th largest key in exactly 32 compare+reduce steps, then
    # break boundary ties by ascending node index — the same selection
    # a stable argsort(-score) produces. (A byte-radix histogram select
    # and a full sort were both A/B-measured SLOWER at the 131072-row
    # bucket on the CPU backend — XLA scatter/sort lose to 32 fused
    # compare+reduce passes.)
    u = jnp.where(candidates, _monotone_u32(score), jnp.uint32(0))

    def kth_body(_, lohi):
        lo, hi = lohi
        mid = lo + (hi - lo + 1) // 2
        ok = (candidates & (u >= mid)).sum(dtype=jnp.int32) >= remaining
        return (jnp.where(ok, mid, lo), jnp.where(ok, hi, mid - 1))

    # hi starts at 0xFFFFFFFE: real scores never map to the all-ones
    # image (a positive NaN), and a full-range start would overflow
    # (hi - lo + 1) on the first midpoint.
    thresh, _ = lax.fori_loop(
        0, 32, kth_body, (jnp.uint32(0), jnp.uint32(0xFFFFFFFE))
    )
    above = candidates & (u > thresh)
    boundary = candidates & (u == thresh)
    fill = remaining - above.sum(dtype=jnp.int32)
    order = jnp.cumsum(boundary.astype(jnp.int32), axis=-1)
    selected = (above | (boundary & (order <= fill))) & (remaining > 0)
    counts = base + selected.astype(jnp.int32)
    return counts, count - counts.sum()


def solve_many_async(
    total, sched_cap, used0, job_count0, tg_count0, bw_avail, bw_used0,
    eligible, ask, bw_ask, count: int, penalty: float,
    job_distinct: bool = False, tg_distinct: bool = False,
    exact_threshold: int = EXACT_THRESHOLD,
):
    """Dispatch the solve for ``count`` copies of one ask; return a fetch()
    closure that blocks on the device and yields (node_indices, ok).

    Device dispatch is asynchronous but the result readback pays a full
    host<->device round-trip, so callers overlap independent host work
    (uuid generation, name materialization) between dispatch and fetch.

    The exact scan path (small counts) is in true greedy placement order;
    the fused path reconstructs from per-node counts, so indices come
    grouped by node — copies of one ask are interchangeable, so callers
    must not rely on ordering. Unplaceable tail is idx -1 / ok False.
    """
    if count <= exact_threshold:
        # The exact scan rides the coalescing engine like the water-fill:
        # concurrent workers' small-count solves of one shape bucket
        # stack on the eval axis (coalesce.solve_greedy_rows) and cost ONE
        # device dispatch instead of K. Each stacked row runs the
        # identical independent scan, so results are bit-equal to a lone
        # dispatch (fuzz-pinned).
        from nomad_tpu.ops.coalesce import GLOBAL_SOLVER

        return GLOBAL_SOLVER.submit_exact(
            total, sched_cap, used0, job_count0, tg_count0, bw_avail,
            bw_used0, eligible, ask, bw_ask, count, penalty,
            job_distinct=job_distinct, tg_distinct=tg_distinct,
        )

    import numpy as np

    # Water-fill solver: one dispatch + one transfer for the whole batch.
    # distinct_hosts needs no special-casing: capacity is clamped to one
    # copy on nodes without same-scope allocs, zero otherwise.
    fetch_counts = solve_counts_async(
        total, sched_cap, used0, job_count0, tg_count0, bw_avail, bw_used0,
        eligible, ask, bw_ask, count, penalty,
        job_distinct=job_distinct, tg_distinct=tg_distinct,
    )

    def fetch_fused():
        counts, _unplaced = fetch_counts()
        # Host expansion of the columnar counts is readback-side work:
        # attribute it to the same stage the D2H copy lands in.
        with trace.stage("readback"):
            idxs = np.repeat(
                np.arange(counts.shape[0], dtype=np.int64), counts
            )
            n_placed = idxs.shape[0]
            out_idx = np.full(count, -1, dtype=np.int64)
            out_idx[:n_placed] = idxs[:count]
            oks = np.zeros(count, dtype=bool)
            oks[: min(n_placed, count)] = True
        return out_idx, oks

    return fetch_fused


def solve_counts_async(
    total, sched_cap, used0, job_count0, tg_count0, bw_avail, bw_used0,
    eligible, ask, bw_ask, count: int, penalty: float,
    job_distinct: bool = False, tg_distinct: bool = False,
):
    """Water-fill dispatch returning per-node placement *counts* — the
    columnar form consumed by AllocBatch. One device round-trip; no
    per-placement expansion at all. fetch() -> (counts[N] np.int32,
    n_unplaced int).

    Routed through the coalescing engine: concurrent workers' solves stack
    into a single vmapped dispatch (ops/coalesce.py)."""
    from nomad_tpu.ops.coalesce import GLOBAL_SOLVER

    return GLOBAL_SOLVER.submit(
        total, sched_cap, used0, job_count0, tg_count0, bw_avail, bw_used0,
        eligible, ask, bw_ask, count, penalty,
        job_distinct=job_distinct, tg_distinct=tg_distinct,
    )


def solve_many(
    total, sched_cap, used0, job_count0, tg_count0, bw_avail, bw_used0,
    eligible, ask, bw_ask, count: int, penalty: float,
    job_distinct: bool = False, tg_distinct: bool = False,
    exact_threshold: int = EXACT_THRESHOLD,
):
    """Synchronous wrapper over solve_many_async."""
    fetch = solve_many_async(
        total, sched_cap, used0, job_count0, tg_count0, bw_avail, bw_used0,
        eligible, ask, bw_ask, count, penalty,
        job_distinct=job_distinct, tg_distinct=tg_distinct,
        exact_threshold=exact_threshold,
    )
    return fetch()
