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
from nomad_tpu.scheduler import candidates


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


def _floor_div(a, b):
    """``a // b`` for int32 a >= 0 (a negative a gives 0) and b >= 1,
    without the vector integer division: XLA's TPU backend takes 18 s to
    compile one over a [8, 16384, 4] operand (compiled here for a v5e,
    PR 37), and every program of the coalescer would pay it. Exact for
    every quotient up to 2^22 (and for every a < 2^24): the float
    quotient is then within two of the answer, and two rounds of integer
    products settle it. A larger quotient comes out within 2^-22 of
    itself, and its only reader clips it to a count far below."""
    a = jnp.maximum(a, 0)
    q = jnp.floor(a.astype(jnp.float32) / b.astype(jnp.float32)
                  ).astype(jnp.int32)
    for _ in range(2):
        # (q + 1) * b and q * b stay inside int32 wherever q is exact
        # to within two: both are within 3b of a <= 2^31 - 2^24.
        q = q - (q * b > a).astype(jnp.int32)
        q = q + ((q + 1) * b <= a).astype(jnp.int32)
    return q


def _copies_cap(total, used0, job_count0, tg_count0, bw_avail, bw_used0,
                eligible, ask, bw_ask, most, job_distinct, tg_distinct):
    """Per-node capacity for this ask, in copies, clipped to ``most``
    (int32[N], 0 on a node that is ineligible or already overcommitted).
    The ONE definition the water-fill and the candidate rule share."""
    big = jnp.int32(2**30)
    avail = total - used0
    nonneg = jnp.all(avail >= 0, axis=-1) & (bw_used0 <= bw_avail)
    safe_ask = jnp.maximum(ask, 1)[None, :]
    dim_cap = jnp.where(ask[None, :] > 0, _floor_div(avail, safe_ask), big)
    cap = jnp.min(dim_cap, axis=-1)
    bw_cap = jnp.where(
        bw_ask > 0,
        _floor_div(bw_avail - bw_used0, jnp.maximum(bw_ask, 1)), big)
    cap = jnp.minimum(cap, bw_cap)
    if job_distinct:
        cap = jnp.minimum(cap, jnp.where(job_count0 == 0, 1, 0))
    if tg_distinct:
        cap = jnp.minimum(cap, jnp.where(tg_count0 == 0, 1, 0))
    return jnp.where(eligible & nonneg, jnp.clip(cap, 0, most), 0).astype(jnp.int32)


def restrict_to_candidates(
    total, used0, job_count0, tg_count0, bw_avail, bw_used0, eligible,
    ask, bw_ask, count, cand_key, job_distinct, tg_distinct, spread,
):
    """The candidate rule on the device (scheduler/candidates.py is its
    host side and says why): ``eligible`` narrowed to the nodes this
    evaluation's solve may choose from, wherever those hold all ``count``
    copies; where they do not, nothing is narrowed, so a group is solved
    over every eligible node in this same program and is left short only
    where the cell is. A node is ROOMY where it has room for ``HEADROOM``
    x L copies, L = ceil(count / nodes) being its even share: another
    evaluation's share still fits beside this one's. The two program
    families (``spread``, static):

    - the exact scan packs best fit, into the fullest node, where two
      evaluations cannot both land: it keeps the evaluation's own class
      of nodes, at the finest level that holds the group (level 0 is the
      whole cell). The finest class is its own, to pack as it likes; of a
      coarser one, which other evaluations' classes lie in, it keeps the
      roomy nodes alone, and so does every level of an attempt after a
      refused plan (``cand_key`` >= ``RETRY``: a drawn key, which any
      evaluation in flight may share);
    - the water-fill spreads, and meets other evaluations wherever it
      goes: it keeps the roomy nodes of the whole cell, and of them the
      ``count`` roomiest where there are more than copies: the far end
      of the cell from the fullest node an exact scan is packing.

    ``cand_key`` < 0 narrows nothing. Integer arithmetic throughout: the
    host oracle reproduces the mask exactly."""
    n = total.shape[0]
    bits = candidates.class_bits(n)
    if bits == 0 and not spread:
        return eligible
    room = candidates.HEADROOM
    cap = _copies_cap(total, used0, job_count0, tg_count0, bw_avail,
                      bw_used0, eligible, ask, bw_ask, room * count,
                      job_distinct, tg_distinct)
    if spread:
        nodes = jnp.maximum((cap > 0).sum(dtype=jnp.int32), 1)
        share = (count + nodes - 1) // nodes
        roomy = cap >= room * jnp.maximum(share, 1)
        holds = jnp.where(roomy, jnp.minimum(cap, count), 0).sum() >= count

        # A group of fewer copies than there are roomy nodes goes one to a
        # node: to the ``count`` roomiest, ties and all. The largest floor
        # that still keeps ``count`` nodes, by bisection (cap <= room x
        # count < 2^31).
        def floor_body(_, lohi):
            lo, hi = lohi
            mid = lo + (hi - lo + 1) // 2
            ok = (roomy & (cap >= mid)).sum(dtype=jnp.int32) >= count
            return (jnp.where(ok, mid, lo), jnp.where(ok, hi, mid - 1))

        floor, _ = lax.fori_loop(
            0, 32, floor_body, (jnp.int32(0), room * count))
        kept = roomy & (cap >= floor)
        return eligible & (kept | ~holds | (cand_key < 0))
    rows = lax.iota(jnp.uint32, n)
    node_key = ((rows * jnp.uint32(candidates.ROW_HASH))
                >> (32 - candidates.KEY_BITS)).astype(jnp.int32)
    retry = cand_key >= candidates.RETRY
    diff = node_key ^ (jnp.maximum(cand_key, 0) & (candidates.RETRY - 1))
    # Level l keeps the nodes whose key shares its top l bits with the
    # evaluation's: diff < 2^(KEY_BITS - l). One masked sum a level.
    widths = jnp.asarray(candidates.level_widths(bits), dtype=jnp.int32)
    inside = (diff[:, None] < widths[None, :]) & (cap[:, None] > 0)
    nodes = jnp.maximum(inside.sum(axis=0, dtype=jnp.int32), 1)
    share = jnp.maximum((count + nodes - 1) // nodes, 1)    # L, a level
    shared = (jnp.arange(bits + 1) < bits) | retry
    inside = inside & (~shared[None, :]
                       | (cap[:, None] >= room * share[None, :]))
    held = jnp.where(inside, jnp.minimum(cap, count)[:, None], 0
                     ).sum(axis=0) >= count
    level = jnp.max(jnp.where(held, jnp.arange(bits + 1), -1))
    kept = jnp.take(inside, jnp.maximum(level, 0), axis=1)
    return eligible & (kept | (level < 0) | (cand_key < 0))


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
    cap = _copies_cap(total, used0, job_count0, tg_count0, bw_avail,
                      bw_used0, eligible, ask, bw_ask, count,
                      job_distinct, tg_distinct)

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
    cand_key: int = candidates.NO_KEY, scan_steps: int = 0,
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
    ``scan_steps`` asks the exact scan for the program of that many
    steps at the least (the inactive ones place nothing): a remainder
    then rides the program its whole group compiled.
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
            cand_key=cand_key, scan_steps=scan_steps,
        )

    import numpy as np

    # Water-fill solver: one dispatch + one transfer for the whole batch.
    # distinct_hosts needs no special-casing: capacity is clamped to one
    # copy on nodes without same-scope allocs, zero otherwise.
    fetch_counts = solve_counts_async(
        total, sched_cap, used0, job_count0, tg_count0, bw_avail, bw_used0,
        eligible, ask, bw_ask, count, penalty,
        job_distinct=job_distinct, tg_distinct=tg_distinct,
        cand_key=cand_key,
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
    cand_key: int = candidates.NO_KEY,
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
        cand_key=cand_key,
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
