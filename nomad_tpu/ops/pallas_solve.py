"""Pallas TPU kernel for the closed-form water-fill solve.

The jnp path (ops/binpack.py solve_waterfill) lowers as several XLA ops.
This kernel runs the ENTIRE water-fill — per-node capacity, the level
binary search, the BestFit score, and the top-k partial round — as one
VMEM-resident program per eval:

- The node axis is laid out DENSE: a node vector [N] becomes an
  [N/128, 128] tile grid (lanes x sublanes both full), so a 16,384-node
  bucket costs 64 KiB per i32 vector and the whole working set of one
  eval is ~1 MiB; HBM is read once and the bisections' reductions all hit
  on-chip memory.
- The top-k is a rank-space binary search over the monotone uint32 image
  of the float32 scores (32 fixed VPU passes), with ties broken by
  ascending node index exactly like the jnp path.
- Per-eval scalars (ask, bandwidth ask, count, penalty) are whole-array
  SMEM operands indexed by ``pl.program_id(0)``: a (1, ·) SMEM block over
  the eval axis does not lower for B > 1.

The kernel grids over the eval axis — each grid step solves one eval of
the coalesced batch, so K in-flight evals still cost one dispatch. There
is no single-eval wrapper: the coalescer's jitted entry
(ops/coalesce.py solve_waterfill_rows) stacks the riders' rows and traces
this kernel inside the same program, a lone eval being B = 1.

Semantics are those of solve_waterfill (differential-tested in interpret
mode in tests/test_pallas_solve.py, lowered for TPU without a device in
tests/test_pallas_lowering.py; on the chip it is what every drain cell
of the benchmark runs, with ``correct`` decided against
benchmark/reference.py). Reference semantics: AllocsFit/ScoreFit
(/root/reference/nomad/structs/funcs.go:44-124) and the Select loop it
reformulates (/root/reference/scheduler/stack.go:131-159).
"""

from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp

# jax.experimental.pallas costs >1s to import; it is pulled in lazily at
# first trace (inside solve_waterfill_pallas_batched) so control-plane
# startup and CPU-only deployments never pay for it.

# Python scalars, not jnp values: the kernel must not capture traced
# constants (pallas requires closures to be static).
_BIG = 2**30
_NEG_INF = float("-inf")

_LANES = 128
# Node axis is padded (ineligible rows) to whole (8, 128) i32/f32 tiles.
_NODE_TILE = 8 * _LANES

# Largest node bucket the kernel is selected for. One eval's blocks are
# 16 dense [N] vectors of 4 bytes, double-buffered by the grid pipeline,
# plus about a dozen live temporaries: under 256 B/node, which is what
# the call asks Mosaic for — 32 MiB at the 131,072 bucket, the largest
# that was compiled and compared with the jnp water-fill on a chip
# (PR 21; no benchmark cell reaches it: ROADMAP C4), against a v5e's
# 128 MiB of VMEM. Larger buckets take the jnp water-fill.
PALLAS_MAX_NODES = 131072

# Shared with the jnp water-fill's partial round: ONE definition of the
# order-preserving float->uint32 map, so the kernel's and the jnp path's
# kth-largest selections can never drift on key semantics. (binpack has
# no module-level import of this package, so no cycle.)
from nomad_tpu.ops.binpack import _monotone_u32  # noqa: E402


def _count_true(mask):
    return jnp.sum(mask.astype(jnp.int32))


def _waterfill_kernel(
    # SMEM, whole arrays, row = eval
    ask_ref,       # (B, D) i32
    bw_ask_ref,    # (B,) i32
    count_ref,     # (B,) i32
    penalty_ref,   # (B,) f32
    # VMEM blocks of one eval; node axis dense over (R, 128)
    total_ref,     # (1, D, R, 128) i32
    used_ref,      # (1, D, R, 128) i32
    sched_cap_ref, # (1, 2, R, 128) f32
    jc_ref,        # (1, R, 128) i32
    tc_ref,        # (1, R, 128) i32
    bw_avail_ref,  # (1, R, 128) i32
    bw_used_ref,   # (1, R, 128) i32
    elig_ref,      # (1, R, 128) i32 (0/1)
    # output
    counts_ref,    # (1, R, 128) i32
    *, d_res: int, job_distinct: bool, tg_distinct: bool,
):
    from jax.experimental import pallas as pl

    b = pl.program_id(0)
    count = count_ref[b]
    bw_ask = bw_ask_ref[b]
    penalty = penalty_ref[b]

    elig = elig_ref[0] != 0
    jc = jc_ref[0]
    tc = tc_ref[0]
    bw_avail = bw_avail_ref[0]
    bw_used = bw_used_ref[0]

    # -- per-node capacity in copies of this ask (binpack.py cap block) --
    shape = jc.shape
    cap = jnp.full(shape, _BIG, dtype=jnp.int32)
    nonneg = jnp.ones(shape, dtype=jnp.bool_)
    for d in range(d_res):
        a = ask_ref[b, d]
        avail_d = total_ref[0, d] - used_ref[0, d]
        nonneg = nonneg & (avail_d >= 0)
        dim_cap = avail_d // jnp.maximum(a, 1)
        cap = jnp.where(a > 0, jnp.minimum(cap, dim_cap), cap)
    bw_free = bw_avail - bw_used
    nonneg = nonneg & (bw_free >= 0)
    bw_cap = jnp.where(bw_ask > 0, bw_free // jnp.maximum(bw_ask, 1), _BIG)
    cap = jnp.minimum(cap, bw_cap)
    if job_distinct:
        cap = jnp.minimum(cap, jnp.where(jc == 0, 1, 0))
    if tg_distinct:
        cap = jnp.minimum(cap, jnp.where(tc == 0, 1, 0))
    cap = jnp.where(elig & nonneg, jnp.clip(cap, 0, count), 0)

    # -- largest L with sum(min(cap, L)) <= count: 32-step bisection ----
    def bs_body(_, lohi):
        lo, hi = lohi
        mid = lo + (hi - lo + 1) // 2
        ok = jnp.sum(jnp.minimum(cap, mid)) <= count
        return (jnp.where(ok, mid, lo), jnp.where(ok, hi, mid - 1))

    level, _ = jax.lax.fori_loop(0, 32, bs_body, (jnp.int32(0), count))
    base = jnp.minimum(cap, level)
    remaining = count - jnp.sum(base)

    # -- partial round: score nodes with headroom (binpack.py
    #    _greedy_step_state on the post-base utilization) --------------
    fit = elig
    for d in range(d_res):
        a = ask_ref[b, d]
        used_b = used_ref[0, d] + base * a
        fit = fit & (used_b + a <= total_ref[0, d])
    fit = fit & ((bw_used + base * bw_ask + bw_ask) <= bw_avail)
    if job_distinct:
        fit = fit & ((jc + base) == 0)
    if tg_distinct:
        fit = fit & ((tc + base) == 0)

    score_acc = jnp.zeros(shape, dtype=jnp.float32)
    for d in range(2):
        scap = sched_cap_ref[0, d]
        a = ask_ref[b, d]
        used_b = (used_ref[0, d] + (base + 1) * a).astype(jnp.float32)
        free = 1.0 - used_b / jnp.maximum(scap, 1.0)
        free = jnp.where(scap > 0, free, _NEG_INF)
        score_acc = score_acc + jnp.power(10.0, free)
    score = jnp.clip(20.0 - score_acc, 0.0, 18.0)
    score = score - penalty * (jc + base).astype(jnp.float32)
    score = jnp.where(fit, score, _NEG_INF)

    candidates = fit & (cap > level)

    # -- top-`remaining` by score among candidates, ties by ascending
    #    node index (the stable-argsort order of the jnp path) ----------
    u = jnp.where(candidates, _monotone_u32(score), jnp.uint32(0))

    def kth_body(_, lohi):
        lo, hi = lohi
        mid = lo + (hi - lo + 1) // 2
        ok = _count_true(candidates & (u >= mid)) >= remaining
        return (jnp.where(ok, mid, lo), jnp.where(ok, hi, mid - 1))

    # hi starts at 0xFFFFFFFE, not 0xFFFFFFFF: real scores never map to
    # the all-ones image (that is a positive-NaN), and a full-range start
    # would overflow (hi - lo + 1) to zero on the first midpoint.
    thresh, _ = jax.lax.fori_loop(
        0, 32, kth_body, (jnp.uint32(0), jnp.uint32(0xFFFFFFFE)),
    )
    above = candidates & (u > thresh)
    boundary = candidates & (u == thresh)
    fill = remaining - _count_true(above)
    # First-`fill` boundary nodes by ascending node index. A prefix-cut
    # bisection, not a cumsum (Pallas TPU has no cumsum lowering):
    # count(boundary & idx < m) is monotone in m, so the largest prefix
    # holding <= fill boundary nodes selects exactly min(fill, |boundary|)
    # of them in index order.
    n = shape[0] * shape[1]
    idx = (jax.lax.broadcasted_iota(jnp.int32, shape, 0) * shape[1]
           + jax.lax.broadcasted_iota(jnp.int32, shape, 1))

    def tie_body(_, lohi):
        lo, hi = lohi
        mid = lo + (hi - lo + 1) // 2
        ok = _count_true(boundary & (idx < mid)) <= fill
        return (jnp.where(ok, mid, lo), jnp.where(ok, hi, mid - 1))

    cut, _ = jax.lax.fori_loop(
        0, 32, tie_body, (jnp.int32(0), jnp.int32(n)),
    )
    selected = above | (boundary & (idx < cut))
    selected = selected & (remaining > 0)

    counts_ref[0] = base + selected.astype(jnp.int32)


@partial(
    jax.jit,
    static_argnames=("job_distinct", "tg_distinct", "interpret"),
)
def solve_waterfill_pallas_batched(
    total,       # [B, N, D] i32
    sched_cap,   # [B, N, 2] f32
    used0,       # [B, N, D] i32
    job_count0,  # [B, N] i32
    tg_count0,   # [B, N] i32
    bw_avail,    # [B, N] i32
    bw_used0,    # [B, N] i32
    eligible,    # [B, N] bool
    ask,         # [B, D] i32
    bw_ask,      # [B] i32
    count,       # [B] i32
    penalty,     # [B] f32
    job_distinct: bool,
    tg_distinct: bool,
    interpret: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Batched water-fill, one grid step per eval: returns (counts
    [B, N], remaining [B]). Traced inside coalesce.solve_waterfill_rows,
    which stacks the riders' rows (a lone eval is B = 1) in the same
    program; nothing calls it eagerly on the dispatch path."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, n, d_res = total.shape
    # Pad the node axis with ineligible rows to whole tiles: they have
    # capacity 0 and the highest indices, so they change no selection.
    n_pad = -(-n // _NODE_TILE) * _NODE_TILE
    rows = n_pad // _LANES

    def dense(v):
        # [B, N, ...] -> [B, ..., R, 128], node axis minor.
        v = jnp.pad(v, ((0, 0), (0, n_pad - n)) + ((0, 0),) * (v.ndim - 2))
        v = jnp.moveaxis(v, 1, -1)
        return v.reshape(v.shape[:-1] + (rows, _LANES))

    smem = pl.BlockSpec(memory_space=pltpu.SMEM)

    def per_eval(*dims):
        shape = (1,) + dims + (rows, _LANES)
        return pl.BlockSpec(
            shape, lambda i: (i,) + (0,) * (len(shape) - 1),
            memory_space=pltpu.VMEM,
        )

    kernel = partial(
        _waterfill_kernel, d_res=d_res,
        job_distinct=job_distinct, tg_distinct=tg_distinct,
    )
    # Blocks (double-buffered) + temporaries, see PALLAS_MAX_NODES; never
    # below the compiler's own 16 MiB default.
    vmem_bytes = max(16 << 20, 256 * n_pad)
    counts = pl.pallas_call(
        kernel,
        grid=(b,),
        in_specs=[
            smem, smem, smem, smem,      # ask, bw_ask, count, penalty
            per_eval(d_res),             # total
            per_eval(d_res),             # used
            per_eval(2),                 # sched_cap
            per_eval(),                  # job_count
            per_eval(),                  # tg_count
            per_eval(),                  # bw_avail
            per_eval(),                  # bw_used
            per_eval(),                  # eligible
        ],
        out_specs=per_eval(),
        out_shape=jax.ShapeDtypeStruct((b, rows, _LANES), jnp.int32),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=vmem_bytes),
        interpret=interpret,
    )(
        ask.astype(jnp.int32),
        bw_ask.astype(jnp.int32),
        count.astype(jnp.int32),
        penalty.astype(jnp.float32),
        dense(total), dense(used0), dense(sched_cap),
        dense(job_count0), dense(tg_count0),
        dense(bw_avail), dense(bw_used0),
        dense(eligible.astype(jnp.int32)),
    )
    counts = counts.reshape(b, n_pad)[:, :n]
    return counts, count.astype(jnp.int32) - counts.sum(axis=-1)


def selected(n_padded: int) -> bool:
    """Whether the coalescer dispatches this kernel for a node bucket:
    on a TPU backend, up to the largest bucket it is sized for. Decided
    before dispatch from what the process can observe; a kernel that then
    fails raises to the eval like any other device error."""
    return jax.default_backend() == "tpu" and n_padded <= PALLAS_MAX_NODES
