"""The candidate rule: which nodes one evaluation's solve may choose from.

The dense solve scores every node, so left alone every evaluation in
flight takes the same best fit: on a cell whose machines and tasks differ
in shape that is a nearly full or a small machine with room for one, the
plan pipeline refuses the losers, and after the reference's two (batch)
or five (service) attempts the job is left short. The reference's stack
keeps evaluations apart by ranking a shuffled sample of two or log2(n)
nodes (stack.go:94-121, scheduler/stack.py); this is what does it for
the dense solve, at a quality the sample cannot have.

- **Classes that do not overlap.** A node's class is a hash of its mirror
  row (``node_keys``), ``KEY_BITS`` bits of it. Level ``l`` splits the
  cell into ``2**l`` classes by the top ``l`` bits: every class of a level
  is the union of two of the next, level 0 is the whole cell, and the
  finest level (``class_bits``) keeps classes of sixteen padded rows or
  ``2**KEY_BITS`` classes, whichever is coarser.
- **An evaluation's key** (``draw_key``) names its class at every level.
  The first attempt takes it from the raft index that orders the
  evaluation among its neighbours (its job's modify index) by an odd
  multiplier modulo ``2**KEY_BITS`` (``index_key``): a bijection, so
  evaluations whose indexes lie within 256 of each other never share a
  finest class, and the evaluations in flight at one time are such
  neighbours in the broker's queue; and the step that keeps neighbours
  furthest apart at the coarser levels too, whatever stride the log's
  other entries (the evaluation, its plan, its end) give their indexes:
  of the 128 odd steps it is the one under which sixteen indexes a
  stride of 1 to 6 apart always fall into 13 or more of level 4's
  sixteen classes. A draw from the
  evaluation's seeded stream would put two of sixteen in flight into one
  of 256 classes in one case out of three (1 - exp(-120/256)). An attempt
  after a refused plan, and an evaluation with no index, draw from
  ``ctx.prng``: fresh candidates, not the same argmax.
- **The exact scan: the finest level that holds the group.** A solve of
  128 copies at the most packs best fit into the fullest node, where two
  evaluations cannot both land. It runs over the evaluation's own class,
  at the finest level that holds every copy asked for (ops/binpack.py
  ``restrict_to_candidates``, inside the dispatch's one program); where
  no level does, over every eligible node, so no group is left short that
  the whole cell could hold. Its finest class is its own to pack. A
  coarser class has other evaluations' classes inside it, and a drawn
  key's class may be anybody's: of those it keeps the ROOMY nodes alone,
  the ones with room for ``HEADROOM`` x L copies (L = ceil(count /
  nodes), the group's even share), so that what another evaluation puts
  there in the same instant still fits.
- **The water-fill: the roomy nodes.** A larger group spreads, L =
  ceil(count / nodes) copies a node and one more on the best of them,
  over more machines than any class that evaluations in flight could
  have to themselves. What it is kept from is the nearly full machine:
  it runs over the nodes with room for ``HEADROOM`` x L copies, so that
  another evaluation's share still fits beside its own, and, where there
  are more of those than copies, over the ``count`` roomiest of them (one
  copy a node): the exact scans pack the fullest machine of their class,
  the water-fills sprinkle the emptiest of the cell, and the two meet
  only on a cell that is empty anyway. Where the roomy nodes do not hold
  the group (a cell near full), it runs over every eligible node, a copy
  or two a node as it always did. On a cell of one node shape and one
  task shape every node is as roomy as the next, and nothing changes.

Everything here is integer arithmetic on the host, numpy at the most: it
is the oracle the device's mask is compared with, decision for decision
(tests/test_candidates.py), and a host-backend server imports it without
importing jax.
"""

from __future__ import annotations

from functools import lru_cache
from typing import List

import numpy as np

KEY_BITS = 8
# 2**32 / golden ratio, odd: consecutive rows land far apart, and no
# stride of the fleet (every 16th node a variant, shapes dealt in runs)
# lines up with a class.
ROW_HASH = 0x9E3779B1
# Odd, so a bijection on the keys; which odd, by exhaustive search
# (tests/test_candidates.py holds the property).
INDEX_STEP = 197
# The finest class keeps this many padded rows.
MIN_CLASS_ROWS = 16
# A node is roomy where it has room for this many times the copies a
# solve would give it.
HEADROOM = 4
PRNG_STREAM = "solver.candidates"
NO_KEY = -1
# Added to a drawn key: no class of it is the evaluation's own.
RETRY = 1 << KEY_BITS


def class_bits(n_padded: int) -> int:
    """Levels below the whole cell for a node bucket of ``n_padded``."""
    rows = max(int(n_padded), 1) // MIN_CLASS_ROWS
    return max(0, min(KEY_BITS, rows.bit_length() - 1))


def level_widths(bits: int) -> List[int]:
    """``widths[l]``: a node is in the evaluation's class of level ``l``
    iff ``node_key ^ key < widths[l]``."""
    return [1 << (KEY_BITS - level) for level in range(bits + 1)]


@lru_cache(maxsize=8)
def node_keys(n_padded: int) -> np.ndarray:
    """int32[n_padded], read-only: every row's key, as the device
    computes it."""
    rows = np.arange(n_padded, dtype=np.uint64)
    keys = (((rows * ROW_HASH) & 0xFFFFFFFF)
            >> (32 - KEY_BITS)).astype(np.int32)
    keys.setflags(write=False)
    return keys


def index_key(index: int) -> int:
    """The key of a raft index: ``INDEX_STEP`` steps around the ring of
    keys, one to one on any ``2**KEY_BITS`` consecutive indexes."""
    return (index * INDEX_STEP) & ((1 << KEY_BITS) - 1)


def draw_key(ctx) -> int:
    """The key of ``ctx``'s evaluation at its current attempt: its
    index's, or one drawn from its seeded stream and marked ``RETRY``."""
    if ctx.attempt == 0 and ctx.eval_index > 0:
        return index_key(ctx.eval_index)
    return RETRY | ctx.prng(
        f"{PRNG_STREAM}.{ctx.attempt}").getrandbits(KEY_BITS)


def level_of(n_padded: int, key: int, placed_rows: np.ndarray) -> int:
    """The finest level whose class of ``key`` holds every placed row
    (``class_bits`` where nothing was placed outside the first class)."""
    bits = class_bits(n_padded)
    if key < 0 or bits == 0 or len(placed_rows) == 0:
        return bits
    diff = int((node_keys(n_padded)[placed_rows] ^ (key & (RETRY - 1))).max())
    return max(0, min(bits, KEY_BITS - diff.bit_length()))


def oracle_mask(cap: np.ndarray, key: int, count: int,
                spread: bool) -> np.ndarray:
    """bool[N]: the nodes ``restrict_to_candidates`` leaves eligible
    among the eligible, from the per-node capacity in copies (0 where
    ineligible; not clipped) it starts from. ``spread`` names the
    program family: the water-fill (True) or the exact scan."""
    n = len(cap)
    everything = np.ones(n, dtype=bool)
    if key < 0:
        return everything
    cap = np.clip(cap.astype(np.int64), 0, HEADROOM * count)

    def roomy(inside):
        share = -(-count // max(int(inside.sum()), 1))
        return inside & (cap >= HEADROOM * max(share, 1))

    def holds(inside):
        return int(np.minimum(cap, count)[inside].sum()) >= count

    if spread:
        kept = roomy(cap > 0)
        if not holds(kept):
            return everything
        if int(kept.sum()) <= count:
            return kept
        floor = np.sort(cap[kept])[-count]      # the count-th roomiest
        return kept & (cap >= floor)
    bits = class_bits(n)
    if bits == 0:
        return everything
    diff = node_keys(n) ^ (key & (RETRY - 1))
    kept = everything
    for level, width in enumerate(level_widths(bits)):
        inside = (diff < width) & (cap > 0)
        if level < bits or key >= RETRY:
            inside = roomy(inside)
        if holds(inside):
            kept = inside
    return kept
