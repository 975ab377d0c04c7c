"""The bytes and operations that one solve NEEDS, as functions of the
node bucket, the eval width and the count bucket alone, so that a
roofline share reads the same work whatever implements the kernel.

Per evaluation and padded node row a solve has to read the node's
capacity (4 x int32), its schedulable cpu/memory as floats (2 x float32),
its usage (4 x int32), the job's and the task group's alloc counts on it
(2 x int32), its bandwidth available and used (2 x int32) and its
eligibility (1 byte): 57 bytes. A water-fill writes one int32 count per
row; the exact greedy solve writes one int32 node index and one flag per
task of the count bucket. A stacked dispatch of width B does that B
times: each member may read another mirror generation. Operations: one
bin-packing score per row (two powers, their sum, the fit test: 16
operations), and for the greedy solve one comparison per row and pick.
"""

from __future__ import annotations

import json
import os
from typing import Dict, Optional

ROW_READ_BYTES = 4 * 4 + 2 * 4 + 4 * 4 + 2 * 4 + 2 * 4 + 1
SCORE_OPS = 16


def node_bucket(n: int) -> int:
    """The power-of-two row count (at least 8) that n nodes are padded to."""
    b = 8
    while b < n:
        b *= 2
    return b


def waterfill(node_bucket: int, width: int, count: int = 0) -> Dict[str, int]:
    rows = node_bucket * width
    return {"bytes": rows * (ROW_READ_BYTES + 4), "ops": rows * SCORE_OPS}


def greedy(node_bucket: int, width: int, count: int) -> Dict[str, int]:
    rows = node_bucket * width
    return {"bytes": rows * ROW_READ_BYTES + width * count * 5,
            "ops": rows * (SCORE_OPS + count)}


KERNELS = {"waterfill": waterfill, "greedy": greedy}


def peaks(device_kind: str) -> Dict[str, float]:
    with open(os.path.join(os.path.dirname(__file__), "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}")
    return table[device_kind]


def least_seconds(kernel: str, device_kind: str, node_bucket: int,
                  widths: Dict[int, int], count: int = 0) -> Optional[float]:
    """The least time the chip could take for ``widths[w]`` dispatches of
    each width w: the larger of bytes over peak bytes/s and operations
    over peak operations/s. None where there was no dispatch."""
    peak = peaks(device_kind)
    total = 0.0
    for width, n in widths.items():
        if n <= 0:
            continue
        need = KERNELS[kernel](node_bucket, int(width), count)
        total += n * max(need["bytes"] / peak["hbm_bytes_per_s"],
                         need["ops"] / peak["bf16_flops_per_s"])
    return total or None
