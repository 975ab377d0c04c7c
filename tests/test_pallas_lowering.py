"""Deviceless TPU lowering of the water-fill dispatch on the Pallas kernel.

``jax.export`` with ``platforms=["tpu"]`` runs the Pallas TPU lowering
rules — BlockSpec checks, memory spaces, every primitive's Mosaic rule —
without a device, so a kernel the installed JAX refuses is caught here on
the CPU-pinned suite. Interpret mode skips all of that: it accepted the
per-eval ``(1, ·)`` SMEM blocks that do not lower for B > 1. Whether the
Mosaic COMPILER then accepts the module only a chip can say: the
benchmark's water-fill cells run the kernel there (``benchmark/run.py``;
``jit_solve_waterfill_rows`` in the ledger's ``device_ops``), with
``correct`` decided against ``benchmark/reference.py``. What is lowered
is the program the coalescer dispatches (``solve_waterfill_rows``: the
riders' rows as they are, stacked inside it), not the kernel alone.
"""

import jax
import jax.numpy as jnp
import pytest

from nomad_tpu.ops.coalesce import MAX_BATCH_BUCKET, solve_waterfill_rows

NODE_BUCKET = 16384  # cell-10k: bucket(10_000)


def _arg_shapes(b, n, d=4):
    """(rows, counts, penalties) as a dispatch of width b hands them in."""
    S = jax.ShapeDtypeStruct
    i32, f32 = jnp.int32, jnp.float32
    row = (
        S((n, d), i32), S((n, 2), f32), S((n, d), i32),
        S((n,), i32), S((n,), i32), S((n,), i32), S((n,), i32),
        S((n,), jnp.bool_), S((d,), i32), S((), i32),
    )
    return (row,) * b, S((b,), i32), S((b,), f32)


@pytest.mark.parametrize("flags", [(False, False), (True, False),
                                   (False, True)],
                         ids=["plain", "job_distinct", "tg_distinct"])
@pytest.mark.parametrize("width", [1, 2, 4, 8])
def test_kernel_lowers_for_tpu_at_every_coalesced_width(width, flags):
    assert width <= MAX_BATCH_BUCKET
    jd, td = flags

    def solve(rows, counts, penalties):
        return solve_waterfill_rows(rows, counts, penalties, jd, td,
                                    kernel="pallas")

    exported = jax.export.export(jax.jit(solve), platforms=["tpu"])(
        *_arg_shapes(width, NODE_BUCKET))
    assert "tpu_custom_call" in exported.mlir_module()
    counts, remaining = exported.out_avals
    assert counts.shape == (width, NODE_BUCKET)
    assert remaining.shape == (width,)
