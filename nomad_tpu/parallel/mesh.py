"""Mesh construction + sharded batched solve.

The batched eval solve is the device analog of Nomad's optimistic
concurrency (plan verification still serializes at plan-apply,
/root/reference/nomad/plan_apply.go:39-117): B coalesced evaluations solve
independently against the same state snapshot, vmapped over the eval axis,
while the node axis is sharded across chips. Conflicts between evals in a
batch surface exactly where they do in the reference — at plan apply, via
RefreshIndex retries.
"""

from __future__ import annotations

import logging
import os
import threading
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from nomad_tpu.ops.binpack import solve_greedy
from nomad_tpu.parallel.mesh_config import SolverMeshConfig

EVAL_AXIS = "evals"
NODE_AXIS = "nodes"

logger = logging.getLogger("nomad_tpu.parallel")


def make_mesh(
    n_devices: Optional[int] = None, eval_parallel: int = 1
) -> Mesh:
    """Build a 2D (evals, nodes) mesh over the available devices."""
    devices = jax.devices()
    if n_devices is not None:
        devices = devices[:n_devices]
    n = len(devices)
    if n % eval_parallel != 0:
        raise ValueError(f"{n} devices not divisible by eval_parallel={eval_parallel}")
    arr = np.array(devices).reshape(eval_parallel, n // eval_parallel)
    return Mesh(arr, (EVAL_AXIS, NODE_AXIS))


# ---------------------------------------------------------------------------
# Production node-axis sharding.
#
# When a mesh is configured (explicitly or via NOMAD_TPU_NODE_SHARDS), the
# node-axis tensors of every production solve — the water-fill kernels that
# carry the 10k-node x 100k-task load, and the mirror tensors they read —
# are placed with NamedShardings over the NODE_AXIS. jit then compiles the
# same kernels SPMD: the binary-search sum and the partial-round top-k
# become XLA collectives over ICI (psum / all-gather of shard maxima), with
# no kernel changes. This is the blueprint's scale axis (SURVEY.md §7
# "blockwise/sharded masking and top-k over the node axis, pjit-sharded
# across ICI"; the reference's analogous scale bound is the candidate scan,
# /root/reference/scheduler/stack.go:94-121).

_mesh_lock = threading.Lock()
_configured_mesh: Optional[Mesh] = None
_env_checked = False

# Sharding-path observability: tests assert on these so a regression that
# starts resharding mirror tensors per dispatch (instead of reading them
# born-sharded) fails loudly rather than silently costing a cross-shard
# transfer per solve. node_puts: tensors placed sharded at birth;
# node_reshards: node-axis tensors that arrived at dispatch with the WRONG
# sharding (should stay 0 on the warm path); replications: small per-eval
# scalars/vectors copied to every device (bounded per dispatch).
STATS = {"node_puts": 0, "node_reshards": 0, "replications": 0}


def reset_stats() -> None:
    for key in STATS:
        STATS[key] = 0


def configure_node_sharding(
    n_devices: Optional[int] = None, eval_parallel: int = 1
) -> Mesh:
    """Shard all subsequent production solves over a device mesh. The node
    axis extent must be a power of two (node tensors are padded to
    power-of-two buckets, ops/binpack.py bucket())."""
    global _configured_mesh
    mesh = make_mesh(n_devices, eval_parallel=eval_parallel)
    node_extent = mesh.shape[NODE_AXIS]
    if node_extent & (node_extent - 1):
        raise ValueError(
            f"node axis extent {node_extent} is not a power of two; node "
            "tensors are padded to power-of-two buckets and must divide"
        )
    with _mesh_lock:
        _configured_mesh = mesh
    return mesh


def clear_node_sharding() -> None:
    global _configured_mesh
    with _mesh_lock:
        _configured_mesh = None


def node_sharding_mesh() -> Optional[Mesh]:
    """The configured solve mesh, or None (single-device dispatch).

    First call honors NOMAD_TPU_NODE_SHARDS=<k>: shard over the first k
    local devices (k a power of two)."""
    global _env_checked, _configured_mesh
    with _mesh_lock:
        if _configured_mesh is not None:
            return _configured_mesh
        if _env_checked:
            return None
        _env_checked = True
    k = int(os.environ.get("NOMAD_TPU_NODE_SHARDS", "0") or 0)
    if k > 1:
        try:
            return configure_node_sharding(k)
        except Exception as e:
            logger.warning(
                "NOMAD_TPU_NODE_SHARDS=%d not usable (%s); solves stay "
                "single-device", k, e,
            )
    return None


def mesh_for_nodes(n: int) -> Optional[Mesh]:
    """The configured mesh if the padded node-axis length ``n`` divides
    evenly over it, else None (single-device dispatch). Small clusters on
    big meshes — a padded bucket shorter than the node-axis extent — fall
    back rather than crash every solve."""
    mesh = node_sharding_mesh()
    if mesh is None or n % mesh.shape[NODE_AXIS] != 0:
        return None
    return mesh


def put_node_sharded(x, trailing_dims: int = 0):
    """Place one node-axis tensor ([N, ...]) on the configured mesh, or on
    the default device when no mesh is configured (or doesn't divide the
    padded length). The mirror uses this so node tensors are born sharded
    and dispatches pay no reshard."""
    n = np.shape(x)[0]
    mesh = mesh_for_nodes(n)
    if mesh is None:
        return jnp.asarray(x)
    STATS["node_puts"] += 1
    spec = P(NODE_AXIS, *(None,) * trailing_dims)
    return jax.device_put(x, NamedSharding(mesh, spec))


# Water-fill argument shardings, in solve_waterfill positional order:
# total[N,4], sched_cap[N,2], used0[N,4], job_count0[N], tg_count0[N],
# bw_avail[N], bw_used0[N], eligible[N], ask[D], bw_ask[].
WF_SPECS = (
    P(NODE_AXIS, None), P(NODE_AXIS, None), P(NODE_AXIS, None),
    P(NODE_AXIS), P(NODE_AXIS), P(NODE_AXIS), P(NODE_AXIS), P(NODE_AXIS),
    P(), P(),
)


def replicate_on_mesh(mesh: Mesh, *xs) -> tuple:
    """Replicate small tensors (asks, penalties, active masks) across the
    mesh so they can join sharded node tensors in one jit call."""
    sharding = NamedSharding(mesh, P())
    out = []
    for x in xs:
        if isinstance(x, jax.Array) and x.sharding == sharding:
            out.append(x)
        else:
            STATS["replications"] += 1
            out.append(jax.device_put(x, sharding))
    return tuple(out)


def shard_waterfill_args(mesh: Mesh, args10) -> tuple:
    """Place the 10 water-fill tensor args with node-axis shardings.

    Mirror tensors and per-eval usage are born sharded (put_node_sharded),
    so the node-axis args skip device_put entirely; anything arriving with
    the wrong sharding is counted in STATS["node_reshards"] — the guardrail
    tests hold that at zero on the warm path."""
    out = []
    for x, spec in zip(args10, WF_SPECS):
        target = NamedSharding(mesh, spec)
        if isinstance(x, jax.Array) and x.sharding == target:
            out.append(x)
            continue
        if spec and spec[0] == NODE_AXIS:
            STATS["node_reshards"] += 1
        else:
            STATS["replications"] += 1
        out.append(jax.device_put(x, target))
    return tuple(out)


def constrain_eval_stack(mesh: Mesh, stacked, specs) -> tuple:
    """Inside a jitted batched solve (ops/coalesce.py solve_*_rows): pin
    the eval-stacked [B, ...] tensors to their rows' shardings (``specs``,
    of WF_SPECS) with the eval axis over EVAL_AXIS when the mesh has one
    that divides B. The rows arrive node-sharded (shard_waterfill_args);
    this keeps the stack from gathering them."""
    b = stacked[0].shape[0]
    eval_axis = EVAL_AXIS if b % mesh.shape[EVAL_AXIS] == 0 else None
    return tuple(
        jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, P(eval_axis, *spec)))
        for x, spec in zip(stacked, specs)
    )


# Per-mesh jit cache for node-sharded helper programs (the mirror's
# delta scatters). Keyed by (mesh id, fn, out signature) and bounded:
# meshes are configured once per process in production, but tests
# configure/clear repeatedly and the stale jits would otherwise pile up.
_SHARDED_JIT_CACHE: dict = {}
_SHARDED_JIT_CAP = 64


def node_sharded_jit(fn, n: int, out_trailing: Tuple[int, ...]):
    """jit ``fn`` with every output's axis 0 pinned to the NODE_AXIS
    sharding (``out_trailing[i]`` = that output's trailing dims), or None
    when no mesh divides the padded length ``n`` — the caller then uses
    its plain single-device jit.

    This is what makes the mirror's row-sliced delta scatters mesh-aware:
    a scatter into a sharded buffer whose output sharding floats free
    would let GSPMD gather the whole node axis onto one device, and every
    later solve would pay a reshard (STATS['node_reshards'] counts those;
    the guardrail tests hold it at zero)."""
    mesh = mesh_for_nodes(n)
    if mesh is None:
        return None
    key = (id(mesh), fn, out_trailing)
    with _mesh_lock:
        jitted = _SHARDED_JIT_CACHE.get(key)
        if jitted is None:
            out_sh = tuple(
                NamedSharding(mesh, P(NODE_AXIS, *(None,) * t))
                for t in out_trailing
            )
            jitted = jax.jit(fn, out_shardings=out_sh)
            if len(_SHARDED_JIT_CACHE) >= _SHARDED_JIT_CAP:
                _SHARDED_JIT_CACHE.clear()
            _SHARDED_JIT_CACHE[key] = jitted
    return jitted


# ---------------------------------------------------------------------------
# The server-config face of the mesh: `server { solver_mesh { } }`.


def apply_solver_mesh(cfg: SolverMeshConfig, log=None) -> Optional[Mesh]:
    """Configure the process solve mesh from a parsed solver_mesh block.
    Transparent fallback: when the local device set can't satisfy the
    requested extents (a one-device box running a mesh-configured
    config), solves stay single-device and the server keeps running —
    the knob describes a capability, not a hard requirement."""
    log = log or logger
    if not cfg.enabled:
        return None
    needed = max(cfg.node_shards, 1) * cfg.eval_parallel
    n_local = len(jax.devices())
    if n_local < needed:
        log.warning(
            "solver_mesh wants %d device(s) (node_shards=%d x "
            "eval_parallel=%d) but only %d present; solves stay "
            "single-device", needed, cfg.node_shards, cfg.eval_parallel,
            n_local,
        )
        return None
    try:
        mesh = configure_node_sharding(
            needed, eval_parallel=cfg.eval_parallel
        )
    except Exception as e:
        log.warning("solver_mesh not usable (%s); solves stay "
                    "single-device", e)
        return None
    log.info("solver mesh configured: %s", dict(mesh.shape))
    return mesh


@partial(jax.jit, static_argnames=("k", "job_distinct", "tg_distinct"))
def _batched_solve(
    total, sched_cap, used0, job_count0, tg_count0, bw_avail, bw_used0,
    eligible, ask, bw_ask, active, penalty, k, job_distinct, tg_distinct,
):
    """vmap of the greedy scan over a batch of evals.

    Shared across the batch: node tensors (total, sched_cap, bw_avail).
    Per-eval: usage, counts, eligibility, ask — each eval solves against the
    same optimistic snapshot, like concurrent reference workers.
    """
    return jax.vmap(
        solve_greedy,
        in_axes=(None, None, 0, 0, 0, None, 0, 0, 0, 0, 0, 0, None, None, None),
    )(
        total, sched_cap, used0, job_count0, tg_count0, bw_avail, bw_used0,
        eligible, ask, bw_ask, active, penalty, k, job_distinct, tg_distinct,
    )


def shard_batched_inputs(mesh: Mesh, batch: dict) -> dict:
    """Place batched-solve inputs on the mesh: node-axis tensors sharded over
    NODE_AXIS, eval-axis tensors over EVAL_AXIS."""
    shardings = {
        # [N, D] node tensors: shard the node axis
        "total": NamedSharding(mesh, P(NODE_AXIS, None)),
        "sched_cap": NamedSharding(mesh, P(NODE_AXIS, None)),
        "bw_avail": NamedSharding(mesh, P(NODE_AXIS)),
        # [B, N(, D)] per-eval tensors: evals x nodes
        "used0": NamedSharding(mesh, P(EVAL_AXIS, NODE_AXIS, None)),
        "job_count0": NamedSharding(mesh, P(EVAL_AXIS, NODE_AXIS)),
        "tg_count0": NamedSharding(mesh, P(EVAL_AXIS, NODE_AXIS)),
        "bw_used0": NamedSharding(mesh, P(EVAL_AXIS, NODE_AXIS)),
        "eligible": NamedSharding(mesh, P(EVAL_AXIS, NODE_AXIS)),
        # [B, ...] small per-eval tensors: replicate over the node axis
        "ask": NamedSharding(mesh, P(EVAL_AXIS, None)),
        "bw_ask": NamedSharding(mesh, P(EVAL_AXIS)),
        "active": NamedSharding(mesh, P(EVAL_AXIS, None)),
        "penalty": NamedSharding(mesh, P(EVAL_AXIS)),
    }
    return {
        name: jax.device_put(value, shardings[name])
        for name, value in batch.items()
    }


def solve_batch_on_mesh(
    mesh: Mesh, batch: dict, k: int,
    job_distinct: bool = False, tg_distinct: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Run the batched greedy solve with mesh shardings; XLA inserts the
    cross-chip argmax collectives over the node axis.

    ``batch`` keys match shard_batched_inputs. Returns (idxs[B,k], oks[B,k],
    scores[B,k]).
    """
    placed = shard_batched_inputs(mesh, batch)
    with mesh:
        return _batched_solve(
            placed["total"], placed["sched_cap"], placed["used0"],
            placed["job_count0"], placed["tg_count0"], placed["bw_avail"],
            placed["bw_used0"], placed["eligible"], placed["ask"],
            placed["bw_ask"], placed["active"], placed["penalty"],
            k, job_distinct, tg_distinct,
        )


def make_tiny_batch(n_nodes: int, n_evals: int, k: int) -> dict:
    """Tiny well-formed inputs for compile checks and the multichip dryrun."""
    total = np.zeros((n_nodes, 4), dtype=np.int32)
    total[:, 0] = 4000
    total[:, 1] = 8192
    total[:, 2] = 100 * 1024
    total[:, 3] = 150
    sched_cap = total[:, :2].astype(np.float32)
    return {
        "total": jnp.asarray(total),
        "sched_cap": jnp.asarray(sched_cap),
        "bw_avail": jnp.full((n_nodes,), 1000, dtype=jnp.int32),
        "used0": jnp.zeros((n_evals, n_nodes, 4), dtype=jnp.int32),
        "job_count0": jnp.zeros((n_evals, n_nodes), dtype=jnp.int32),
        "tg_count0": jnp.zeros((n_evals, n_nodes), dtype=jnp.int32),
        "bw_used0": jnp.zeros((n_evals, n_nodes), dtype=jnp.int32),
        "eligible": jnp.ones((n_evals, n_nodes), dtype=bool),
        "ask": jnp.tile(
            jnp.array([500, 256, 0, 0], dtype=jnp.int32), (n_evals, 1)
        ),
        "bw_ask": jnp.zeros((n_evals,), dtype=jnp.int32),
        "active": jnp.ones((n_evals, k), dtype=bool),
        "penalty": jnp.full((n_evals,), 10.0, dtype=jnp.float32),
    }
