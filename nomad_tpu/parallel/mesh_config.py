"""The server-config face of the solve mesh: ``server { solver_mesh { } }``.

Kept apart from parallel/mesh.py (which imports jax) so that parsing a
server config — every ServerConfig does — stays jax-free and a
``scheduler_backend="host"`` server never imports it.
"""

from __future__ import annotations


class SolverMeshConfig:
    """Parsed ``server { solver_mesh { } }`` block: how many devices the
    node axis of every production solve shards over, and the eval-axis
    extent of the 2D mesh. Parse-time validated like admission/express —
    a typo'd knob fails config load, not leader-establish. The default
    (node_shards 0) keeps solves single-device; a mesh the local device
    set can't satisfy falls back transparently at apply time (scale-down
    of the same binary onto a smaller box must not crash the server)."""

    __slots__ = ("node_shards", "eval_parallel")

    _KEYS = ("node_shards", "eval_parallel")

    def __init__(self, node_shards: int = 0, eval_parallel: int = 1):
        self.node_shards = node_shards
        self.eval_parallel = eval_parallel

    @property
    def enabled(self) -> bool:
        return self.node_shards > 1 or self.eval_parallel > 1

    @classmethod
    def parse(cls, data) -> "SolverMeshConfig":
        if data is None:
            return cls()
        if not isinstance(data, dict):
            raise ValueError("server.solver_mesh must be a mapping")
        unknown = sorted(set(data) - set(cls._KEYS))
        if unknown:
            raise ValueError(
                f"unknown server.solver_mesh key(s) {unknown} "
                f"(have: {list(cls._KEYS)})"
            )
        out = {}
        for key, lo, hi in (("node_shards", 0, 4096),
                            ("eval_parallel", 1, 64)):
            v = data.get(key)
            if v is None:
                continue
            if (not isinstance(v, int) or isinstance(v, bool)
                    or not lo <= v <= hi):
                raise ValueError(
                    f"server.solver_mesh.{key} must be an integer in "
                    f"[{lo}, {hi}], got {v!r}"
                )
            if v > 1 and v & (v - 1):
                # Node tensors pad to power-of-two buckets; a non-power-
                # of-two extent could never divide them evenly.
                raise ValueError(
                    f"server.solver_mesh.{key} must be a power of two, "
                    f"got {v}"
                )
            out[key] = v
        return cls(out.get("node_shards", 0), out.get("eval_parallel", 1))

    def as_dict(self) -> dict:
        return {"node_shards": self.node_shards,
                "eval_parallel": self.eval_parallel}
