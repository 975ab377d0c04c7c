"""Scheduler package: pure placement logic behind a Factory registry.

Mirrors the reference seam (/root/reference/scheduler/scheduler.go:13-87):
schedulers are constructed by name from ``BUILTIN_SCHEDULERS``, receive an
immutable ``State`` view and a ``Planner``, and process one Evaluation at a
time. The TPU solver registers here as additional factories
(``tpu-service``/``tpu-batch`` and the coalescing batch dispatcher), so the
control plane dispatches evals to it without knowing about devices.
"""

from __future__ import annotations

import logging
from typing import Callable, Dict, Optional, Protocol, Tuple

from nomad_tpu.structs import Evaluation, Plan, PlanResult


class SchedulerError(Exception):
    pass


class SetStatusError(SchedulerError):
    """Processing failed and the eval should be moved to ``eval_status``
    (reference: generic_sched.go:32-40)."""

    def __init__(self, err: str, eval_status: str):
        super().__init__(err)
        self.eval_status = eval_status


class State(Protocol):
    """Immutable view of global state (reference: scheduler/scheduler.go:55-71)."""

    def nodes(self): ...
    def allocs_by_job(self, job_id: str): ...
    def allocs_by_node(self, node_id: str): ...
    def node_by_id(self, node_id: str): ...
    def job_by_id(self, job_id: str): ...


class Planner(Protocol):
    """Plan submission interface (reference: scheduler/scheduler.go:74-87)."""

    def submit_plan(self, plan: Plan) -> Tuple[PlanResult, Optional[State]]: ...
    def update_eval(self, ev: Evaluation) -> None: ...
    def create_eval(self, ev: Evaluation) -> None: ...


class Scheduler(Protocol):
    def process(self, ev: Evaluation) -> None: ...


Factory = Callable[[State, Planner, logging.Logger], Scheduler]

BUILTIN_SCHEDULERS: Dict[str, Factory] = {}


def register(name: str, factory: Factory) -> None:
    BUILTIN_SCHEDULERS[name] = factory


def new_scheduler(
    name: str,
    state: State,
    planner: Planner,
    logger: Optional[logging.Logger] = None,
) -> Scheduler:
    """Instantiate a scheduler by name (reference: scheduler.go:19-31)."""
    factory = BUILTIN_SCHEDULERS.get(name)
    if factory is None:
        raise SchedulerError(f"unknown scheduler '{name}'")
    return factory(state, planner, logger or logging.getLogger("nomad_tpu.sched"))


# ---------------------------------------------------------------------------
# Device acquisition: one process, in process, once.
#
# A chip belongs to one process at a time. The server that schedules on it
# claims it itself — Server.start() calls acquire_device() before any
# worker exists, so every worker sees the same device from its first eval
# — and a process that cannot claim one does not start. Nothing here
# routes to the host scheduler because a device is missing:
# scheduler_backend="host" is how an operator asks for that, and it never
# imports jax.

import os as _os
import threading as _threading
import time as _time

from nomad_tpu.backoff import CircuitBreaker

# Device circuit breaker: after N consecutive DEVICE errors mid-solve
# (XLA faults or injected solver.execute faults — counted by
# tpu/solver.py around each dispatch), the scheduler factory stops
# routing evals to the device and takes the host-oracle CPU path (same
# placements, scalar speed) instead of failing every eval into the
# broker's nack/delivery-limit reaper. After the cooldown, ONE half-open
# probe eval rides the device path again: success closes the breaker,
# failure re-opens it with a doubled cooldown. Transitions are visible in
# /v1/agent/metrics (solver.breaker.to_open / to_half_open / to_closed
# counters + solver.breaker.state gauge) and in solver_stats().
DEVICE_BREAKER = CircuitBreaker(
    threshold=int(_os.environ.get("NOMAD_TPU_BREAKER_THRESHOLD", "3")),
    cooldown=float(_os.environ.get("NOMAD_TPU_BREAKER_COOLDOWN", "15")),
    name=("solver", "breaker"),
)

_device_lock = _threading.Lock()
_device: Optional[Dict[str, object]] = None


def configure_compile_cache(platform: str) -> Optional[str]:
    """Place JAX's persistent compilation cache for a process that holds
    ``platform``; returns its directory, or None where there is none.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX's own handling of it
    stands and nothing is set in code. Otherwise an accelerator's cache
    lives at ``<checkout>/.jax_cache`` — a fixed path derived from the
    package's location, because the path is part of the cache key and a
    directory that moves never hits — and keeps every program however
    quickly it compiled (the solver's programs are small and there are
    dozens). The CPU backend gets none: XLA:CPU's loader distrusts its own
    cached executables (a machine-feature check that warns of SIGILL on
    every hit) and the chip path gains nothing from them.
    """
    placed = _os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    if platform == "cpu":
        return None
    import jax

    path = _os.path.join(
        _os.path.dirname(_os.path.dirname(_os.path.dirname(
            _os.path.abspath(__file__)))),
        ".jax_cache",
    )
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path


# XLA's own events (jax.monitoring, jax 0.9): the first wraps
# compile_or_get_cached, so it fires for a backend compile AND for a load
# from the persistent cache; the second fires first, on the same thread,
# only when the cache served the program.
XLA_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
XLA_CACHE_LOAD_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
_xla_tls = _threading.local()
_xla_listening = False


def _on_xla_duration(event: str, duration: float, **_kwargs) -> None:
    """jax.monitoring duration listener: counts every program XLA compiles
    or loads from the persistent cache, process-wide, into SOLVER_PANEL
    (``xla_compiles`` / ``xla_cache_loads`` and their milliseconds), and
    notes a compile on the span active on the compiling thread."""
    from nomad_tpu import trace
    from nomad_tpu.tpu.solver import SOLVER_PANEL

    if event == XLA_CACHE_LOAD_EVENT:
        _xla_tls.loaded_at = _time.perf_counter()
        SOLVER_PANEL.record_xla(duration, loaded=True)
    elif event == XLA_COMPILE_EVENT:
        loaded_at = getattr(_xla_tls, "loaded_at", None)
        _xla_tls.loaded_at = None
        # The load's own enclosing event spans the load. A stamp from
        # before it began is a load whose enclosing event never fired
        # here, and this is a compile.
        if (loaded_at is not None
                and loaded_at >= _time.perf_counter() - duration - 0.001):
            return
        SOLVER_PANEL.record_xla(duration, loaded=False)
        span = trace.current_span()
        if span is not None:
            span.annotate("compiled", True)


def acquire_device() -> Dict[str, object]:
    """Claim the accelerator for THIS process and return what JAX reports
    (platform, device_kind, count), where its compile cache lives, and how
    long reaching the device took (``acquire_s``: importing jax, backend
    initialization, the first ``jax.devices()``).
    The first call initializes the JAX backend — whatever
    ``jax.devices()`` raises propagates, so a server without a device
    fails to start instead of scheduling on the host — places the compile
    cache before anything compiles, imports the solver stack, so a
    broken import fails the start and not the first eval, and registers
    the listener for XLA's compile events. Later calls return the same
    record."""
    global _device, _xla_listening
    with _device_lock:
        if _device is None:
            t0 = _time.perf_counter()
            import jax

            devices = jax.devices()
            acquire_s = _time.perf_counter() - t0
            platform = devices[0].platform
            cache_dir = configure_compile_cache(platform)
            from nomad_tpu.tpu import solver  # noqa: F401

            if not _xla_listening:  # once a process, whatever resets _device
                jax.monitoring.register_event_duration_secs_listener(
                    _on_xla_duration)
                _xla_listening = True
            _device = {
                "platform": platform,
                "device_kind": devices[0].device_kind,
                "count": len(devices),
                "compile_cache": cache_dir,
                "acquire_s": round(acquire_s, 4),
            }
        return dict(_device)


def device_status() -> Dict[str, object]:
    """The device this process holds, for Stats()/agent-info: platform,
    device kind, count, compile-cache directory and ``acquire_s``, or
    ``{"acquired": False}`` in a process that has not claimed one
    (scheduler_backend="host")."""
    with _device_lock:
        if _device is None:
            return {"acquired": False}
        return {"acquired": True, **_device}


def _register_builtins() -> None:
    from nomad_tpu.scheduler.generic import new_batch_scheduler, new_service_scheduler
    from nomad_tpu.scheduler.system import new_system_scheduler

    register("service", new_service_scheduler)
    register("batch", new_batch_scheduler)
    register("system", new_system_scheduler)

    def _lazy_tpu(variant: str) -> Factory:
        def factory(state, planner, logger):
            if not DEVICE_BREAKER.allow():
                # Breaker open: the device is failing solves. Degrade to
                # the host oracle for this eval instead of burning one of
                # its delivery attempts on a dead device; allow() hands
                # the post-cooldown half-open probe to exactly one eval.
                from nomad_tpu import telemetry

                telemetry.incr_counter(
                    ("scheduler", "device", "breaker_fallback")
                )
                return BUILTIN_SCHEDULERS[variant](state, planner, logger)
            acquire_device()
            from nomad_tpu.tpu import solver

            return solver.new_tpu_scheduler(variant, state, planner, logger)

        return factory

    register("tpu-service", _lazy_tpu("service"))
    register("tpu-batch", _lazy_tpu("batch"))
    register("tpu-system", _lazy_tpu("system"))


_register_builtins()
