"""Reader ``setup``: what the program itself says of its set-up, read
from the process as it stands after the run (not differenced over the
window: set-up is over before the window opens).

source args: ``{"key": "device_acquire_s"}`` for the seconds
``acquire_device()`` took to reach the chip (``device_status()``), or
``{"key": "program_load_s"}`` for the seconds XLA spent compiling and
loading programs from the persistent cache over the whole process
(``SOLVER_PANEL``'s ``xla_compile_ms`` + ``xla_cache_load_ms``), which
is set-up's share as long as ``xla_compiles_in_window`` reads 0.

None in a process that never acquired a device, and with a program that
keeps no such record."""

from __future__ import annotations


def read(args, ctx):
    from nomad_tpu.scheduler import device_status

    status = device_status()
    if not status.get("acquired"):
        return None
    if args["key"] == "device_acquire_s":
        value = status.get("acquire_s")
        return None if value is None else float(value)
    if args["key"] == "program_load_s":
        from nomad_tpu.tpu.solver import SOLVER_PANEL

        panel = SOLVER_PANEL.snapshot()
        if "xla_compile_ms" not in panel:
            return None
        return (panel["xla_compile_ms"] + panel["xla_cache_load_ms"]) / 1000.0
    raise ValueError(f"reader setup: unknown key {args['key']!r}")
