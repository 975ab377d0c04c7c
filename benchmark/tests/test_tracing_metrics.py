"""The per-layer metrics that read the program's stage cuts, front-door
span and XLA compile events (ISSUE 26), on the CPU against the rehearsal
configuration: a traced rehearsal run of each mix reports every one of
them as a finite number, and the ``setup`` reader reports nothing in a
process that holds no device.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""

from __future__ import annotations

import json
import math
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import run  # noqa: E402
from benchmark.readers import setup  # noqa: E402

SET_UP = {"setup_device_acquire_s", "setup_program_load_s"}
NEW = {
    "rehearsal-256.rehearsal-steady": SET_UP | {
        "frontdoor_register_mean_ms.steady",
        "staging_usage_base_mean_ms.steady",
        "staging_blocked_mean_ms.steady",
        "execute_hold_mean_ms.steady",
        "execute_launch_mean_ms.steady",
        "execute_wake_mean_ms.steady",
        "xla_compiles_in_window.steady",
        "xla_cache_loads_in_window.steady",
    },
    "rehearsal-256.rehearsal-burst": SET_UP | {
        "staging_usage_base_mean_ms.drain",
        "usage_rebuilds_per_solve.drain",
        "staging_blocked_mean_ms.drain",
        "execute_hold_mean_ms.drain",
        "xla_compiles_in_window.drain",
        "scalar_plans_per_plan.drain",
        "usage_rolls_per_solve.drain",
        "xla_cache_loads_in_window.drain",
        "scalar_lone_per_plan.drain",
        "scalar_ineligible_per_plan.drain",
        "scalar_object_rows_per_plan.drain",
        "scalar_unfit_per_plan.drain",
    },
}


def test_every_new_metric_belongs_to_a_rehearsed_mix():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        listed = {m["name"] for m in json.load(f)["per_layer"]}
    # A later PR adds metrics of its own: no name of theirs has to be
    # written here.
    assert set().union(*NEW.values()) <= listed
    for workload, names in NEW.items():
        assert names <= {m["name"] for m in run.Cell(workload).per_layer()}


@pytest.mark.parametrize("workload", sorted(NEW))
def test_traced_rehearsal_reports_the_new_metrics(capsys, workload):
    rc = run.main(["--workload", workload, "--seed", "2147483777",
                   "--seconds", "2.0", "--trace", "1",
                   "--drain-timeout", "6"])
    out = capsys.readouterr()
    assert rc == 0, out.err[-2000:]
    result = json.loads(out.out.strip().splitlines()[-1])
    assert result["correct"] is True
    metrics = result["metrics"]
    assert NEW[workload] <= set(metrics), NEW[workload] - set(metrics)
    for name in NEW[workload]:
        value = metrics[name]["value"]
        assert isinstance(value, (int, float)) and math.isfinite(value), name
        assert value >= 0.0, name
    assert metrics["setup_device_acquire_s"]["value"] > 0.0


def test_setup_reader_reports_nothing_without_a_device(monkeypatch):
    from nomad_tpu import scheduler

    monkeypatch.setattr(scheduler, "_device", None)
    for key in ("device_acquire_s", "program_load_s"):
        assert setup.read({"key": key}, run.RunContext()) is None


def test_setup_reader_reports_nothing_on_a_program_without_the_record(
        monkeypatch):
    """The parent commit's ``device_status()`` has no ``acquire_s`` and
    its panel no XLA totals: the metric is left out, never 0."""
    from nomad_tpu import scheduler
    from nomad_tpu.tpu import solver

    monkeypatch.setattr(scheduler, "_device", {
        "platform": "cpu", "device_kind": "cpu", "count": 1,
        "compile_cache": None})
    monkeypatch.setattr(solver.SOLVER_PANEL, "snapshot",
                        lambda: {"solves": 3})
    for key in ("device_acquire_s", "program_load_s"):
        assert setup.read({"key": key}, run.RunContext()) is None
    with pytest.raises(ValueError):
        setup.read({"key": "nonesuch"}, run.RunContext())
