#!/usr/bin/env python
"""Tier-1 suite wrapper: the ROADMAP verify command with failure forensics.

Runs the exact tier-1 pytest invocation (ROADMAP.md "Tier-1 verify") but
always captures ``-rf`` (failed-test summary) and ``--junitxml`` so a
flaky full run leaves NAMED evidence instead of an anonymous red — the
round-5 verdict's "unnamed 1-in-3 full-suite flake" existed precisely
because full runs were thrown away. Artifacts per run:

    /tmp/tier1_<N>.log          full pytest output (tee'd to stdout)
    /tmp/tier1_<N>.xml          junit XML: machine-greppable failed names
    /tmp/tier1_<N>_bundle.json  debug bundle, written ONLY on a failed
                                run: fetched from a live agent when
                                NOMAD_TPU_DEBUG_AGENT is set, else the
                                process-local capture (nomad_tpu.bundle)
                                — red runs ship flight-recorder data

Usage: ``python tools/tier1.py [repeat]`` — repeat defaults to 1; pass 3
to hunt a 1-in-3 flake. Exit code: 0 only if every run passed. After the
runs, prints one summary line per run plus every distinct failed test id
seen across runs (collection errors excluded: the suite tolerates them
via --continue-on-collection-errors, e.g. test_jobspec.py's dependency on
the /root/reference checkout that CI containers lack).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import xml.etree.ElementTree as ET

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def capture_bundle(path: str) -> str:
    """Write a debug bundle next to the junitxml of a failed run.

    NOMAD_TPU_DEBUG_AGENT (an http://host:port) targets a live test
    agent's /v1/agent/debug/bundle; otherwise the bundle is the
    process-local capture — the suite ran as a SUBPROCESS, so that
    fallback records the wrapper process only (its threads, plus any
    registries the harness itself armed), NOT the dead suite's state.
    The bundle is stamped with its capture scope so empty sections read
    as "wrong process", never as "nothing happened". Best-effort:
    forensics must never fail the report."""
    try:
        addr = os.environ.get("NOMAD_TPU_DEBUG_AGENT", "")
        if addr:
            from nomad_tpu.api.client import ApiClient

            bundle = ApiClient(address=addr).agent().debug_bundle()
            bundle["source"] = {"kind": "live-agent", "address": addr}
        else:
            from nomad_tpu.bundle import collect

            bundle = collect(agent=None)
            bundle["source"] = {
                "kind": "process-local",
                "process": "tier1-wrapper",
                "note": "suite ran as a subprocess; set "
                        "NOMAD_TPU_DEBUG_AGENT to capture a live agent",
            }
        with open(path, "w") as f:
            json.dump(bundle, f, indent=2, default=str)
        return path
    except Exception as e:  # noqa: BLE001 - forensics are best-effort
        print(f"tier1: debug bundle capture failed: {e}", file=sys.stderr)
        return ""


PYTEST_ARGS = [
    "-m", "pytest", "tests/", "-q", "-m", "not slow",
    "--continue-on-collection-errors",
    "-p", "no:cacheprovider", "-p", "no:xdist", "-p", "no:randomly",
    "-rf",
]
TIMEOUT_S = 870  # the ROADMAP tier-1 budget


def run_nomadlint() -> int:
    """The static-analysis gate, run BEFORE pytest: any nomadlint finding
    outside the committed baseline fails tier-1 without spending the test
    budget. The run also refreshes /tmp/nomadlint_report.json, which a
    failed run's debug bundle embeds (nomad_tpu.bundle `nomadlint`
    section) — red-run forensics carry the gate's view of the tree."""
    print("=== nomadlint gate ===")
    try:
        proc = subprocess.run(
            [sys.executable, "-m", "tools.nomadlint", "--baseline"],
            cwd=REPO, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True, timeout=120,
        )
        out, rc = proc.stdout, proc.returncode
    except subprocess.TimeoutExpired as e:
        out = ((e.stdout or "") if isinstance(e.stdout, str)
               else (e.stdout or b"").decode("utf-8", "replace"))
        out += "\nnomadlint gate TIMED OUT after 120s\n"
        rc = 1
    sys.stdout.write(out)
    with open("/tmp/tier1_nomadlint.log", "w") as f:
        f.write(out)
    return rc


def run_once(n: int) -> dict:
    import threading

    log_path = f"/tmp/tier1_{n}.log"
    xml_path = f"/tmp/tier1_{n}.xml"
    # A wedged run that gets killed never writes its junitxml; a stale
    # file from a previous invocation would silently masquerade as this
    # run's forensics.
    try:
        os.remove(xml_path)
    except FileNotFoundError:
        pass
    with open(log_path, "w") as logf:
        proc = subprocess.Popen(
            [sys.executable, *PYTEST_ARGS, f"--junitxml={xml_path}"],
            cwd=REPO, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True, env={**os.environ, "JAX_PLATFORMS": "cpu"},
        )

        # Pump output on a thread so the TIMEOUT_S budget is enforced by
        # proc.wait below even when a wedged run never closes stdout — a
        # hung suite is exactly the scenario this wrapper must outlive.
        def pump():
            for line in proc.stdout:
                sys.stdout.write(line)
                logf.write(line)

        reader = threading.Thread(target=pump, daemon=True)
        reader.start()
        try:
            rc = proc.wait(timeout=TIMEOUT_S)
        except subprocess.TimeoutExpired:
            proc.kill()
            rc = -1
        reader.join(timeout=5)
    failed, collect_errors = [], []
    xml_ok = False
    try:
        for case in ET.parse(xml_path).getroot().iter("testcase"):
            if case.find("failure") is None and case.find("error") is None:
                continue
            if not case.get("classname"):
                # Collection error (junit records it as a classname-less
                # testcase): tolerated per --continue-on-collection-errors.
                collect_errors.append(case.get("name", ""))
            else:
                failed.append(
                    f"{case.get('classname', '')}::{case.get('name', '')}"
                )
    except (OSError, ET.ParseError):
        pass
    else:
        xml_ok = True
    return {"run": n, "rc": rc, "failed": failed,
            "collect_errors": collect_errors, "xml_ok": xml_ok,
            "log": log_path, "xml": xml_path}


def main() -> int:
    repeat = int(sys.argv[1]) if len(sys.argv) > 1 else 1
    if run_nomadlint() != 0:
        capture_bundle("/tmp/tier1_nomadlint_bundle.json")
        print("tier1: nomadlint gate FAILED — fix the findings, suppress "
              "with `# nomadlint: allow(RULE) -- reason`, or grandfather "
              "with `python -m tools.nomadlint --write-baseline` "
              "(log: /tmp/tier1_nomadlint.log)")
        return 1
    results = [run_once(n) for n in range(1, repeat + 1)]
    print("\n=== tier1 summary ===")
    all_failed: dict = {}
    ok = True
    for r in results:
        # rc==1 with zero failed testcases is the tolerated
        # collection-error posture (--continue-on-collection-errors) —
        # but ONLY when the junitxml parsed: rc==1 without forensics
        # (corrupt/missing xml) must read as a failure, not a pass.
        passed = (
            not r["failed"]
            and (r["rc"] == 0 or (r["rc"] == 1 and r["xml_ok"]))
        )
        status = "PASS" if passed else "FAIL"
        bundle = ""
        if not passed:
            ok = False
            bundle = capture_bundle(f"/tmp/tier1_{r['run']}_bundle.json")
        artifacts = ", ".join(p for p in (r["log"], r["xml"], bundle) if p)
        print(f"run {r['run']}: {status} rc={r['rc']} "
              f"failed={len(r['failed'])} "
              f"collect_errors={len(r['collect_errors'])} "
              f"({artifacts})")
        for name in r["failed"]:
            all_failed.setdefault(name, []).append(r["run"])
    if all_failed:
        print("distinct failures across runs:")
        for name, runs in sorted(all_failed.items()):
            print(f"  {name}  (runs {runs})")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
