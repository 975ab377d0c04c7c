"""One process of the multi-host DCN dryrun.

Usage: python -m nomad_tpu.parallel.dcn_worker <process_id> <num_processes>
       <coordinator_port> [n_nodes] [count] [local_devices]

Environment setup (platform pin, virtual device count) happens BEFORE jax
is imported, which is why this launcher is separate from parallel/dcn.py.
Prints one line ``DCN_RESULT {json}`` and exits 0 on success — the
contract consumed by tests/test_dcn.py and __graft_entry__.dryrun_dcn.
"""

import json
import os
import sys


def _main() -> None:
    process_id = int(sys.argv[1])
    num_processes = int(sys.argv[2])
    port = sys.argv[3]
    n_nodes = int(sys.argv[4]) if len(sys.argv) > 4 else 1024
    count = int(sys.argv[5]) if len(sys.argv) > 5 else 900
    local_devices = int(sys.argv[6]) if len(sys.argv) > 6 else 4

    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count={local_devices}"
        ).strip()

    from nomad_tpu.parallel import dcn

    try:
        dcn.initialize(f"127.0.0.1:{port}", num_processes, process_id)
    except Exception as e:
        print(f"DCN_UNSUPPORTED {type(e).__name__}: {e}", flush=True)
        sys.exit(3)

    mesh = dcn.dcn_mesh()
    out = dcn.run_dcn_solve(mesh, n_nodes=n_nodes, count=count)
    out["process_id"] = process_id
    out["ok"] = bool(
        out["placed"] == count and out["unplaced"] == 0
        and out["n_processes"] == num_processes
    )
    print("DCN_RESULT " + json.dumps(out), flush=True)
    sys.exit(0 if out["ok"] else 1)


if __name__ == "__main__":
    _main()
