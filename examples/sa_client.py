"""Tenant-side walkthrough of the SA service (DESIGN.md §18).

Starts an in-process StudyServer over the pathology workflow, serves it
on an ephemeral TCP port, then drives it as two tenants would:

* tenant ``alice`` submits a MOAT study and polls it to completion;
* tenant ``bob`` submits the *same spec* concurrently — the content
  signature matches, so the Manager executes the tasks once and both
  jobs observe the same objective vector;
* ``bob`` then submits a wide grid sweep and cancels it mid-flight,
  which frees the workers without touching alice's results.

Run:  PYTHONPATH=src python examples/sa_client.py
"""

from __future__ import annotations

import threading

from repro import device
from repro.app.pipeline import pathology_service_build
from repro.service import ServiceClient, StudyServer, StudySpec


def main() -> None:
    device.use_compile_cache()
    server = StudyServer.from_build(
        pathology_service_build,
        {"size": 32, "n_tiles": 2, "seed": 0},
        n_workers=2,
    )
    addr = server.serve_background("127.0.0.1:0")
    print(f"server on {addr}")
    try:
        alice = ServiceClient(addr, "alice")
        bob = ServiceClient(addr, "bob")

        moat = StudySpec(sampler="moat", n_trajectories=2, seed=7)
        job_a = alice.submit(moat)
        job_b = bob.submit(moat)  # identical signature: executes once
        print(f"alice submitted {job_a}; bob submitted {job_b}")

        res_a = alice.result(job_a, timeout=300)
        res_b = bob.result(job_b, timeout=300)
        assert res_a["state"] == res_b["state"] == "DONE", (res_a, res_b)
        obj_a = res_a["result"]["objective"]
        obj_b = res_b["result"]["objective"]
        assert obj_a == obj_b, "shared execution must agree bit-for-bit"
        print(f"moat objective ({len(obj_a)} runs): {obj_a[:4]} ...")
        print(
            "tasks executed — alice's job: "
            f"{res_a['result']['tasks_executed']}, bob's (shared): "
            f"{res_b['result']['tasks_executed']}"
        )

        sweep = StudySpec(sampler="grid", names=["T1", "FH", "RC"])
        job_c = bob.submit(sweep)
        # cancel from a second thread while the sweep is mid-flight
        threading.Timer(0.3, lambda: bob.cancel(job_c)).start()
        res_c = bob.result(job_c, timeout=300)
        print(f"sweep {job_c} ended {res_c['state']}")

        stats = alice.server_stats()
        print(
            "server: "
            f"{stats['registry']['jobs']} jobs, cache hits "
            f"{stats['cache']['hits']}, tenant dispatch "
            f"{stats['scheduler'].get('tenant_dispatch')}"
        )
        alice.close()
        bob.close()
    finally:
        server.close()


if __name__ == "__main__":
    main()
