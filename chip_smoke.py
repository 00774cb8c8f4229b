"""Smoke run of the sensitivity-analysis main path on one TPU chip.

    python3 chip_smoke.py

Run from the root of a checkout. It needs one TPU chip and runs every phase
in this one process (a chip belongs to one process at a time). Each phase
prints one line with its wall time and counters; any failure exits non-zero
and the closing JSON line is not printed.

1. device: fails unless JAX's default backend is a TPU.
2. study: two 4096x4096 synthetic tiles (seeds 0 and 1) and one MOAT
   trajectory over the Table I space (16 runs) through
   ``run_dataset_study`` on two thread workers, once under the ``hybrid``
   reuse policy and once under ``none`` (the naive oracle: every run
   re-executed, nothing merged). Every final mask must be bit-identical
   between the two, and so must the Dice lists.
3. cross-check: the default-parameter mask of one 512x512 tile computed on
   the chip and on the host's CPU backend must agree to Dice >= 0.99 (their
   reductions round differently, so they are not bit-equal). This catches
   a miscompile.
4. service: a ``StudyServer`` over the 4096x4096 pathology build (one tile),
   served on loopback; a ``ServiceClient`` submits the study's 16 runs as an
   explicit spec. The job must end ``DONE`` with every objective equal to
   ``1 - dice`` of the study phase's tile 0, exactly.

The last line of standard output is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.

There is no four-chip phase: nothing in ``runtime/`` or ``engine/`` places
work on any device but the default one, so no SA path spans chips. The
sharded LM code (``dist/sharding.py``, ``models/moe.py``) is not on the SA
path.
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))
os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs under /tmp

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro import device  # noqa: E402
from repro.app import TABLE1_SPACE, run_dataset_study, synthetic_tile  # noqa: E402
from repro.app.pipeline import build_workflow, pathology_service_build  # noqa: E402
from repro.core import dice, morris_trajectories  # noqa: E402
from repro.engine import execute_plan, plan_study  # noqa: E402
from repro.service import ServiceClient, StudyServer, StudySpec  # noqa: E402

SIZE = 4096  # the paper's tile edge
SEEDS = (0, 1)
CROSS_SIZE = 512
N_WORKERS = 2
MIN_CROSS_DICE = 0.99
JOB_TIMEOUT_S = 600.0


class SmokeFailure(RuntimeError):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def report(name: str, t0: float, **counters) -> None:
    fields = " ".join(f"{k}={v}" for k, v in counters.items())
    print(f"[{name}] wall_s={time.perf_counter() - t0:.3f} {fields}", flush=True)


def device_phase() -> jax.Device:
    t0 = time.perf_counter()
    backend = jax.default_backend()
    check(backend == "tpu", f"no TPU: JAX's default backend is {backend!r}")
    dev = jax.devices()[0]
    report(
        "device", t0, device_kind=repr(dev.device_kind),
        device_count=jax.device_count(), jax=jax.__version__,
    )
    return dev


def study_phase(size: int = SIZE):
    """Run the hybrid study and the none oracle; returns the param sets and
    the hybrid Dice lists ``[tile][run]``."""
    t0 = time.perf_counter()
    tiles = [synthetic_tile(size, size, seed=s) for s in SEEDS]
    sets, _moves = morris_trajectories(TABLE1_SPACE, 1, seed=0)
    report("tiles", t0, size=size, n_tiles=len(tiles), n_runs=len(sets))

    results = {}
    for strategy in ("hybrid", "none"):
        t0 = time.perf_counter()
        res = run_dataset_study(
            tiles, sets, strategy=strategy, backend="thread", n_workers=N_WORKERS
        )
        masks = [
            [np.asarray(res["stream"].outputs[i][rid]["mask"]) for rid in range(len(sets))]
            for i in range(len(tiles))
        ]
        results[strategy] = (res["dice"], masks)
        report(
            f"study-{strategy}", t0,
            tasks_executed=res["tasks_executed"], tasks_total=res["tasks_total"],
            reuse_factor=res["reuse_factor"], retries=res["retries"],
            backups_launched=res["backups_launched"],
            nuclei_share=float(np.mean([m.mean() for row in masks for m in row])),
            reference_nuclei_share=[float(m.mean()) for m in res["reference_masks"]],
        )
        del res

    t0 = time.perf_counter()
    (dice_h, masks_h), (dice_n, masks_n) = results["hybrid"], results["none"]
    for i in range(len(tiles)):
        for rid in range(len(sets)):
            check(
                np.array_equal(masks_h[i][rid], masks_n[i][rid]),
                f"tile {i} run {rid}: hybrid mask differs from the none oracle",
            )
    check(dice_h == dice_n, f"Dice lists differ: {dice_h} vs {dice_n}")
    report("study-compare", t0, masks_equal=len(tiles) * len(sets), dice_equal=True)
    return sets, dice_h


def cross_check_phase(chip: jax.Device, size: int = CROSS_SIZE) -> float:
    """Default-parameter mask of one tile on the chip and on the CPU."""
    t0 = time.perf_counter()
    tile = synthetic_tile(size, size, seed=0)
    plan = plan_study(
        build_workflow(size, size), [TABLE1_SPACE.default()],
        policy="rmsr", active_paths=1,
    )
    masks = []
    for dev in (chip, jax.devices("cpu")[0]):
        with jax.default_device(dev):
            raw = jax.device_put(tile, dev)
            mask = execute_plan(plan, {"raw": raw}).outputs[0]["mask"]
        check(mask.devices() == {dev}, f"mask ran on {mask.devices()}, not {dev}")
        masks.append(np.asarray(mask))
    d = float(dice(masks[0], masks[1]))
    check(d >= MIN_CROSS_DICE, f"chip vs CPU Dice {d} < {MIN_CROSS_DICE}")
    report(
        "cross-check", t0, size=size, dice_chip_vs_cpu=d,
        bit_equal=bool(np.array_equal(masks[0], masks[1])),
    )
    return d


def service_phase(sets, tile0_dice, size: int = SIZE) -> None:
    t0 = time.perf_counter()
    server = StudyServer.from_build(
        pathology_service_build, {"size": size, "n_tiles": 1},
        backend="thread", n_workers=N_WORKERS,
    )
    report("service-build", t0, size=size, n_tiles=1)
    t0 = time.perf_counter()
    try:
        addr = server.serve_background("127.0.0.1:0")
        with ServiceClient(addr, "smoke") as client:
            job_id = client.submit(
                StudySpec(sampler="explicit", param_sets=[dict(ps) for ps in sets])
            )
            polls = 0
            deadline = time.monotonic() + JOB_TIMEOUT_S
            while client.status(job_id)["state"] not in ("DONE", "FAILED", "CANCELLED"):
                check(time.monotonic() < deadline, f"job {job_id} not done in {JOB_TIMEOUT_S}s")
                polls += 1
                time.sleep(1.0)
            job = client.result(job_id, wait=False)
    finally:
        server.close()
    check(job["state"] == "DONE", f"job ended {job['state']}: {job['error']}")
    objective = job["result"]["objective"]
    expected = [1.0 - d for d in tile0_dice]
    check(objective == expected, f"objectives {objective} != 1 - dice {expected}")
    report(
        "service", t0, state=job["state"], polls=polls,
        tasks_executed=job["result"]["tasks_executed"],
        objectives_equal=len(objective),
    )


def main() -> int:
    device.use_compile_cache()
    t_all = time.perf_counter()
    chip = device_phase()
    sets, dice_h = study_phase()
    cross_check_phase(chip)
    service_phase(sets, dice_h[0])
    report("total", t_all)
    print(json.dumps({
        "ok": True,
        "device": {
            "platform": chip.platform,
            "kind": chip.device_kind,
            "count": len(jax.devices()),
        },
    }), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except SmokeFailure as err:
        print(f"chip_smoke: FAILED: {err}", file=sys.stderr, flush=True)
        sys.exit(1)
