"""Streaming dataset executor benchmark (DESIGN.md §10) — the perf
trajectory's machine-readable series (``BENCH_streaming.json``).

Three measurements:

(1) **real** — a multi-tile study on small tiles with real JAX tasks:
    K sequential ``execute_plan`` calls (one Manager session per call)
    versus one ``execute_study`` over the same tiles (one persistent
    session, per-tile stage edges) at 1/2/4 Workers. Every row reports the
    scheduler observables — pump occupancy and mean worker idle fraction —
    and streaming outputs are asserted bit-identical to sequential.

(2) **paper scale** — the discrete-event streaming model
    (``runtime.simulate_stream``) fed by the hybrid plan's frozen per-stage
    bucket makespans (measured JAX costs scaled to 4K×4K tiles), the full
    6,113 tiles at 32→256 nodes × 28 cores. The single pump is charged
    ``PUMP_SERVICE`` per scheduling event (the measured order-of-magnitude
    of the Python pump's per-event cost — see the real rows' pump
    occupancy). Paper claim: >92% efficiency at 256 nodes.

(3) **autotune** — ``runtime.autotune_stream`` over re-planned bucket-size
    candidates, minimizing simulated makespan. This is the
    reuse-vs-balance trade made visible: coarse buckets maximize merged-
    prefix reuse (least total work, best makespan), finer buckets maximize
    efficiency; the chosen point and the best-efficiency point are both
    reported.

NOTE: the DES section deliberately ignores ``SMOKE`` for tile count and
run count — the simulator is cheap, and a 200-tile smoke study hits a
parallelism ceiling at 7,168 cores that reads as an efficiency collapse
but is only a small-sample artifact. Only the measured-cost tile size
shrinks under smoke.
"""

from __future__ import annotations

import time
from typing import Dict, List, Sequence

import jax.numpy as jnp
import numpy as np

from repro.app import synthetic_tile
from repro.app.pipeline import build_segmentation_stage, build_workflow
from repro.core import Workflow
from repro.engine import ClusterSpec, execute_plan, execute_study, plan_study
from repro.runtime import autotune_stream, simulate_stream
from repro.runtime.manager import Manager

from benchmarks.common import SMOKE, measure_task_costs, moat_param_sets

TILE = 4096  # paper §IV-B whole-slide tile size
N_TILES_PAPER = 6113  # full dataset even under SMOKE (see module docstring)
N_SIM_RUNS = 160

# Charged per scheduling event (dispatch or completion settle) in the DES:
# the measured order-of-magnitude of the Python pump's per-event cost
# (lock + lease bookkeeping + callback; the real rows' pump_occ is the
# container-scale measurement of the same quantity).
PUMP_SERVICE = 1.5e-3
# The paper-scale operating point: bucket size 14 keeps per-bucket work
# fine enough that 7,168 cores stay load-balanced (bucket 28 trades that
# balance for deeper merged-prefix reuse — the autotune rows quantify it).
OPERATING_BUCKET = 14
BUCKET_CANDIDATES = (14, 28) if SMOKE else (7, 14, 28)


def _sched_tags(sched: Dict) -> str:
    """The per-row scheduler observables."""
    return (
        f"pump_occ={sched['pump_occupancy']:.2f}"
        f"_idle={sched['worker_idle_fraction']:.2f}"
    )


def run(csv: List[str]) -> None:
    # ---------------- (1) real streaming execution, container scale ------
    size = 48 if SMOKE else 64
    n_tiles = 3 if SMOKE else 6
    n_runs = 16 if SMOKE else 32
    wf = build_workflow(size, size)
    sets = moat_param_sets(n_runs, seed=7)
    plan = plan_study(wf, sets, policy="hybrid", max_bucket_size=8, active_paths=2)
    tiles = [
        {"raw": jnp.asarray(synthetic_tile(size, size, seed=t))}
        for t in range(n_tiles)
    ]

    execute_plan(plan, tiles[0])  # warm: jit compile every task variant

    t0 = time.perf_counter()
    sessions0 = Manager.sessions_started
    seq_outputs = [execute_plan(plan, tile).outputs for tile in tiles]
    t_seq = time.perf_counter() - t0
    seq_sessions = Manager.sessions_started - sessions0
    csv.append(
        f"streaming_real_sequential,{t_seq*1e6/n_tiles:.0f},"
        f"tiles={n_tiles}_sessions={seq_sessions}"
    )

    def check_identical(stream):
        for i in range(n_tiles):  # bit-identical to sequential per-tile runs
            for rid in range(n_runs):
                assert np.array_equal(
                    np.asarray(stream.outputs[i][rid]["mask"]),
                    np.asarray(seq_outputs[i][rid]["mask"]),
                )

    for w in (1, 2, 4):
        t0 = time.perf_counter()
        sessions0 = Manager.sessions_started
        stream = execute_study(plan, tiles, cluster=ClusterSpec(n_workers=w))
        dt = time.perf_counter() - t0
        assert Manager.sessions_started - sessions0 == 1
        check_identical(stream)
        csv.append(
            f"streaming_real_workers{w},{dt*1e6/n_tiles:.0f},"
            f"throughput={stream.throughput:.2f}tiles_s"
            f"_eff={stream.parallel_efficiency:.2f}"
            f"_speedup_vs_seq={t_seq/max(dt,1e-9):.2f}x_sessions=1"
            f"_{_sched_tags(stream.scheduler)}"
        )

    # ---------------- (2) paper-scale streaming simulation ---------------
    mh = 64 if SMOKE else 128
    costs = measure_task_costs(mh, mh)
    scale = (TILE / mh) ** 2
    seg = build_segmentation_stage(
        TILE, TILE, costs={k: v * scale for k, v in costs.items()}
    )
    sim_sets = moat_param_sets(N_SIM_RUNS, seed=4)

    def bucket_costs(bucket_size: int) -> List[List[float]]:
        sim_plan = plan_study(
            Workflow(stages=(seg,)), sim_sets,
            policy="hybrid", max_bucket_size=bucket_size,
            active_paths=min(bucket_size, 28),
        )
        sbc = [
            [b.schedule.makespan for b in sp.buckets] for sp in sim_plan.stages
        ]
        # normalization as a cheap parameter-free front stage, per DESIGN §10
        sbc.insert(0, [costs["normalize"] * scale])
        return sbc

    costs_by_bucket = {bs: bucket_costs(bs) for bs in BUCKET_CANDIDATES}
    op_costs = costs_by_bucket[OPERATING_BUCKET]

    def sim_row(name: str, sim, extra: str = "") -> None:
        csv.append(
            f"{name},{sim.makespan*1e6:.0f},"
            f"eff={sim.parallel_efficiency:.3f}"
            f"_tput={sim.throughput:.2f}tiles_s"
            f"_pump_occ={sim.pump_occupancy:.2f}"
            f"_idle={sim.worker_idle_fraction:.2f}{extra}"
        )

    nodes_list = (32, 256) if SMOKE else (32, 64, 128, 256)
    for nodes in nodes_list:
        flat = simulate_stream(
            op_costs, N_TILES_PAPER, n_nodes=nodes, seed=0,
            pump_service=PUMP_SERVICE,
        )
        bar = simulate_stream(
            op_costs, N_TILES_PAPER, n_nodes=nodes, seed=0,
            pump_service=PUMP_SERVICE, barrier=True,
        )
        sim_row(
            f"streaming_sim_nodes{nodes}_flat", flat,
            extra=f"_vs_barrier={bar.makespan/max(flat.makespan,1e-12):.2f}x",
        )

    # ---------------- (3) autotune bucket size --------------------------
    tuned = autotune_stream(
        costs_by_bucket, N_TILES_PAPER, n_nodes=256,
        pump_service=PUMP_SERVICE, seed=0,
    )
    sim_row(
        f"streaming_autotune_bucket{tuned.bucket_size}",
        tuned.sim,
        extra=f"_candidates={len(tuned.table)}",
    )
    best_eff = max(tuned.table, key=lambda row: row[2])
    csv.append(
        f"streaming_autotune_best_eff,{best_eff[1]*1e6:.0f},"
        f"bucket={best_eff[0]}_eff={best_eff[2]:.3f}"
    )
