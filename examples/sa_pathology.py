"""End-to-end driver: distributed SA study over a multi-tile dataset.

A thin caller of the StudyPlanner engine's streaming executor. The study is
planned ONCE (plan→bucket→schedule; plans are input-independent), then the
whole tile dataset is pipelined through that single plan by
``execute_study``: one persistent Manager session spans every tile and
stage, stage edges are per-tile (tile A segments while tile B normalizes),
and straggler backup-tasks stay enabled throughout. Compares the no-reuse
policy's planned work against the hybrid policy's real wall-clock and
computes Spearman correlations of each parameter against the Dice
difference.

Usage (README-level):

    PYTHONPATH=src python examples/sa_pathology.py [--runs 48] [--tiles 4]
                                                   [--workers 2] [--size 72]
                                                   [--backend thread|process]

    # --backend process swaps the Manager's Worker pool for RPC worker
    # PROCESSES behind the same WorkerBackend API (DESIGN.md §13): spawn
    # workers rebuild the workflow+plan from picklable specs, and results
    # cross the process boundary only as SharedStore keys. Fast-path flags
    # (DESIGN.md §14) ride the spec: --backend 'process[none]' replays the
    # pre-fast-path wire, 'process[-shm]' drops one mechanism, etc.

    # --hierarchy 4 splits the Manager into 4 sub-manager pumps with
    # locality-aware dispatch and work stealing (DESIGN.md §15); results
    # stay bit-identical to the flat scheduler. 'auto' sizes the fan-out
    # from the pool; 'fanout=4,-steal' tunes individual features.

    # Adaptive mode (DESIGN.md §11): a multi-round MOAT -> prune -> VBD ->
    # refine study driven by repro.study.StudyDriver — one persistent
    # Manager session and result store across rounds, each round planning
    # only its delta against the cached trie:
    PYTHONPATH=src python examples/sa_pathology.py --adaptive [--rounds 4]

    # Fleet mode (DESIGN.md §12): the same adaptive study sharded across K
    # StudyDriver *processes* pooling one crash-safe SharedStore directory
    # (atomic writes + per-key file locks + manifest); round N+1 plans
    # against the union of every process's committed keys:
    PYTHONPATH=src python examples/sa_pathology.py --fleet 2 [--rounds 4]

    # Library form — dataset-level study in three lines:
    from repro.engine import ClusterSpec, execute_study, plan_study
    plan = plan_study(workflow, param_sets, policy="hybrid")
    stream = execute_study(plan, tiles, cluster=ClusterSpec(n_workers=8))
    # stream.outputs[tile][run_id] — bit-identical to per-tile execute_plan;
    # stream.throughput / stream.parallel_efficiency — paper §IV-D metrics.
"""

import argparse
import time

import jax.numpy as jnp
import numpy as np

from repro import device
from repro.app import synthetic_tile
from repro.app.pipeline import build_workflow, TABLE1_SPACE
from repro.core import correlation_indices, dice, morris_trajectories
from repro.core.params import ParamSpace
from repro.engine import ClusterSpec, execute_plan, execute_study, plan_study

SPACE = ParamSpace.from_dict(
    {
        "B": [210, 220, 230], "G": [210, 220, 230], "R": [210, 220, 230],
        "T1": [2.5, 5.0, 7.5], "T2": [2.5, 5.0, 7.5],
        "G1": [20, 40, 60], "G2": [10, 20, 30],
        "minS": [2, 10, 20], "maxS": [900, 1200, 1500],
        "minSPL": [5, 20, 40], "minSS": [2, 10, 20], "maxSS": [900, 1200, 1500],
        "FH": [4, 8], "RC": [4, 8], "WConn": [4, 8],
    }
)


def run_adaptive(args) -> None:
    """Adaptive multi-round study: screen, prune, quantify, refine — with
    cross-round incremental planning and the persistent result store."""
    from repro.app.pipeline import run_adaptive_study

    tiles = [synthetic_tile(args.size, args.size, seed=t) for t in range(args.tiles)]
    out = run_adaptive_study(
        tiles,
        space=SPACE,
        max_rounds=args.rounds,
        n_workers=args.workers,
        seed=3,
        backend=args.backend,
        hierarchy=args.hierarchy,
    )
    dispatch = ", ".join(f"{k}={v}" for k, v in out["dispatch_counts"].items())
    print(
        f"adaptive study [{out['backend']} backend, {dispatch or 'no dispatch'}]: "
        f"{out['rounds']} rounds, "
        f"{out['tasks_executed']}/{out['tasks_requested']} tasks executed "
        f"(reuse factor {out['reuse_factor']:.2f}x), "
        f"cache {out['cache_hits']} hits / {out['cache_misses']} misses / "
        f"{out['cache_spills']} spills / {out['cache_flushed']} flushed, "
        f"{out['wall_seconds']:.1f}s"
    )
    for r in out["rounds_detail"]:
        known = f", {r['planned_known']} known from prior rounds" if r["planned_known"] else ""
        print(
            f"  [{r['kind']:6s}] {r['n_new']}/{r['n_proposed']} new runs, "
            f"{r['tasks_executed']} tasks executed{known} — {r['decision'].get('reason', '')}"
        )
        ranking = r["analysis"].get("ranking")
        if ranking:
            print(f"           importance: {' > '.join(ranking[:6])}")
    print(f"surviving parameters: {out['active']}")


def run_fleet(args) -> None:
    """Fleet mode: shard the adaptive study across N processes pooling one
    crash-safe SharedStore directory."""
    import tempfile

    from repro.app.pipeline import run_fleet_study

    store_dir = args.store_dir or tempfile.mkdtemp(prefix="rtf_fleet_")
    out = run_fleet_study(
        n_procs=args.fleet,
        store_dir=store_dir,
        size=args.size,
        n_tiles=args.tiles,
        space=SPACE,
        max_rounds=args.rounds,
        n_workers=args.workers,
        seed=3,
    )
    fleet = out["fleet"]
    print(
        f"fleet study ({fleet['n_procs']} procs over {store_dir}): "
        f"{out['rounds']} rounds, "
        f"{out['tasks_executed']}/{out['tasks_requested']} combined tasks "
        f"(reuse factor {out['reuse_factor']:.2f}x), "
        f"{fleet['committed_keys']} committed store keys, "
        f"{fleet['store_disk_hits']} cross-process rehydrations, "
        f"{fleet['dedup_writes']} lock-elided double-writes, "
        f"{fleet['corrupt']} corrupt reads, {out['wall_seconds']:.1f}s"
    )
    for r in out["rounds_detail"]:
        print(
            f"  [{r['kind']:6s}] {r['n_new']}/{r['n_proposed']} new runs, "
            f"{r['tasks_executed']} tasks executed — "
            f"{r['decision'].get('reason', '')}"
        )
    print(f"surviving parameters: {out['active']}")


def main() -> None:
    device.use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--runs", type=int, default=48)
    ap.add_argument("--tiles", type=int, default=4)
    ap.add_argument("--workers", type=int, default=2)
    ap.add_argument("--size", type=int, default=72)
    ap.add_argument("--adaptive", action="store_true",
                    help="multi-round adaptive study (MOAT -> prune -> VBD -> refine)")
    ap.add_argument("--rounds", type=int, default=4, help="adaptive round budget")
    ap.add_argument("--fleet", type=int, default=0, metavar="K",
                    help="shard the adaptive study across K processes "
                         "pooling one SharedStore")
    ap.add_argument("--store-dir", default=None,
                    help="SharedStore directory for --fleet (default: fresh tmpdir)")
    ap.add_argument("--backend", default="thread",
                    help="WorkerBackend for the study's Manager session: "
                         "'thread' (default, in-process Workers) or "
                         "'process' — RPC worker processes pooling a "
                         "SharedStore — or 'socket' — a TCP fleet "
                         "(DESIGN.md §16) whose workers join by address, "
                         "e.g. 'socket[store=obj:/data/sa]'. Fast-path "
                         "flags select per DESIGN.md §14, e.g. "
                         "'process[none]' or 'process[-shm]'")
    ap.add_argument("--hierarchy", default=None,
                    help="scheduler topology for the Manager session "
                         "(DESIGN.md §15): 'flat' (default, one pump), an "
                         "integer fan-out, 'auto', or a spec string like "
                         "'fanout=4,-steal,block=16'")
    args = ap.parse_args()
    if args.backend != "thread" and not args.backend.startswith(
        ("process", "socket")
    ):
        ap.error(f"--backend must be 'thread', 'process[...]' or "
                 f"'socket[...]', got {args.backend!r}")

    if args.fleet > 0:
        run_fleet(args)
        return
    if args.adaptive:
        run_adaptive(args)
        return

    sets, _ = morris_trajectories(SPACE, max(1, args.runs // (SPACE.dim + 1)), seed=3)
    sets = sets[: args.runs]
    wf = build_workflow(args.size, args.size)
    cluster = ClusterSpec(n_workers=args.workers, straggler_factor=4.0)

    # Plan once (input-independent), stream every tile through the one plan.
    plan = plan_study(wf, sets, cluster=cluster, policy="hybrid",
                      max_bucket_size=len(sets), active_paths=4)
    ref_plan = plan_study(wf, [TABLE1_SPACE.default()], policy="rmsr")
    sub = sets[: max(4, len(sets) // 8)]
    naive_plan = plan_study(wf, sub, policy="none")
    print(f"plan: {plan.tasks_executed}/{plan.tasks_total} tasks "
          f"({plan.reuse_fraction*100:.0f}% reuse) in {plan.bucket_count()} buckets")

    tiles_np = [synthetic_tile(args.size, args.size, seed=t) for t in range(args.tiles)]
    tiles = [{"raw": jnp.asarray(im)} for im in tiles_np]
    backend = None
    if args.backend.startswith("process"):
        from repro.app.pipeline import pathology_rpc_build
        from repro.runtime import ProcessRpcBackend
        from repro.runtime.transport import process_flag_kwargs

        backend = ProcessRpcBackend(
            build=pathology_rpc_build, build_kwargs={"images": tiles_np},
            **process_flag_kwargs(args.backend),
        )
    elif args.backend.startswith("socket"):
        from repro.app.pipeline import pathology_rpc_build
        from repro.runtime import SocketBackend, socket_flag_kwargs

        kwargs = socket_flag_kwargs(args.backend)
        kwargs.setdefault("store", args.store_dir)
        if kwargs["store"] is None:
            del kwargs["store"]  # backend owns a throwaway tempdir
        backend = SocketBackend(
            build=pathology_rpc_build, build_kwargs={"images": tiles_np},
            **kwargs,
        )

    # reference masks first: the 1-run reference plan, streamed over all
    # tiles — also serves as the jit warm-up so the timings below are fair
    ref_stream = execute_study(ref_plan, tiles, cluster=cluster)
    ref_masks = [ref_stream.outputs[t][0]["mask"] for t in range(args.tiles)]

    # naive baseline: time a subsample of independent runs, extrapolate
    t0 = time.perf_counter()
    execute_plan(naive_plan, tiles[0])
    t_naive = (time.perf_counter() - t0) * (len(sets) * args.tiles) / len(sub)

    t0 = time.perf_counter()
    try:
        stream = execute_study(plan, tiles, cluster=cluster, backend=backend,
                               hierarchy=args.hierarchy)
        t_hybrid = time.perf_counter() - t0  # before cleanup: timing the
    finally:                                 # study, not the rmtree
        if backend is not None:
            backend.cleanup()  # throwaway tempdir store

    all_scores = {
        rid: [float(dice(stream.outputs[t][rid]["mask"], ref_masks[t]))
              for t in range(args.tiles)]
        for rid in range(len(sets))
    }
    mean_scores = [1.0 - float(np.mean(all_scores[r])) for r in range(len(sets))]
    print(f"naive (est) {t_naive:.1f}s vs streaming engine(hybrid) {t_hybrid:.1f}s "
          f"-> {t_naive/max(t_hybrid,1e-9):.2f}x  "
          f"[{stream.backend} backend, {stream.throughput:.2f} tiles/s, "
          f"eff={stream.parallel_efficiency:.2f}, "
          f"{stream.manager_sessions} Manager session]")
    sched = stream.scheduler
    if sched.get("fanout", 1) > 1:
        print(f"scheduler [{sched['mode']} fanout={sched['fanout']}]: "
              f"{sched['steals']} steals ({sched['steal_items']} items), "
              f"locality hit-rate {sched['locality_hit_rate']:.2f}, "
              f"pump occupancy {sched['pump_occupancy']:.2f}, "
              f"mean worker idle {sched['worker_idle_fraction']:.2f}")
    corr = correlation_indices(SPACE, sets, mean_scores)
    print("top parameters by |spearman|:")
    for name, v in sorted(corr.items(), key=lambda kv: -abs(kv[1]["spearman"]))[:8]:
        print(f"  {name:8s} spearman={v['spearman']:+.3f} pearson={v['pearson']:+.3f}")


if __name__ == "__main__":
    main()
