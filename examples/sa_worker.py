"""Remote worker entrypoint: join a pathology SA fleet by TCP address.

The multi-host counterpart of ``sa_pathology.py --backend socket`` — run
this on ANY host that can reach the leader's control-plane address and the
study's store root (a shared directory, or an ``obj:<root>`` object store
that needs no shared filesystem at all):

    # on the leader (listens on a fixed port, waits for external workers):
    PYTHONPATH=src python examples/sa_pathology.py \
        --backend 'socket[0.0.0.0:7077,external]' --workers 2 \
        --store-dir obj:/data/sa-store

    # on each worker host:
    PYTHONPATH=src:examples python examples/sa_worker.py \
        --connect leader-host:7077 --tiles 4 --size 72

Inputs never cross the wire: the worker REGENERATES the synthetic tiles
deterministically (same seeds as the leader — ``synthetic_tile(size,
size, seed=t)`` for t in 0..tiles-1), so leader and workers agree on the
dataset by construction, and results cross hosts only as store keys. For a
real dataset the pattern is the same — give every host a build that loads
identical tiles (e.g. from the object store) instead of synthesising them.

This wraps the generic ``python -m repro.runtime.net worker`` CLI: that
entrypoint takes any ``--build module:callable``; this one bakes in the
pathology build and its tile-regeneration arguments. Store spec, option
flags and heartbeat cadence all arrive from the leader in the welcome
frame, so the only coordination needed is the address (and a matching
--tiles/--size, which the leader's run prints).
"""

import argparse

from repro import device
from repro.app import synthetic_tile
from repro.app.pipeline import pathology_rpc_build
from repro.runtime.net import run_worker


def pathology_worker_build(n_tiles: int = 4, size: int = 72):
    """Spawn/remote-importable build: regenerate the leader's synthetic
    tiles (deterministic seeds) and hand them to the standard RPC build."""
    tiles = [synthetic_tile(size, size, seed=t) for t in range(n_tiles)]
    return pathology_rpc_build(tiles)


def main() -> int:
    device.use_compile_cache()
    ap = argparse.ArgumentParser(
        description="Join a pathology SA socket fleet (DESIGN.md §16)"
    )
    ap.add_argument("--connect", required=True, metavar="HOST:PORT",
                    help="the leader's control-plane address")
    ap.add_argument("--tiles", type=int, default=4,
                    help="tile count — must match the leader's --tiles")
    ap.add_argument("--size", type=int, default=72,
                    help="tile size — must match the leader's --size")
    ap.add_argument("--id", type=int, default=None,
                    help="re-register under a previously assigned worker id")
    ap.add_argument("--store", default=None,
                    help="override the leader's store spec for this host "
                         "(plain directory or obj:<root>)")
    args = ap.parse_args()
    wid = run_worker(
        args.connect,
        build=pathology_worker_build,
        build_kwargs={"n_tiles": args.tiles, "size": args.size},
        worker_id=args.id,
        store=args.store,
    )
    print(f"worker {wid} retired cleanly")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
