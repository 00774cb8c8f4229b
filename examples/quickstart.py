"""Quickstart: a parameter sensitivity analysis with computation reuse.

Runs a small MOAT screening study over the pathology pipeline on a synthetic
tile, executes it with RMSR (maximal merging, memory-bounded depth-first
scheduling), and prints parameter importance plus the reuse accounting.

    PYTHONPATH=src python examples/quickstart.py
"""

import numpy as np

from repro import device
from repro.app import run_study, synthetic_tile
from repro.core import ParamSpace, moat_indices, morris_trajectories

SPACE = ParamSpace.from_dict(
    {
        "B": [210, 230], "G": [210, 230], "R": [210, 230],
        "T1": [2.5, 5.0], "T2": [2.5, 5.0],
        "G1": [20, 40], "G2": [10, 20],
        "minS": [2, 10], "maxS": [900, 1200],
        "minSPL": [5, 20], "minSS": [2, 10], "maxSS": [900, 1200],
        "FH": [4, 8], "RC": [4, 8], "WConn": [4, 8],
    }
)


def main() -> None:
    device.use_compile_cache()
    tile = synthetic_tile(96, 96, seed=7)
    sets, moves = morris_trajectories(SPACE, 3, seed=0)
    print(f"MOAT study: {len(sets)} runs over {SPACE.dim} parameters")

    out = run_study(tile, sets, strategy="rmsr", active_paths=4)
    print(
        f"reuse: {out['tasks_executed']}/{out['tasks_total']} tasks executed "
        f"({out['reuse_fraction']*100:.1f}% eliminated), "
        f"wall {out['wall_seconds']:.1f}s"
    )

    res = moat_indices(SPACE, [1.0 - d for d in out["dice"]], moves)
    print("\nparameter importance (mu*, descending):")
    for name in res.ranking()[:8]:
        print(f"  {name:8s} mu*={res.mu_star[name]:.4f} sigma={res.sigma[name]:.4f}")


if __name__ == "__main__":
    main()
