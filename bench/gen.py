"""The benchmark's own traffic generators.

Copies of the program's seeded generators, kept here so that no change to
the program can change the yardstick: the synthetic H&E tile
(``repro.app.pipeline.synthetic_tile``), the paper's Table I parameter
space (``repro.app.pipeline.TABLE1_SPACE``), the Morris trajectories
(``repro.core.params.morris_trajectories``) and the Saltelli design
(``repro.core.sa.saltelli_sample``). ``bench/tests/test_gen.py`` checks
that each gives byte-identical output to the program's version.

A parameter space is a plain ``{name: [values...]}`` dict in the order of
the paper's table; a parameter set is a tuple of ``(name, value)`` pairs
sorted by name, the form the program's planner takes.
"""

from __future__ import annotations

from typing import Any, Dict, List, Sequence, Tuple

import numpy as np

ParamSet = Tuple[Tuple[str, Any], ...]
Space = Dict[str, List[Any]]

# Table I of arXiv:1910.14548: the segmentation's 15 parameters and their grids.
TABLE1: Space = {
    "B": list(range(210, 241, 10)),
    "G": list(range(210, 241, 10)),
    "R": list(range(210, 241, 10)),
    "T1": [x / 2.0 for x in range(5, 16)],  # 2.5 .. 7.5
    "T2": [x / 2.0 for x in range(5, 16)],
    "G1": list(range(5, 81, 5)),
    "G2": list(range(2, 41, 2)),
    "minS": list(range(2, 41, 2)),
    "maxS": list(range(900, 1501, 50)),
    "minSPL": list(range(5, 81, 5)),
    "minSS": list(range(2, 41, 2)),
    "maxSS": list(range(900, 1501, 50)),
    "FH": [4, 8],
    "RC": [4, 8],
    "WConn": [4, 8],
}

SPACES: Dict[str, Space] = {"table1": TABLE1}


def paramset(d: Dict[str, Any]) -> ParamSet:
    return tuple(sorted(d.items()))


def default_params(space: Space) -> ParamSet:
    """The grid midpoint of every parameter: the paper's reference run."""
    return paramset({k: v[len(v) // 2] for k, v in space.items()})


def quantise(space: Space, u: np.ndarray) -> List[ParamSet]:
    """Map an (n, dim) array of unit-cube points onto the grids."""
    names = list(space)
    out = []
    for row in u:
        d = {}
        for name, x in zip(names, row):
            vals = space[name]
            d[name] = vals[min(int(float(x) * len(vals)), len(vals) - 1)]
        out.append(paramset(d))
    return out


def synthetic_tile(h: int, w: int, *, seed: int) -> np.ndarray:
    """Synthetic H&E-like float32 RGB tile: pink stroma, purple nuclei,
    red blood cells and a bright glass band across the top eighth."""
    rng = np.random.default_rng(seed)
    img = np.empty((h, w, 3), np.float32)
    img[..., 0] = 215 + rng.normal(0, 6, (h, w))
    img[..., 1] = 170 + rng.normal(0, 6, (h, w))
    img[..., 2] = 195 + rng.normal(0, 6, (h, w))

    def blobs(n, rmin, rmax, color, jitter=10.0):
        for _ in range(n):
            cy, cx = rng.integers(0, h), rng.integers(0, w)
            rad = rng.uniform(rmin, rmax)
            r = int(np.ceil(rad))
            y0, x0 = max(0, cy - r), max(0, cx - r)
            yy, xx = np.mgrid[y0 : min(h, cy + r + 1), x0 : min(w, cx + r + 1)]
            m = (yy - cy) ** 2 + (xx - cx) ** 2 < rad**2
            box = img[y0 : y0 + m.shape[0], x0 : x0 + m.shape[1]]
            for c in range(3):
                box[..., c][m] = color[c] + rng.normal(0, jitter)

    blobs(max(4, h * w // 1600), 3.0, 9.0, (110, 70, 150))  # nuclei
    blobs(max(2, h * w // 6400), 2.0, 6.0, (190, 60, 70))  # red blood cells
    img[: h // 8, :, :] = 245 + rng.normal(0, 3, (h // 8, w, 3))  # glass
    return np.clip(img, 0, 255).astype(np.float32)


def tile_seeds(seed: int, n: int) -> List[int]:
    """The seeds of a run's ``n`` tiles: any whole number maps to distinct
    non-negative ones."""
    return [(seed % 2**63) * 64 + k for k in range(n)]


def morris_trajectories(
    space: Space, n_trajectories: int, *, seed: int
) -> List[List[ParamSet]]:
    """MOAT design: each trajectory starts at a random grid point and moves
    one parameter at a time by a random number of grid steps, giving
    ``dim + 1`` sets. Returns one list of sets per trajectory."""
    rng = np.random.default_rng(seed)
    names = list(space)
    trajectories = []
    for _ in range(n_trajectories):
        idx = {k: rng.integers(0, len(space[k])) for k in names}
        cur = {k: space[k][idx[k]] for k in names}
        sets = [paramset(cur)]
        for j in rng.permutation(len(names)):
            k = names[j]
            card = len(space[k])
            if card > 1:
                step = int(rng.integers(1, max(2, card // 2)))
                idx[k] = (idx[k] + step) % card
                cur[k] = space[k][idx[k]]
            sets.append(paramset(cur))
        trajectories.append(sets)
    return trajectories


def saltelli_rows(space: Space, n_base: int, *, seed: int) -> List[List[ParamSet]]:
    """Saltelli cross-sampling, grouped by base row: row j is
    ``[A_j, B_j, A_B^(0)_j, ..., A_B^(d-1)_j]``, ``d + 2`` sets."""
    rng = np.random.default_rng(seed)
    d = len(space)
    a = rng.random((n_base, d))
    b = rng.random((n_base, d))
    blocks = [a, b]
    for i in range(d):
        ab = a.copy()
        ab[:, i] = b[:, i]
        blocks.append(ab)
    sets = quantise(space, np.concatenate(blocks, axis=0))
    return [[sets[k * n_base + j] for k in range(d + 2)] for j in range(n_base)]


DESIGNS = {
    "moat": lambda space, size, seed: morris_trajectories(space, size, seed=seed),
    "saltelli": lambda space, size, seed: saltelli_rows(space, size, seed=seed),
}


def design(kind: str, space: Space, size: int, *, seed: int) -> List[List[ParamSet]]:
    """The study's parameter sets, in the groups the harness feeds."""
    return DESIGNS[kind](space, size, seed)


def flatten(groups: Sequence[Sequence[ParamSet]]) -> List[ParamSet]:
    return [ps for g in groups for ps in g]
