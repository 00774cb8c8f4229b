"""The benchmark's copies of the traffic generators give byte-identical
output to the program's versions."""

import hashlib

import numpy as np
import pytest

import gen
from repro.app import TABLE1_SPACE, synthetic_tile
from repro.core import morris_trajectories, saltelli_sample


def test_table1_space_is_the_programs():
    assert list(gen.TABLE1) == list(TABLE1_SPACE.names)
    for p in TABLE1_SPACE.params:
        assert tuple(gen.TABLE1[p.name]) == p.values
    assert gen.default_params(gen.TABLE1) == TABLE1_SPACE.default()


@pytest.mark.parametrize("seed", [0, 1, 7, 3_000_000_123])
@pytest.mark.parametrize("shape", [(48, 48), (64, 96), (256, 256)])
def test_synthetic_tile_bytes(seed, shape):
    mine = gen.synthetic_tile(*shape, seed=seed)
    theirs = synthetic_tile(*shape, seed=seed)
    assert mine.dtype == theirs.dtype == np.float32
    assert hashlib.sha256(mine.tobytes()).digest() == hashlib.sha256(theirs.tobytes()).digest()


@pytest.mark.parametrize("seed", [0, 5, 2**31 + 11])
@pytest.mark.parametrize("n", [1, 3])
def test_morris_trajectories(seed, n):
    sets, _moves = morris_trajectories(TABLE1_SPACE, n, seed=seed)
    groups = gen.morris_trajectories(gen.TABLE1, n, seed=seed)
    assert [len(g) for g in groups] == [TABLE1_SPACE.dim + 1] * n
    assert gen.flatten(groups) == sets


@pytest.mark.parametrize("seed", [0, 5, 2**31 + 11])
@pytest.mark.parametrize("n_base", [1, 4])
def test_saltelli_rows(seed, n_base):
    sets, n = saltelli_sample(TABLE1_SPACE, n_base, seed=seed)
    rows = gen.saltelli_rows(gen.TABLE1, n_base, seed=seed)
    d = TABLE1_SPACE.dim
    assert [len(r) for r in rows] == [d + 2] * n_base
    for j, row in enumerate(rows):
        assert row == [sets[k * n + j] for k in range(d + 2)]
    assert sorted(gen.flatten(rows)) == sorted(sets)
