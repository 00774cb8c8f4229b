"""The control: the plain reference computed in bfloat16, the nearest
precision below the float32 that the configuration states, put in the
program's place. The comparison has to reject it. ``bench/control.py``
reads the same numbers on the chip at the cells' own sizes."""

import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

import check
import gen
from helpers import BENCH

CONFIGS = ["moat800-t1-4k", "vbd7990-t1-4k"]


def _answers(config, dtype, edge=256):
    space = gen.SPACES[config["space"]]
    tiles = [gen.synthetic_tile(edge, edge, seed=s) for s in gen.tile_seeds(7, 2)]
    group = gen.design(config["design"], space, config["design_size"], seed=config["design_seed"])[0]
    sample = [(0, dict(group[0])), (1, dict(group[-1]))]
    return check.reference_answers(tiles, dict(gen.default_params(space)), sample, dtype)


@pytest.mark.parametrize("name", CONFIGS)
def test_bfloat16_reference_is_not_correct(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        config = json.load(f)
    f32 = _answers(config, jnp.float32)
    bf16 = _answers(config, jnp.bfloat16)
    numbers = check.compare(bf16[0], bf16[1], f32[0], f32[1])
    correct, shown = check.judge(numbers, config["limits"])
    assert not correct, shown
    # and the float32 reference against itself is correct
    assert check.judge(check.compare(f32[0], f32[1], f32[0], f32[1]), config["limits"])[0]


def test_mismatch_share():
    a = np.zeros((4, 4), bool)
    b = a.copy()
    b[0, 0] = True
    assert check.mismatch_share(a, a) == 0.0
    assert check.mismatch_share(a, b) == 1 / 16
