"""Integration tests: the pathology workflow + SA study driver.

The critical invariant (paper §II-B): computation reuse is an optimization,
never an approximation — every strategy must produce identical Dice vectors.
"""

import hashlib
import time

import numpy as np
import pytest

from repro.app import TABLE1_SPACE, run_study, synthetic_tile
from repro.app import ops
from repro.app.pipeline import build_workflow
from repro.core import halton_sequence, moat_indices, morris_trajectories
from repro.core.params import ParamSpace
from repro.engine import ClusterSpec, execute_plan, plan_study

import jax.numpy as jnp

H = W = 64


@pytest.fixture(scope="module")
def tile():
    return synthetic_tile(H, W, seed=3)


SMALL_SPACE = ParamSpace.from_dict(
    {
        "B": [210, 230],
        "G": [210, 230],
        "R": [210, 230],
        "T1": [2.5, 5.0],
        "T2": [2.5, 5.0],
        "G1": [20, 40],
        "G2": [10, 20],
        "minS": [2, 10],
        "maxS": [900, 1200],
        "minSPL": [5, 20],
        "minSS": [2, 10],
        "maxSS": [900, 1200],
        "FH": [4, 8],
        "RC": [4, 8],
        "WConn": [4, 8],
    }
)


@pytest.fixture(scope="module")
def param_sets():
    pts = halton_sequence(12, SMALL_SPACE.dim)
    return SMALL_SPACE.quantise(pts)


class TestOps:
    def test_background_mask(self, tile):
        fg = ops.background_mask(jnp.asarray(tile), 230.0, 230.0, 230.0)
        # glass band at the top must be background
        assert float(fg[: H // 8].mean()) < 0.2
        assert float(fg[H // 2 :].mean()) > 0.8

    def test_area_filter_removes_small(self):
        m = jnp.zeros((32, 32), bool).at[2:4, 2:4].set(True).at[10:20, 10:20].set(True)
        out = ops.area_filter(m, 10, 1000)
        assert not bool(out[2, 2]) and bool(out[15, 15])

    def test_fill_holes(self):
        m = jnp.zeros((16, 16), bool).at[4:12, 4:12].set(True).at[7:9, 7:9].set(False)
        out = ops.fill_holes(m, conn=4)
        assert bool(out[7, 7]) and not bool(out[0, 0])

    def test_label_components_two_blobs(self):
        m = jnp.zeros((16, 16), bool).at[2:5, 2:5].set(True).at[10:13, 10:13].set(True)
        lab = ops.label_components(m, conn=8)
        l1, l2 = int(lab[3, 3]), int(lab[11, 11])
        assert l1 != l2 and l1 >= 0 and l2 >= 0
        assert int(lab[0, 0]) == -1
        sizes = ops.component_sizes(lab)
        assert int(sizes[3, 3]) == 9 and int(sizes[0, 0]) == 0

    def test_watershed_splits_touching_blobs(self):
        m = np.zeros((24, 40), bool)
        yy, xx = np.mgrid[0:24, 0:40]
        m |= (yy - 12) ** 2 + (xx - 13) ** 2 < 64
        m |= (yy - 12) ** 2 + (xx - 27) ** 2 < 64
        out = ops.watershed_split(jnp.asarray(m), 5, conn=8)
        lab = ops.label_components(out, conn=8)
        n_comp = len({int(v) for v in np.unique(np.asarray(lab)) if v >= 0})
        assert n_comp >= 2  # split line separates the two discs


class TestSyntheticTile:
    # sha256 of the tile bytes as the full-image blob drawing produced them
    PINNED = {
        (48, 0): "33fac1e3bb219a78176145b7b356b60dc3f9911db083a4ff99b7658cc28aaaae",
        (48, 1): "82818bd1ecf88f2d3ef6f1166d57a7fd67886b7bf7f8e49ebfbf273570ed5527",
        (256, 0): "cd3e02d6b1304fffd73cc632c2e38346118e1a0e25d555959bf99a9b962974c3",
        (256, 1): "8c84ada4126a6e04296b6f682b3440c4be9498f1ccbf6ace9c35d9bcecc1ead3",
    }

    @pytest.mark.parametrize("size,seed", sorted(PINNED))
    def test_bytes_are_pinned(self, size, seed):
        t = synthetic_tile(size, size, seed=seed)
        assert t.dtype == np.float32 and t.shape == (size, size, 3)
        assert hashlib.sha256(t.tobytes()).hexdigest() == self.PINNED[(size, seed)]

    def test_paper_size_tile_builds_in_seconds(self):
        t0 = time.perf_counter()
        t = synthetic_tile(4096, 4096, seed=0)
        elapsed = time.perf_counter() - t0
        assert t.shape == (4096, 4096, 3)
        assert elapsed < 30.0, f"4096x4096 tile took {elapsed:.1f}s"


class TestStudy:
    def test_strategies_agree_exactly(self, tile, param_sets):
        base = run_study(tile, param_sets, strategy="none")
        for strat, kw in [
            ("stage", {}),
            ("rtma", {"max_bucket_size": 4}),
            ("rmsr", {"active_paths": 2}),
        ]:
            out = run_study(tile, param_sets, strategy=strat, **kw)
            np.testing.assert_allclose(out["dice"], base["dice"], atol=0, rtol=0)

    def test_reuse_reduces_task_count(self, tile, param_sets):
        none = run_study(tile, param_sets, strategy="none")
        stage = run_study(tile, param_sets, strategy="stage")
        rmsr = run_study(tile, param_sets, strategy="rmsr")
        assert none["tasks_executed"] == none["tasks_total"]
        assert stage["tasks_executed"] <= none["tasks_executed"]
        assert rmsr["tasks_executed"] <= stage["tasks_executed"]
        assert rmsr["reuse_fraction"] > 0.0

    def test_dice_in_range_and_default_is_one(self, tile):
        ref = TABLE1_SPACE.default()
        out = run_study(tile, [ref], strategy="none")
        assert out["dice"][0] == pytest.approx(1.0)

    def test_engine_acceptance_64_sets(self, tile):
        """ISSUE acceptance: for ≥64 param sets, hybrid's planned peak_bytes
        ≤ rtma's at equal bucket size, hybrid's tasks_executed ≤ the
        per-bucket RTMA count, and execute_plan outputs are bit-identical
        across the three policies and across n_workers ∈ {1, 4}."""
        h, w = tile.shape[:2]
        wf = build_workflow(h, w)
        pts = halton_sequence(64, SMALL_SPACE.dim)
        sets = SMALL_SPACE.quantise(pts)
        plans = {
            pol: plan_study(wf, sets, policy=pol, max_bucket_size=8, active_paths=2)
            for pol in ("rtma", "rmsr", "hybrid")
        }
        assert plans["hybrid"].peak_bytes <= plans["rtma"].peak_bytes
        assert plans["hybrid"].tasks_executed <= plans["rtma"].tasks_executed

        raw = {"raw": jnp.asarray(tile)}
        masks = {}
        for pol, plan in plans.items():
            for workers in (1, 4):
                res = execute_plan(plan, raw, cluster=ClusterSpec(n_workers=workers))
                masks[(pol, workers)] = {
                    rid: np.asarray(out["mask"]) for rid, out in res.outputs.items()
                }
        base = masks[("rtma", 1)]
        assert set(base) == set(range(64))
        for key, got in masks.items():
            for rid in range(64):
                np.testing.assert_array_equal(got[rid], base[rid], err_msg=str((key, rid)))

    def test_moat_end_to_end(self, tile):
        """MOAT screening over a reduced space; reuse must be high because
        consecutive MOAT runs differ in a single parameter."""
        small = SMALL_SPACE
        sets, moves = morris_trajectories(small, 2, seed=1)
        out = run_study(tile, sets, strategy="rmsr")
        res = moat_indices(small, out["dice"], moves)
        assert set(res.mu_star) == set(small.names)
        assert all(v >= 0 for v in res.mu_star.values())
        assert out["reuse_fraction"] > 0.3  # MOAT shares long prefixes
