"""Row-blocked label and flood kernel vs the XLA loops: random masks,
corridors that cross many blocks, components and equidistant seeds on block
boundaries, empty and full masks, ragged shapes and the launch count, run in
interpret mode on CPU."""

import functools

import numpy as np
import pytest

pytest.importorskip("hypothesis")  # property tests need hypothesis; skip cleanly without it
from hypothesis import given, settings, strategies as st

import jax
import jax.numpy as jnp

from repro.app import ops
from repro.kernels import label as klabel
from repro.kernels import ops as kops
from repro.kernels import ref as kref
from repro.kernels.ref import dilate, neighbors, shift2d

label_components_ref = jax.jit(kref.label_components_ref, static_argnames="conn")
flood_ref = jax.jit(kref.flood_ref, static_argnames="conn")


def labels(mask, conn, block_rows=None):
    return klabel.label_components_pallas(
        jnp.asarray(mask), conn=conn, interpret=True, return_passes=True, block_rows=block_rows
    )


def flood(seeds, pre, conn, block_rows=None):
    return klabel.flood_pallas(
        jnp.asarray(seeds), jnp.asarray(pre), conn=conn, interpret=True, return_passes=True,
        block_rows=block_rows,
    )


@functools.partial(jax.jit, static_argnames="conn")
def _label_iterations(mask, conn):
    h, w = mask.shape
    big = h * w
    lab = jnp.where(mask, jnp.arange(h * w, dtype=jnp.int32).reshape(h, w), big)

    def body(s):
        cur, _, n = s
        new = cur
        for dy, dx in neighbors(conn):
            new = jnp.minimum(new, shift2d(cur, dy, dx, big))
        new = jnp.where(mask, new, big)
        return new, jnp.any(new != cur), n + 1

    return jax.lax.while_loop(lambda s: s[1], body, (lab, jnp.bool_(True), jnp.int32(0)))[2]


def label_iterations(mask, conn):
    """Iterations of the XLA label loop, counted."""
    return int(_label_iterations(jnp.asarray(mask), conn))


@functools.partial(jax.jit, static_argnames="conn")
def _flood_iterations(seeds, pre, conn):
    big = seeds.size

    def body(s):
        cur, _, n = s
        nb = jnp.full_like(cur, big)
        for dy, dx in neighbors(conn):
            nb = jnp.minimum(nb, shift2d(cur, dy, dx, big))
        new = jnp.where((cur == big) & pre, nb, cur)
        return new, jnp.any(new != cur), n + 1

    return jax.lax.while_loop(lambda s: s[1], body, (seeds, jnp.bool_(True), jnp.int32(0)))[2]


def flood_iterations(seeds, pre, conn):
    """Iterations of the XLA flood loop, counted."""
    return int(_flood_iterations(jnp.asarray(seeds), jnp.asarray(pre), conn))


def assert_labels_exact(mask, conn, block_rows=None):
    got, passes = labels(mask, conn, block_rows)
    want = label_components_ref(jnp.asarray(mask), conn)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert 1 <= int(passes) <= label_iterations(mask, conn)
    return np.asarray(got), int(passes)


def assert_flood_exact(seeds, pre, conn, block_rows=None):
    got, passes = flood(seeds, pre, conn, block_rows)
    want = flood_ref(jnp.asarray(seeds), jnp.asarray(pre), conn)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert 1 <= int(passes) <= flood_iterations(seeds, pre, conn)
    return np.asarray(got), int(passes)


def random_mask(h, w, seed, density=0.55):
    return np.random.default_rng(seed).random((h, w)) < density


def watershed_seeds(pre):
    """The seeds ``ops.watershed_split`` floods from: one label per regional
    maximum of the distance transform inside ``pre``."""
    return _watershed_seeds(jnp.asarray(pre))


@jax.jit
def _watershed_seeds(pre):
    dist = ops.distance_transform(pre, conn=4)
    maxima = (dist >= dilate(dist, conn=8)) & pre & (dist > 1.0)
    return jnp.where(maxima, label_components_ref(maxima, 8), pre.size)


def random_seeds(pre, seed, share=0.03):
    """Seeds at random pixels of ``pre`` with random labels, some repeated."""
    rng = np.random.default_rng(seed)
    h, w = pre.shape
    at = pre & (rng.random((h, w)) < share)
    return np.where(at, rng.integers(0, max(1, h * w // 8), (h, w)), h * w).astype(np.int32)


def spiral(n):
    """A one-pixel corridor that winds inward from the top-left corner,
    one pixel of background between its turns: right, down, left, up,
    right, ..., each leg two shorter than the one two legs before. Returns
    the mask and the corridor's inner end."""
    m = np.zeros((n, n), bool)
    y = x = 0
    m[0, 0] = True
    legs = [n - 1, n - 1, n - 1] + [k for k in range(n - 3, 0, -2) for _ in (0, 1)]
    for i, length in enumerate(legs):
        dy, dx = [(0, 1), (1, 0), (0, -1), (-1, 0)][i % 4]
        for _ in range(length):
            y, x = y + dy, x + dx
            m[y, x] = True
    return m, (y, x)


SHAPES = [(16, 16), (37, 130), (65, 161), (128, 256)]


@pytest.mark.parametrize("block_rows", [None, 8, 24])
@pytest.mark.parametrize("h,w", SHAPES)
@pytest.mark.parametrize("conn", [4, 8])
def test_labels_match_xla_on_random_masks(h, w, conn, block_rows):
    assert_labels_exact(random_mask(h, w, seed=h * 7919 + w + conn), conn, block_rows)


@pytest.mark.parametrize("block_rows", [None, 8, 24])
@pytest.mark.parametrize("h,w", SHAPES)
@pytest.mark.parametrize("conn", [4, 8])
def test_flood_matches_xla_from_watershed_seeds(h, w, conn, block_rows):
    pre = random_mask(h, w, seed=h + 31 * w + conn, density=0.7)
    assert_flood_exact(watershed_seeds(pre), pre, conn, block_rows)


@pytest.mark.parametrize("block_rows", [None, 8])
@pytest.mark.parametrize("conn", [4, 8])
def test_flood_matches_xla_from_random_seeds_with_shared_labels(conn, block_rows):
    pre = random_mask(65, 161, seed=5, density=0.75)
    assert_flood_exact(random_seeds(pre, seed=6), pre, conn, block_rows)


@pytest.mark.parametrize("block_rows", [8, 16])
@pytest.mark.parametrize("conn", [4, 8])
def test_spiral_crosses_many_blocks(conn, block_rows):
    """One corridor that crosses every block boundary many times: the
    smallest index must walk all of it, and a seed at each end floods it."""
    m, end = spiral(41)
    got, passes = assert_labels_exact(m, conn, block_rows)
    assert len(np.unique(got[m])) == 1 and got[m][0] == 0
    assert passes >= 3  # a label had to go back up across a boundary
    seeds = np.full(m.shape, m.size, np.int32)
    seeds[0, 0] = 900  # the outer end
    seeds[end] = 17  # the inner end
    lab, _ = assert_flood_exact(seeds, m, conn, block_rows)
    assert set(np.unique(lab[m])) == {17, 900}


@pytest.mark.parametrize("block_rows", [8, 16])
@pytest.mark.parametrize("conn", [4, 8])
def test_components_that_touch_block_boundaries(conn, block_rows):
    """Boxes whose top or bottom row lies on a block's first or last row,
    diagonal steps across a boundary, and a column that spans every block."""
    h, w = 6 * block_rows + 5, 140
    m = np.zeros((h, w), bool)
    for k, r in enumerate(range(block_rows, h - 4, block_rows)):
        m[r - 1 : r + 1, 4 + 10 * k : 8 + 10 * k] = True  # straddles the boundary
        m[r, 70 + 8 * k] = True  # starts on a block's first row
        m[r - 1, 71 + 8 * k] = True  # diagonal neighbour on the block above's last row
    m[:, w - 3] = True  # one column through every block
    m[:, 127:129] = True  # and across the lane-tile seam
    assert_labels_exact(m, conn, block_rows)
    assert_flood_exact(watershed_seeds(m), m, conn, block_rows)


@pytest.mark.parametrize("first", [7, 50])
@pytest.mark.parametrize("conn", [4, 8])
def test_equidistant_seeds_across_a_block_boundary(conn, first):
    """Two seeds at equal geodesic distance from the pixels of a block's
    first row, one above the boundary and one below: the smaller label takes
    them, whichever block its wave starts in."""
    rows = 8
    h, w = 4 * rows, 130
    pre = np.zeros((h, w), bool)
    pre[:, 10:30] = True
    seeds = np.full((h, w), h * w, np.int32)
    boundary = 2 * rows  # the first row of block 2
    seeds[boundary - 6, 20] = first
    seeds[boundary + 6, 20] = 57 - first
    lab, _ = assert_flood_exact(seeds, pre, conn, rows)
    assert lab[boundary, 20] == min(first, 57 - first)


@pytest.mark.parametrize("block_rows", [None, 8])
@pytest.mark.parametrize("conn", [4, 8])
@pytest.mark.parametrize("value", [False, True])
def test_empty_and_full_masks(value, conn, block_rows):
    m = np.full((40, 96), value)
    got, passes = assert_labels_exact(m, conn, block_rows)
    assert (got == (0 if value else -1)).all()
    if not value:
        assert passes == 1
    seeds = np.full(m.shape, m.size, np.int32)
    seeds[3, 5] = 11
    lab, _ = assert_flood_exact(seeds, m, conn, block_rows)
    assert (lab[m] == 11).all()


@pytest.mark.parametrize("block_rows", [8, 16, 24])
@pytest.mark.parametrize("h,w", [(9, 1), (17, 127), (33, 129), (50, 300)])
def test_ragged_shapes(h, w, block_rows):
    """Heights that are not multiples of the block and widths that are not
    multiples of 128 lanes (the padding must never join two components)."""
    m = random_mask(h, w, seed=h * w + block_rows, density=0.6)
    for conn in (4, 8):
        assert_labels_exact(m, conn, block_rows)
        assert_flood_exact(watershed_seeds(m), m, conn, block_rows)


@settings(max_examples=12, deadline=None)
@given(
    shape=st.sampled_from([(24, 40), (33, 130), (70, 20)]),
    conn=st.sampled_from([4, 8]),
    block_rows=st.sampled_from([None, 8, 16]),
    density=st.floats(min_value=0.3, max_value=0.8),
    seed=st.integers(min_value=0, max_value=2**31),
)
def test_property_kernels_equal_xla(shape, conn, block_rows, density, seed):
    m = random_mask(*shape, seed=seed, density=density)
    assert_labels_exact(m, conn, block_rows)
    assert_flood_exact(random_seeds(m, seed=seed + 1, share=0.05), m, conn, block_rows)


def test_layout_fits_the_vmem_budget_at_every_tile_width():
    for h, w in [(4096, 4096), (9216, 9216), (11264, 11264), (5, 3)]:
        for planes in (1, 2):
            rows, blocks, wp = klabel.layout(h, w, planes)
            assert rows % klabel.SUBLANES == 0 and wp % klabel.LANES == 0 and wp > w
            assert blocks * rows >= h > (blocks - 1) * rows
            assert planes * (rows + 2 * klabel.HALO) * wp * 4 <= klabel.VMEM_BUDGET
    assert klabel.layout(4096, 4096, 1)[1] == 3 and klabel.layout(4096, 4096, 2)[1] == 5


def test_off_tpu_the_operators_run_the_xla_loops():
    """Off a TPU no Pallas kernel is traced, and the results are the
    XLA loops' own."""
    m = jnp.asarray(random_mask(40, 96, seed=9))
    for fn, args in [
        (ops.label_components, (m,)),
        (ops.area_filter, (m, jnp.int32(3), jnp.int32(900))),
        (ops.watershed_split, (m, jnp.int32(5))),
    ]:
        assert "pallas_call" not in str(jax.make_jaxpr(fn)(*args))
    np.testing.assert_array_equal(
        np.asarray(ops.label_components(m, conn=4)), np.asarray(label_components_ref(m, 4))
    )
    seeds = watershed_seeds(m)
    np.testing.assert_array_equal(
        np.asarray(kops.flood(seeds, m, conn=8)), np.asarray(flood_ref(seeds, m, 8))
    )
