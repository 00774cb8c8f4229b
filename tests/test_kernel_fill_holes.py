"""Bit-packed fill-holes kernel vs the XLA loop and the benchmark's plain
reference: shapes, word and lane boundaries, a long spiral and a property
sweep, run in interpret mode on CPU."""

import importlib.util
import pathlib

import numpy as np
import pytest

pytest.importorskip("hypothesis")  # property tests need hypothesis; skip cleanly without it
from hypothesis import given, settings, strategies as st

import jax
import jax.numpy as jnp

from repro.app import ops
from repro.kernels import ops as kops
from repro.kernels import fill_holes as kfill
from repro.kernels.ref import dilate, fill_holes_ref

_REF_PATH = pathlib.Path(__file__).resolve().parents[1] / "bench" / "reference.py"
_spec = importlib.util.spec_from_file_location("bench_reference", _REF_PATH)
bench_ref = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(bench_ref)


def kernel(mask, conn, **kw):
    return kfill.fill_holes_pallas(jnp.asarray(mask), conn=conn, interpret=True, **kw)


def assert_all_agree(mask, conn):
    """Kernel == XLA path of ``ops.fill_holes`` == bench reference, bit for bit."""
    mask = jnp.asarray(mask)
    got = np.asarray(kernel(mask, conn))
    xla = np.asarray(ops.fill_holes(mask, conn=conn))  # the XLA loop off a TPU
    plain = np.asarray(bench_ref.fill_holes(mask, conn))
    np.testing.assert_array_equal(xla, plain)
    np.testing.assert_array_equal(got, xla)
    return got


def xla_iterations(mask, conn):
    """Iterations of the float32 reconstruction loop, counted."""
    inv = (~jnp.asarray(mask)).astype(jnp.float32)
    edge = np.zeros(mask.shape, bool)
    edge[0, :] = edge[-1, :] = edge[:, 0] = edge[:, -1] = True
    seed = jnp.where(jnp.asarray(edge), inv, 0.0)

    def body(s):
        m, _, n = s
        new = jnp.minimum(dilate(m, conn=conn), inv)
        return new, jnp.any(new != m), n + 1

    _, _, n = jax.lax.while_loop(lambda s: s[1], body, (seed, jnp.bool_(True), jnp.int32(0)))
    return int(n)


def random_mask(h, w, seed, density=0.45):
    return np.random.default_rng(seed).random((h, w)) < density


def ring(mask, y0, x0, y1, x1):
    """Draw the closed outline of the box [y0, y1] x [x0, x1]."""
    mask[y0, x0 : x1 + 1] = mask[y1, x0 : x1 + 1] = True
    mask[y0 : y1 + 1, x0] = mask[y0 : y1 + 1, x1] = True


def spiral(n):
    """Concentric square walls, one gap each, gaps alternating top and
    bottom: the background is one corridor from the border to the centre."""
    m = np.zeros((n, n), bool)
    c = n // 2
    for d in range(0, c, 2):
        ring(m, d, d, n - 1 - d, n - 1 - d)
        if (d // 2) % 2 == 0:
            m[d, c] = False
        else:
            m[n - 1 - d, c] = False
    return m


@pytest.mark.parametrize("h,w", [(16, 16), (40, 96), (65, 161), (128, 4096), (24, 4160)])
@pytest.mark.parametrize("conn", [4, 8])
def test_kernel_matches_xla_and_reference(h, w, conn):
    assert_all_agree(random_mask(h, w, seed=h * 7919 + w + conn), conn)


@pytest.mark.parametrize("h,w", [(1, 1), (5, 3), (16, 16), (40, 96), (65, 161), (9, 4160)])
def test_pack_unpack_round_trip(h, w):
    bits = random_mask(h, w, seed=h + w, density=0.5)
    words = kfill.pack(jnp.asarray(bits))
    hp, wp = kfill.packed_shape(h, w)
    assert words.shape == (hp, wp) and words.dtype == jnp.uint32
    assert hp % 8 == 0 and wp % 128 == 0
    np.testing.assert_array_equal(np.asarray(kfill.unpack(words, h, w)), bits)
    # padding packs to 0: nothing outside the image is passable
    assert int(jnp.sum(jax.lax.population_count(words))) == int(bits.sum())


def test_pack_bit_layout():
    """Bit b of word j holds column b * wp + j."""
    h, w = 8, 300
    bits = np.zeros((h, w), bool)
    bits[3, 0] = bits[3, 129] = bits[5, 299] = True
    words = np.asarray(kfill.pack(jnp.asarray(bits)))
    wp = kfill.packed_shape(h, w)[1]
    assert wp == 128
    assert words[3, 0] == 1 and words[3, 1] == 2
    assert words[5, 299 % wp] == 1 << (299 // wp)
    assert np.count_nonzero(words) == 3


@pytest.mark.parametrize("w", [161, 4160])
@pytest.mark.parametrize("conn", [4, 8])
def test_holes_and_passages_across_word_and_lane_boundaries(w, conn):
    """Boxes straddling the columns where a neighbour is another bit of the
    row's last word (wp - 1 | wp), another vreg of lanes (127 | 128), or the
    next word (31 | 32). Half of them open onto a 1 px channel from the top
    edge, entering just right of the boundary: the reach crosses it inside."""
    h = kfill.CHUNK_ROWS + 40  # two row chunks: the stripes cross their seam
    wp = kfill.packed_shape(h, w)[1]
    m = np.zeros((h, w), bool)
    cuts = sorted({31, 127, wp - 1} | ({255} if w > 256 else set()))
    for k, c in enumerate(cuts):
        y0 = 4 + 36 * (k % 4)
        ring(m, y0, c - 3, y0 + 8, c + 4)
        if k % 2:  # a passage: a gap in the box, then a channel to the top edge
            m[y0, c + 1] = False
            m[: y0, c : c + 3] = True
            m[: y0, c + 1] = False
    seam = slice(kfill.CHUNK_ROWS - 4, kfill.CHUNK_ROWS + 4)
    m[seam, :] = np.where(np.arange(w) % 5 == 0, True, m[seam, :])
    out = assert_all_agree(m, conn)
    for k, c in enumerate(cuts):
        y0 = 4 + 36 * (k % 4)
        assert bool(out[y0 + 4, c]) == (k % 2 == 0), (c, k)


@pytest.mark.parametrize("conn", [4, 8])
@pytest.mark.parametrize("value", [False, True])
def test_uniform_masks(value, conn):
    m = np.full((40, 96), value)
    out, sweeps = kernel(m, conn, return_sweeps=True)
    assert bool(jnp.all(out == value))
    assert int(sweeps) == xla_iterations(m, conn)
    assert_all_agree(m, conn)


@pytest.mark.parametrize("conn", [4, 8])
def test_spiral_converges_and_counts_the_xla_iterations(conn):
    m = spiral(49)
    m[24, 24] = True  # the centre pixel, at the corridor's end
    out, sweeps = kernel(m, conn, return_sweeps=True)
    assert int(sweeps) > 500
    assert int(sweeps) == xla_iterations(m, conn)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(fill_holes_ref(jnp.asarray(m), conn)))
    # a closed spiral: every corridor pixel becomes a hole
    closed = m.copy()
    closed[0, 24] = True
    assert bool(jnp.all(kernel(closed, conn)))


@pytest.mark.parametrize("conn", [4, 8])
def test_sweeps_stay_jacobi_steps_across_row_chunks(conn):
    """A corridor down through three row chunks advances one row a sweep:
    no chunk may see rows its neighbour rewrote in the same sweep."""
    h = 2 * kfill.CHUNK_ROWS + 22
    m = np.ones((h, 16), bool)
    m[: h - 10, 8] = False
    m[h - 9 : h - 1, 2:6] = False  # a hole at the bottom
    out, sweeps = kernel(m, conn, return_sweeps=True)
    assert int(sweeps) == xla_iterations(m, conn) == h - 10
    assert bool(jnp.all(out[:, :8])) and not bool(out[100, 8])


def test_connectivity_decides_a_diagonal_passage():
    """A cell whose only opening is a diagonal step: a hole under 4-conn,
    reached from the border under 8-conn."""
    m = np.zeros((16, 16), bool)
    ring(m, 4, 4, 10, 10)
    m[4, 4] = False  # the corner opens diagonally onto (3, 3) and (5, 5)
    four = np.asarray(assert_all_agree(m, 4))
    eight = np.asarray(assert_all_agree(m, 8))
    assert four[7, 7] and not eight[7, 7]


@settings(max_examples=15, deadline=None)
@given(
    shape=st.sampled_from([(24, 40), (33, 130), (300, 40)]),
    conn=st.sampled_from([4, 8]),
    density=st.floats(min_value=0.2, max_value=0.7),
    seed=st.integers(min_value=0, max_value=2**31),
)
def test_property_kernel_equals_xla(shape, conn, density, seed):
    m = random_mask(*shape, seed=seed, density=density)
    got, sweeps = kernel(m, conn, return_sweeps=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(fill_holes_ref(jnp.asarray(m), conn)))
    assert int(sweeps) == xla_iterations(m, conn)


def test_dispatch_off_tpu_runs_the_xla_loop():
    m = jnp.asarray(random_mask(24, 40, seed=3))
    np.testing.assert_array_equal(
        np.asarray(kops.fill_holes(m, conn=8)), np.asarray(fill_holes_ref(m, 8))
    )


def test_vmem_budget_takes_4k_tiles_and_refuses_9k():
    assert kfill.packed_bytes(4096, 4096) == 4 << 20
    assert kfill.fits_vmem(4096, 4096)
    assert kfill.fits_vmem(8192, 8192)
    assert not kfill.fits_vmem(9216, 9216)
    assert not kfill.fits_vmem(11264, 11264)
