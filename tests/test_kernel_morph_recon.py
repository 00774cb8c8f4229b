"""Row-block reconstruction kernel vs the XLA loop: shapes and connectivity,
blocks of a few rows that values cross in both directions, negative gray
values, markers above the mask, plateaus, a one-pixel path that snakes
across blocks, one pass's invariants, the launch count and hypothesis
property tests, run in interpret mode on CPU."""

import functools

import numpy as np
import pytest
pytest.importorskip("hypothesis")  # property tests need hypothesis; skip cleanly without it
from hypothesis import given, settings, strategies as st

import jax
import jax.numpy as jnp

from repro.kernels import label as klabel
from repro.kernels import morph_recon as kmr
from repro.kernels.morph_recon import morph_reconstruct_pallas
from repro.kernels.ref import dilate, morph_reconstruct_ref

recon_ref = jax.jit(morph_reconstruct_ref, static_argnames="conn")


def random_case(h, w, seed):
    rng = np.random.default_rng(seed)
    mask = rng.uniform(0, 100, (h, w)).astype(np.float32)
    marker = np.maximum(mask - rng.uniform(5, 40, (h, w)).astype(np.float32), 0)
    # sprinkle strong peaks so reconstruction has something to propagate
    for _ in range(max(1, h * w // 256)):
        y, x = rng.integers(0, h), rng.integers(0, w)
        marker[y, x] = mask[y, x]
    return jnp.asarray(marker), jnp.asarray(mask)


@functools.partial(jax.jit, static_argnames="conn")
def _xla_steps(marker, mask, conn):
    def body(s):
        m, _, n = s
        new = jnp.minimum(dilate(m, conn=conn), mask)
        return new, jnp.any(new != m), n + 1

    m0 = jnp.minimum(marker, mask)
    return jax.lax.while_loop(lambda s: s[1], body, (m0, jnp.bool_(True), jnp.int32(0)))[2]


def xla_steps(marker, mask, conn):
    """Steps of the XLA loop, counted (the last changes nothing)."""
    return int(_xla_steps(jnp.asarray(marker), jnp.asarray(mask), conn))


def assert_recon_exact(marker, mask, conn, block_rows=None):
    marker, mask = jnp.asarray(marker), jnp.asarray(mask)
    got, passes = morph_reconstruct_pallas(
        marker, mask, conn=conn, interpret=True, return_passes=True, block_rows=block_rows
    )
    want = recon_ref(marker, mask, conn)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert 1 <= int(passes) <= xla_steps(marker, mask, conn)
    return np.asarray(got), int(passes)


def snake(h, w, gap=1):
    """A one-pixel path that runs right along every other row and turns
    down at alternate ends, so it crosses every block boundary; returns its
    mask and its far end."""
    m = np.zeros((h, w), bool)
    rows = list(range(0, h, gap + 1))
    for i, y in enumerate(rows):
        m[y, 1 : w - 1] = True
        if i + 1 < len(rows):
            x = w - 2 if i % 2 == 0 else 1
            m[y : rows[i + 1] + 1, x] = True
    end = (rows[-1], 1 if len(rows) % 2 == 0 else w - 2)
    return m, end


@pytest.mark.parametrize("h,w", [(16, 16), (24, 40), (32, 32), (64, 48), (65, 33)])
@pytest.mark.parametrize("conn", [4, 8])
def test_kernel_matches_ref_shapes(h, w, conn):
    marker, mask = random_case(h, w, seed=h * 1000 + w + conn)
    assert_recon_exact(marker, mask, conn, block_rows=16)


@pytest.mark.parametrize("block_rows", [None, 8, 16, 64])
def test_kernel_block_shape_invariance(block_rows):
    marker, mask = random_case(48, 48, seed=7)
    assert_recon_exact(marker, mask, 8, block_rows)


@pytest.mark.parametrize("block_rows", [8, 16, 24])
@pytest.mark.parametrize("h,w", [(9, 1), (17, 127), (33, 129), (50, 300)])
def test_ragged_shapes(h, w, block_rows):
    """Heights that are not multiples of the block or of 8 rows, and widths
    that are not multiples of 128 lanes: the padding must never raise a
    pixel, nor carry a value round the lane roll's wrap."""
    marker, mask = random_case(h, w, seed=h * w + block_rows)
    for conn in (4, 8):
        assert_recon_exact(marker, mask, conn, block_rows)


@pytest.mark.parametrize("conn", [4, 8])
def test_negative_gray_values(conn):
    """The normalised blue channel can exceed 255, so gray values go below
    zero; padding must stay below every one of them."""
    rng = np.random.default_rng(3)
    mask = rng.uniform(-300, 60, (40, 150)).astype(np.float32)
    mask[5:30, 20:90] = -12.5  # a negative plateau
    marker = mask - rng.uniform(0, 80, mask.shape).astype(np.float32)
    marker[17, 55] = -12.5
    got, _ = assert_recon_exact(marker, mask, conn, block_rows=8)
    assert (got[5:30, 20:90] == -12.5).all()


@pytest.mark.parametrize("conn", [4, 8])
def test_marker_above_the_mask_on_entry(conn):
    """A marker above the mask is clamped to it first, as the XLA loop does."""
    marker, mask = random_case(33, 140, seed=21)
    marker = np.asarray(marker) + np.random.default_rng(22).uniform(-20, 60, marker.shape)
    got, _ = assert_recon_exact(marker.astype(np.float32), mask, conn, block_rows=8)
    assert (got <= np.asarray(mask)).all()


@pytest.mark.parametrize("block_rows", [8, 16])
@pytest.mark.parametrize("conn", [4, 8])
def test_plateaus_and_a_snake_across_blocks(conn, block_rows):
    """Wide plateaus at several heights, and a one-pixel path that crosses
    every block boundary in both directions: the peak at its far end must
    walk all of it, in fewer launches than the XLA loop takes steps."""
    h, w = 6 * block_rows + 3, 140
    path, end = snake(h, w)
    mask = np.where(path, 50.0, 0.0).astype(np.float32)
    mask[:, 0] = 80.0  # a plateau column, joined to the path only diagonally
    marker = np.zeros_like(mask)
    marker[end] = 50.0
    marker[h // 2, 0] = 80.0
    got, passes = assert_recon_exact(marker, mask, conn, block_rows)
    assert (got[path] == 50.0).all()
    assert passes >= 3  # the peak had to go back up across a boundary
    assert passes < xla_steps(marker, mask, conn) // 10
    plateaus = np.full((5 * block_rows, 200), 10.0, np.float32)
    plateaus[:, 60:140] = 30.0
    plateaus[block_rows : 3 * block_rows, 80:120] = 45.0
    seeds = np.zeros_like(plateaus)
    seeds[-1, -1] = 10.0
    seeds[2 * block_rows, 100] = 45.0
    got, _ = assert_recon_exact(seeds, plateaus, conn, block_rows)
    assert (got[:, :60] == 10.0).all() and (got[:, 60:80] == 30.0).all()
    assert (got[block_rows : 3 * block_rows, 80:120] == 45.0).all()


def one_pass(marker, mask, conn, block_rows, rev):
    """One launch of the kernel from ``min(marker, mask)``: the plane after
    it, and whether it changed anything."""
    h, w = mask.shape
    rows, blocks, wp = klabel.layout(h, w, 2, block_rows)
    pad = ((klabel.HALO, klabel.HALO + blocks * rows - h), (0, wp - w))
    r, m = (
        jnp.pad(x, pad, constant_values=kmr._FILL) for x in (jnp.minimum(marker, mask), mask)
    )
    call = klabel.launch(
        kmr._recon_chunk, r.shape, jnp.float32,
        planes=2, written=1, rows=rows, conn=conn, interpret=True,
    )
    out, changed = call(jnp.full((1, 1), rev, jnp.int32), r, m)
    return np.asarray(out[klabel.HALO : klabel.HALO + h, :w]), int(changed[0, 0])


@pytest.mark.parametrize("rev", [0, 1])
@pytest.mark.parametrize("conn", [4, 8])
def test_one_pass_never_lowers_a_pixel_nor_exceeds_the_mask(conn, rev):
    """A pass (either direction) keeps ``min(marker, mask) <= r <= mask``
    and never passes the fixpoint; with the peak in the block it visits
    last, it leaves work for the next pass."""
    h, w = 41, 140
    path, end = snake(h, w)
    rng = np.random.default_rng(5 + conn)
    mask = np.where(path, 50.0, rng.uniform(0, 40, (h, w))).astype(np.float32)
    marker = np.maximum(mask - 30.0, 0.0).astype(np.float32)
    marker[(0, 1) if rev else end] = 50.0
    start = np.minimum(marker, mask)
    out, changed = one_pass(jnp.asarray(marker), jnp.asarray(mask), conn, 8, rev)
    final = np.asarray(recon_ref(jnp.asarray(marker), jnp.asarray(mask), conn))
    assert (out >= start).all() and (out <= mask).all() and (out <= final).all()
    assert changed == 1 and (out > start).any() and (out < final).any()


def test_binary_reconstruction_connectivity():
    """4- vs 8-conn differ on a diagonal bridge — the FH/RC/WConn parameters
    of the paper change results exactly through this mechanism."""
    mask = np.zeros((9, 9), np.float32)
    mask[1:4, 1:4] = 1.0
    mask[4, 4] = 1.0  # diagonal link
    mask[5:8, 5:8] = 1.0
    marker = np.zeros_like(mask)
    marker[2, 2] = 1.0
    r4, _ = assert_recon_exact(marker, mask, 4, block_rows=8)
    r8, _ = assert_recon_exact(marker, mask, 8, block_rows=8)
    assert float(r4[6, 6]) == 0.0  # cannot cross the diagonal with 4-conn
    assert float(r8[6, 6]) == 1.0


@settings(max_examples=15, deadline=None)
@given(
    h=st.integers(min_value=8, max_value=40),
    w=st.integers(min_value=8, max_value=40),
    conn=st.sampled_from([4, 8]),
    block_rows=st.sampled_from([None, 8, 16]),
    seed=st.integers(min_value=0, max_value=2**16),
)
def test_property_kernel_equals_oracle(h, w, conn, block_rows, seed):
    marker, mask = random_case(h, w, seed=seed)
    assert_recon_exact(marker, mask, conn, block_rows)
