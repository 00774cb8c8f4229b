"""Pallas TPU kernel for binary fill-holes on a bit-packed tile.

Fill-holes keeps the mask and adds every background pixel that the image
border cannot reach through background. The XLA path
(``ref.fill_holes_ref``) finds the reachable background as a float32
reconstruction: one HBM round trip of a 4-byte plane per sweep, and the
wavefront crosses about half a 4096² tile, some 2,500 sweeps a call. Here
the planes hold one bit a pixel, the whole fixpoint runs inside one kernel
launch with both planes resident in VMEM, and HBM sees one read of the mask
and one write of the result.

Layout. Bit ``b`` of word ``j`` in a row holds column ``b * wp + j``, where
``wp`` is the row's word count, padded to a multiple of 128 lanes. Packing
and unpacking are then 32 lane-aligned column slabs of the plane, one XLA
fusion each way with no relayout. Rows are padded to a multiple of the
kernel's row chunk. Padding is 0, never passable.

Neighbours. Column ``x - 1`` of word ``j`` is the same bit of word ``j - 1``
(a lane roll), except in word 0, where it is bit ``b - 1`` of the row's last
word (the roll's wrap, shifted up one bit); column ``x + 1`` mirrors that.
Rows above and below come from sublane rolls of a chunk with one vreg of
halo on each side. 8-connectivity dilates separably: ``h = x | left | right``
then ``h | up(h) | down(h)``.

Sweeps. ``reach <- dilate(reach) & free`` is a Jacobi step, as the XLA loop
takes it: chunks are rewritten in place, top to bottom, and the old rows of
the chunk above are carried so that no chunk sees a value of the current
sweep. The loop stops at the first sweep that changes nothing, so the sweep
count equals the XLA loop's iteration count, and the fixpoint is the same
set: the result is exact.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
SUBLANES = 8
WORD_BITS = 32
CHUNK_ROWS = 256  # rows a sweep rewrites at a time: 32 vregs at 128 words a row
VMEM_BUDGET = 16 << 20  # bytes the two packed planes (free, reach) may take
_VMEM_HEADROOM = 8 << 20  # the sweep's chunk values and Mosaic's own scratch


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _chunk_rows(h: int) -> int:
    return min(CHUNK_ROWS, _round_up(h, SUBLANES))


def packed_shape(h: int, w: int) -> Tuple[int, int]:
    """``(rows, words)`` of an ``h x w`` plane packed 32 columns to a word."""
    return _round_up(h, _chunk_rows(h)), _round_up(-(-w // WORD_BITS), LANES)


def packed_bytes(h: int, w: int) -> int:
    """VMEM bytes of the kernel's two packed planes for an ``h x w`` tile."""
    hp, wp = packed_shape(h, w)
    return 2 * hp * wp * 4


def fits_vmem(h: int, w: int) -> bool:
    return packed_bytes(h, w) <= VMEM_BUDGET


def pack(bits: jax.Array) -> jax.Array:
    """Bool ``(h, w)`` -> uint32 ``packed_shape(h, w)``; bit ``b`` of word
    ``j`` is column ``b * wp + j``."""
    h, w = bits.shape
    hp, wp = packed_shape(h, w)
    x = jnp.pad(bits, ((0, hp - h), (0, WORD_BITS * wp - w))).astype(jnp.uint32)
    words = x[:, :wp]
    for b in range(1, WORD_BITS):
        words = words | (x[:, b * wp : (b + 1) * wp] << b)
    return words


def unpack(words: jax.Array, h: int, w: int) -> jax.Array:
    """Inverse of :func:`pack`: uint32 ``(hp, wp)`` -> bool ``(h, w)``."""
    slabs = [(words >> b) & 1 for b in range(WORD_BITS)]
    return jnp.concatenate(slabs, axis=1)[:h, :w].astype(jnp.bool_)


def _horizontal(x: jax.Array) -> jax.Array:
    """``x`` OR its left and right neighbours, in the strided bit layout."""
    wp = x.shape[1]
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    prev = pltpu.roll(x, 1, 1)  # word j - 1; word 0 gets the last word
    nxt = pltpu.roll(x, wp - 1, 1)  # word j + 1; the last word gets word 0
    left = jnp.where(lane == 0, prev << 1, prev)
    right = jnp.where(lane == wp - 1, nxt >> 1, nxt)
    return x | left | right


def _vertical(win: jax.Array) -> jax.Array:
    """``win`` OR the rows above and below (rolls wrap; the caller keeps
    only rows with both neighbours inside the window)."""
    n = win.shape[0]
    return win | pltpu.roll(win, 1, 0) | pltpu.roll(win, n - 1, 0)


def _fill_holes_kernel(free_ref, reach_ref, sweeps_ref, *, h: int, w: int, conn: int, rows: int):
    hp, wp = free_ref.shape
    chunks = hp // rows
    zero8 = jnp.zeros((SUBLANES, wp), jnp.uint32)
    ones = jnp.uint32(0xFFFFFFFF)

    def seed(i, _):
        r0 = pl.multiple_of(i * rows, rows)
        row = r0 + jax.lax.broadcasted_iota(jnp.int32, (rows, wp), 0)
        lane = jax.lax.broadcasted_iota(jnp.int32, (rows, wp), 1)
        edge = jnp.where((row == 0) | (row == h - 1), ones, jnp.uint32(0))
        edge = edge | jnp.where(lane == 0, jnp.uint32(1), jnp.uint32(0))  # column 0
        last = jnp.uint32(1 << ((w - 1) // wp))  # column w - 1
        edge = edge | jnp.where(lane == (w - 1) % wp, last, jnp.uint32(0))
        reach_ref[pl.ds(r0, rows), :] = free_ref[pl.ds(r0, rows), :] & edge
        return 0

    jax.lax.fori_loop(0, chunks, seed, 0)

    def chunk(i, carry):
        above, changed = carry
        r0 = pl.multiple_of(i * rows, rows)
        cur = reach_ref[pl.ds(r0, rows), :]
        below = reach_ref[pl.ds(jnp.minimum(r0 + rows, hp - SUBLANES), SUBLANES), :]
        below = jnp.where(i == chunks - 1, zero8, below)
        win = jnp.concatenate([above, cur, below], axis=0)
        if conn == 8:
            grown = _vertical(_horizontal(win))[SUBLANES : SUBLANES + rows]
        else:
            grown = _horizontal(cur) | _vertical(win)[SUBLANES : SUBLANES + rows]
        new = grown & free_ref[pl.ds(r0, rows), :]
        reach_ref[pl.ds(r0, rows), :] = new
        return cur[rows - SUBLANES :], changed | (new ^ cur)

    def sweep(state):
        n, _ = state
        _, changed = jax.lax.fori_loop(
            0, chunks, chunk, (zero8, jnp.zeros((rows, wp), jnp.uint32))
        )
        return n + 1, jnp.max(jnp.where(changed != 0, 1.0, 0.0)) > 0.0

    n, _ = jax.lax.while_loop(lambda s: s[1], sweep, (jnp.int32(0), jnp.bool_(True)))
    sweeps_ref[0, 0] = n


@functools.partial(jax.jit, static_argnames=("conn", "interpret", "return_sweeps"))
def fill_holes_pallas(
    mask: jax.Array,
    *,
    conn: int = 4,
    interpret: bool = False,
    return_sweeps: bool = False,
):
    """Binary fill-holes of a bool ``mask``; equals ``ref.fill_holes_ref``
    bit for bit. With ``return_sweeps`` also the number of sweeps the
    fixpoint took (the XLA loop's iteration count), an int32 scalar."""
    if conn not in (4, 8):
        raise ValueError(f"connectivity must be 4 or 8, got {conn}")
    h, w = mask.shape
    hp, wp = packed_shape(h, w)
    free = pack(~mask)
    reach, sweeps = pl.pallas_call(
        functools.partial(_fill_holes_kernel, h=h, w=w, conn=conn, rows=_chunk_rows(h)),
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)],
        out_specs=(
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((hp, wp), jnp.uint32),
            jax.ShapeDtypeStruct((1, 1), jnp.int32),
        ),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=packed_bytes(h, w) + _VMEM_HEADROOM
        ),
        interpret=interpret,
    )(free)
    out = mask | ~unpack(reach, h, w)
    return (out, sweeps[0, 0]) if return_sweeps else out
