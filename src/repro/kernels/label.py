"""Pallas TPU kernel for the propagation fixpoints of the segmentation:
connected-component labels and the watershed flood (int32, here), and the
float32 reconstruction by dilation (its chunk rule and exactness argument
are in ``morph_recon``).

The XLA loops (``ref.label_components_ref``, ``ref.flood_ref``,
``ref.morph_reconstruct_ref``) take one Jacobi step of the whole plane per
iteration: one HBM round trip of each 4-byte plane per pixel that a value
has to travel. Here the plane
is cut into row blocks that fit the kernel's VMEM budget. A launch (a pass)
visits the blocks in turn: it copies a block and one sublane tile of halo
rows above and below into VMEM, relaxes the block there until nothing in it
changes, and writes it back in place. A value then crosses a whole block in
one launch, and the launch count depends on how often a label must cross a
block boundary, not on the diameter. Passes alternate top-down and
bottom-up, and a block reads the rows of the block visited before it as
that block left them. The loop of passes stops at the first pass that
changes nothing: every block is then stable against its neighbours' final
rows, so the whole plane is at the fixpoint.

Inside a block, a sweep visits 8-row chunks top to bottom and rewrites each
in place by the caller's chunk rule from its neighbours (left and right by
lane rolls onto a padding column, up and down by sublane rolls that take
the chunk above's last row and the chunk below's first row). A chunk is
recomputed only if it or a chunk next to it changed since its last visit;
the block is settled after a sweep that changes nothing. The planes share
one 4-byte element type; the rule may read planes it never writes (the
reconstruction's mask), and only the planes it writes are copied back.

Exactness. The two int32 loops compute the unique fixpoint of a monotone
relaxation, and any schedule of such relaxations that stops only when no
pixel can be lowered reaches that fixpoint, so the order of blocks, chunks
and sweeps does not change the result.

- Labels: each mask pixel takes the minimum flat index of its component.
  A pixel is lowered only to the label of a neighbour in the mask, so every
  value stays the index of a pixel of its component, and a state that no
  neighbour can lower holds that component's minimum everywhere. Labels
  stay flat pixel indices of the unpadded tile.
- The flood: the XLA loop freezes a pixel of ``pre`` at the first step a
  labelled neighbour appears, with the least label among its neighbours.
  That is not order-free as written: a wave that ran ahead inside one block
  would freeze a pixel before a nearer seed's wave arrived. So the kernel
  relaxes the pair (geodesic distance to a seed through ``pre``, label)
  lexicographically, ``(d, l) <- min((d, l), (d_q + 1, l_q))`` over the
  neighbours ``q``, with seeds at ``(0, label)``. The loop labels a pixel at
  distance ``t`` in step ``t``, with the least label of its neighbours
  labelled in step ``t - 1``; by induction on ``t`` that is the least label
  among the seeds at geodesic distance ``t``, which is exactly this
  relaxation's unique fixpoint. Every value the relaxation writes is a
  (distance, label) pair that some path from a seed achieves, so none falls
  below the fixpoint, and one that no neighbour can lower equals it. The
  distance and the label need a plane each (at 4096² neither fits beside
  the other in one word); the label plane is the result.

Sentinels: a pixel outside the mask holds ``BG`` (labels) or ``FAR``
(flood distance) and is never relaxed; neither value can lower a pixel
inside. Padding columns and rows hold them too, and the width is padded by
at least one column so that a lane roll's wrap lands on padding.
"""

from __future__ import annotations

import functools
from typing import Callable, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
SUBLANES = 8
HALO = SUBLANES  # rows copied above and below a block: one sublane tile each
VMEM_BUDGET = 32 << 20  # bytes the block buffers (halo rows included) may take
_VMEM_HEADROOM = 16 << 20  # the sweep's chunk values and Mosaic's own scratch
BG = 2**31 - 1  # label of a pixel outside the mask
FAR = 2**30  # flood distance of a pixel the flood may not enter
UNREACHED = 2**29  # flood distance of a pixel of ``pre`` no seed has reached


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def layout(h: int, w: int, planes: int, block_rows: Optional[int] = None) -> Tuple[int, int, int]:
    """``(rows, blocks, wp)``: the rows of a block, the number of blocks and
    the padded width for an ``h x w`` tile relaxed in ``planes`` 4-byte
    planes. The block's row count follows from the width, so that the
    blocks' buffers with their halo rows fit ``VMEM_BUDGET``; ``block_rows``
    caps it (tests take a few rows a block to cross many blocks)."""
    wp = _round_up(w + 1, LANES)
    fit = (VMEM_BUDGET // (planes * 4 * wp) - 2 * HALO) // SUBLANES * SUBLANES
    fit = max(SUBLANES, min(fit, block_rows or fit))
    blocks = -(-h // fit)
    return _round_up(-(-h // blocks), SUBLANES), blocks, wp


def _lexmin(a, b):
    """The lexicographically smaller of two (distance, label) pairs."""
    take = (b[0] < a[0]) | ((b[0] == a[0]) & (b[1] < a[1]))
    return jnp.where(take, b[0], a[0]), jnp.where(take, b[1], a[1])


def stencil(bufs, r, row):
    """The 8-row chunk at row ``r`` of each buffer, as ``(up, cur, down)``:
    each pixel's upper neighbour, itself and its lower neighbour, the chunk
    above's last row and the chunk below's first row included."""
    above = [b[pl.ds(pl.multiple_of(r - SUBLANES, SUBLANES), SUBLANES), :] for b in bufs]
    cur = [b[pl.ds(r, SUBLANES), :] for b in bufs]
    below = [b[pl.ds(pl.multiple_of(r + SUBLANES, SUBLANES), SUBLANES), :] for b in bufs]
    up = tuple(
        jnp.where(row == 0, pltpu.roll(a, 1, 0), pltpu.roll(c, 1, 0))
        for a, c in zip(above, cur)
    )
    down = tuple(
        jnp.where(row == SUBLANES - 1, pltpu.roll(b, SUBLANES - 1, 0), pltpu.roll(c, SUBLANES - 1, 0))
        for b, c in zip(below, cur)
    )
    return up, tuple(cur), down


def _labels_chunk(bufs, r, row, left_right, conn):
    """New labels of the chunk at row ``r``, and the pixels that changed."""
    (up,), (cur,), (down,) = stencil(bufs, r, row)
    if conn == 8:  # the 3x3 minimum, separably: rows first, then columns
        v = jnp.minimum(jnp.minimum(up, cur), down)
        new = jnp.minimum(v, jnp.minimum(*left_right(v)))
    else:
        new = jnp.minimum(jnp.minimum(up, down), jnp.minimum(cur, jnp.minimum(*left_right(cur))))
    new = jnp.where(cur == BG, BG, new)
    return (new,), new != cur


def _flood_chunk(bufs, r, row, left_right, conn):
    """New (distance, label) pairs of the chunk at row ``r``, and the pixels
    that changed."""
    up, cur, down = stencil(bufs, r, row)
    if conn == 8:  # the 3x3 lexicographic minimum (the pixel itself loses at d + 1)
        v = _lexmin(_lexmin(up, cur), down)
        (ld, rd), (ll, rl) = left_right(v[0]), left_right(v[1])
        nd, nl = _lexmin(_lexmin(v, (ld, ll)), (rd, rl))
    else:
        (ld, rd), (ll, rl) = left_right(cur[0]), left_right(cur[1])
        nd, nl = _lexmin(_lexmin(up, down), _lexmin((ld, ll), (rd, rl)))
    nd = nd + 1
    better = ((nd < cur[0]) | ((nd == cur[0]) & (nl < cur[1]))) & (cur[0] != FAR)
    return (jnp.where(better, nd, cur[0]), jnp.where(better, nl, cur[1])), better


def _relax_kernel(
    rev_ref, *refs, chunk, planes: int, written: int, rows: int, blocks: int, conn: int
):
    ins, refs = refs[:planes], refs[planes:]
    # a written plane's input aliases its output: read and write through the latter
    planes_ref, (changed_ref,) = refs[:written] + ins[written:], refs[written : written + 1]
    bufs = refs[written + 1 : written + planes + 1]
    sem, last = refs[written + planes + 1 :]  # last[k + 1]: the sweep in which chunk k last changed
    wp = bufs[0].shape[1]
    chunks = rows // SUBLANES
    row = jax.lax.broadcasted_iota(jnp.int32, (SUBLANES, wp), 0)

    def left_right(x):
        return pltpu.roll(x, 1, 1), pltpu.roll(x, wp - 1, 1)

    def update(k):
        r = pl.multiple_of(HALO + k * SUBLANES, SUBLANES)
        new, moved = chunk(bufs, r, row, left_right, conn)
        for b, n in zip(bufs, new):
            b[pl.ds(r, SUBLANES), :] = n
        return jnp.max(jnp.where(moved, 1.0, 0.0)) > 0.0

    def settle():
        """Relax the block in VMEM until a sweep changes nothing; whether
        the first sweep changed anything."""

        def mark(k, _):
            last[k] = jnp.where((k == 0) | (k == chunks + 1), -2, 0)
            return 0

        jax.lax.fori_loop(0, chunks + 2, mark, 0)

        def sweep(state):
            s, _, first = state

            def chunk(k, changed):
                active = jnp.maximum(jnp.maximum(last[k], last[k + 1]), last[k + 2]) >= s - 1
                moved = jax.lax.cond(active, lambda: update(k), lambda: jnp.bool_(False))

                @pl.when(moved)
                def _():
                    last[k + 1] = s

                return changed | moved

            changed = jax.lax.fori_loop(0, chunks, chunk, False)
            return s + 1, changed, jnp.where(s == 1, changed, first)

        _, _, first = jax.lax.while_loop(
            lambda st: st[1], sweep, (jnp.int32(1), jnp.bool_(True), jnp.bool_(False))
        )
        return first

    def block(i, changed):
        b = jnp.where(rev_ref[0, 0] != 0, blocks - 1 - i, i)
        r0 = pl.multiple_of(b * rows, SUBLANES)
        copies = [
            pltpu.make_async_copy(p.at[pl.ds(r0, rows + 2 * HALO)], buf, sem.at[j])
            for j, (p, buf) in enumerate(zip(planes_ref, bufs))
        ]
        for c in copies:
            c.start()
        for c in copies:
            c.wait()
        moved = settle()

        @pl.when(moved)
        def _():
            back = [
                pltpu.make_async_copy(
                    buf.at[pl.ds(HALO, rows)], p.at[pl.ds(r0 + HALO, rows)], sem.at[j]
                )
                for j, (p, buf) in enumerate(zip(planes_ref[:written], bufs))
            ]
            for c in back:
                c.start()
            for c in back:
                c.wait()

        return changed | moved

    changed_ref[0, 0] = jax.lax.fori_loop(0, blocks, block, False).astype(jnp.int32)


def launch(
    chunk: Callable,
    shape: Tuple[int, int],
    dtype,
    *,
    planes: int,
    written: int,
    rows: int,
    conn: int,
    interpret: bool,
):
    """One pass over padded planes of ``shape`` (blocks of ``rows`` rows,
    ``HALO`` rows above the first and below the last):
    ``call(rev, *planes) -> (*written planes, changed)``, each ``(1, 1)``
    int32 scalar in SMEM. ``chunk`` is the rule that rewrites one 8-row
    chunk (``chunk(bufs, r, row, left_right, conn) -> (new planes,
    changed)``); it rewrites the first ``written`` of the ``planes`` planes
    and only reads the rest."""
    blocks, wp = (shape[0] - 2 * HALO) // rows, shape[1]
    out = jax.ShapeDtypeStruct(shape, dtype)
    return pl.pallas_call(
        functools.partial(
            _relax_kernel,
            chunk=chunk, planes=planes, written=written, rows=rows, blocks=blocks, conn=conn,
        ),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)]
        + [pl.BlockSpec(memory_space=pl.ANY)] * planes,
        out_specs=(pl.BlockSpec(memory_space=pl.ANY),) * written
        + (pl.BlockSpec(memory_space=pltpu.SMEM),),
        out_shape=(out,) * written + (jax.ShapeDtypeStruct((1, 1), jnp.int32),),
        scratch_shapes=[pltpu.VMEM((rows + 2 * HALO, wp), dtype)] * planes
        + [pltpu.SemaphoreType.DMA((planes,)), pltpu.SMEM((rows // SUBLANES + 2,), jnp.int32)],
        input_output_aliases={1 + j: j for j in range(written)},
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=planes * (rows + 2 * HALO) * wp * 4 + _VMEM_HEADROOM
        ),
        interpret=interpret,
    )


def relax(
    chunk: Callable,
    planes: Sequence[jax.Array],
    fills: Sequence[Union[int, float]],
    *,
    conn: int,
    interpret: bool,
    block_rows: Optional[int],
    written: Optional[int] = None,
):
    """Relax ``planes`` (``h x w``, one 4-byte dtype) to the fixpoint of
    ``chunk`` (see :func:`launch`), padding each plane with its ``fills``.
    The first ``written`` planes (all by default) are the rule's to rewrite,
    the rest it only reads. Returns the written planes and the number of
    passes (launches) it took."""
    if conn not in (4, 8):
        raise ValueError(f"connectivity must be 4 or 8, got {conn}")
    n = len(planes)
    written = n if written is None else written
    dtype = planes[0].dtype
    h, w = planes[0].shape
    rows, blocks, wp = layout(h, w, n, block_rows)
    pad = ((HALO, HALO + blocks * rows - h), (0, wp - w))
    padded = tuple(jnp.pad(p, pad, constant_values=f) for p, f in zip(planes, fills))
    call = launch(
        chunk, padded[0].shape, dtype,
        planes=n, written=written, rows=rows, conn=conn, interpret=interpret,
    )
    fixed = padded[written:]

    def body(state):
        cur, k, _ = state
        *cur, changed = call(jnp.full((1, 1), k % 2, jnp.int32), *cur, *fixed)
        return tuple(cur), k + 1, changed[0, 0] != 0

    out, passes, _ = jax.lax.while_loop(
        lambda s: s[2], body, (padded[:written], jnp.int32(0), jnp.bool_(True))
    )
    return tuple(p[HALO : HALO + h, :w] for p in out), passes


_STATIC = ("conn", "interpret", "return_passes", "block_rows")


@functools.partial(jax.jit, static_argnames=_STATIC)
def label_components_pallas(
    mask: jax.Array,
    *,
    conn: int = 8,
    interpret: bool = False,
    return_passes: bool = False,
    block_rows: Optional[int] = None,
):
    """Connected-component labels of a bool ``mask``: each pixel the minimum
    flat index of its component, -1 off the mask; equals
    ``ref.label_components_ref`` bit for bit. With ``return_passes`` also
    the number of launches the fixpoint took, an int32 scalar."""
    h, w = mask.shape
    idx = jnp.arange(h * w, dtype=jnp.int32).reshape(h, w)
    (lab,), passes = relax(
        _labels_chunk, (jnp.where(mask, idx, BG),), (BG,),
        conn=conn, interpret=interpret, block_rows=block_rows,
    )
    out = jnp.where(lab == BG, -1, lab)
    return (out, passes) if return_passes else out


@functools.partial(jax.jit, static_argnames=_STATIC)
def flood_pallas(
    seeds: jax.Array,
    pre: jax.Array,
    *,
    conn: int = 8,
    interpret: bool = False,
    return_passes: bool = False,
    block_rows: Optional[int] = None,
):
    """The watershed's competitive flood: ``seeds`` holds each seed pixel's
    label and ``h * w`` elsewhere; every pixel of ``pre`` that a seed
    reaches through ``pre`` takes the least label among the seeds nearest
    to it. Equals ``ref.flood_ref`` bit for bit. With ``return_passes``
    also the number of launches the fixpoint took, an int32 scalar."""
    h, w = seeds.shape
    big = h * w
    dist = jnp.where(seeds != big, 0, jnp.where(pre, UNREACHED, FAR)).astype(jnp.int32)
    (_, lab), passes = relax(
        _flood_chunk, (dist, seeds), (FAR, big),
        conn=conn, interpret=interpret, block_rows=block_rows,
    )
    return (lab, passes) if return_passes else lab
