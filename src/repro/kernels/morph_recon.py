"""Pallas TPU kernel for seg2's grayscale reconstruction by dilation: the
float32 plane settles in VMEM row blocks.

The XLA loop (``ref.morph_reconstruct_ref``) takes one Jacobi step
``r <- min(dilate(r), mask)`` of the whole plane per iteration: one HBM
round trip of two float32 planes per pixel that a value has to travel.
Here the row-block driver of ``kernels.label`` runs it: a launch (a pass)
copies each block of the reconstruction ``r`` and of ``mask``, with one
sublane tile of halo rows, into VMEM, relaxes the block's 8-row chunks
there until nothing in it changes (skipping chunks whose neighbourhood is
settled) and writes ``r`` back; passes alternate direction and stop at the
first that changes nothing. The chunk rule is
``new = min(max over N[p] and p of r, mask_p)``, with ``N`` the 3x3
neighbourhood for conn 8 (rows first, then columns, as the labels do) and
the cross for conn 4; a pixel changed where ``new != r``. With two float32
planes a 4096-wide tile takes 5 blocks of 824 rows.

Exactness: the result equals the XLA loop's bit for bit, whatever the order
of blocks, chunks and sweeps.

- Both start from ``m0 = min(marker, mask)``. Each relaxation
  ``r_p <- min(max_{q in N[p] and p} r_q, mask_p)`` is monotone: it never
  lowers a pixel (``r_p <= mask_p`` holds throughout), and applied to a
  state no higher than a fixpoint ``F >= m0`` it yields a state no higher
  than ``F``. By induction every state lies between ``m0`` and the least
  fixpoint above ``m0``.
- A state in which no relaxation can raise a pixel is a fixpoint above
  ``m0``, hence that least fixpoint; the XLA loop stops at the same one.
- The rule takes only ``max`` and ``min`` of input values, no arithmetic,
  so every value is one of the inputs' and the fixpoint is reached exactly.

Padding rows, columns and the lane roll's wrap column hold a large negative
finite fill in both planes: ``min`` with a padded mask keeps them there, and
``max`` with them never raises a pixel inside.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.label import SUBLANES, relax, stencil

_FILL = -3.0e38  # below every value a tile holds; a plain float, not a traced constant


def _recon_chunk(bufs, r, row, left_right, conn):
    """New reconstruction of the chunk at row ``r`` (``bufs``: ``r``, then
    the mask, read only), and the pixels that rose."""
    (up,), (cur,), (down,) = stencil(bufs[:1], r, row)
    mask = bufs[1][pl.ds(r, SUBLANES), :]
    if conn == 8:  # the 3x3 maximum, separably: rows first, then columns
        v = jnp.maximum(jnp.maximum(up, cur), down)
        new = jnp.maximum(v, jnp.maximum(*left_right(v)))
    else:
        new = jnp.maximum(jnp.maximum(up, down), jnp.maximum(cur, jnp.maximum(*left_right(cur))))
    new = jnp.minimum(new, mask)
    return (new,), new != cur


@functools.partial(
    jax.jit, static_argnames=("conn", "interpret", "return_passes", "block_rows")
)
def morph_reconstruct_pallas(
    marker: jax.Array,
    mask: jax.Array,
    *,
    conn: int = 8,
    interpret: bool = False,
    return_passes: bool = False,
    block_rows: Optional[int] = None,
):
    """Reconstruction by dilation of ``marker`` under ``mask`` (float32),
    to the global fixpoint; equals ``ref.morph_reconstruct_ref`` bit for
    bit. With ``return_passes`` also the number of launches it took, an
    int32 scalar."""
    mask = mask.astype(jnp.float32)
    marker = jnp.minimum(marker.astype(jnp.float32), mask)
    (out,), passes = relax(
        _recon_chunk, (marker, mask), (_FILL, _FILL),
        conn=conn, interpret=interpret, block_rows=block_rows, written=1,
    )
    return (out, passes) if return_passes else out
