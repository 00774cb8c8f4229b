"""Pallas TPU kernel for morphological reconstruction by dilation — the
propagation hot-spot of the paper's segmentation stage (it also powers
fill-holes and the watershed flooding).

TPU adaptation (DESIGN.md §2/§8): the CPU/GPU algorithms use irregular
wavefront queues, which do not map to the MXU/VPU. Instead we tile the image
into VMEM-resident blocks and run *many local sweeps per block per kernel
launch* (raster + anti-raster, the classic two-pass SE decomposition), so the
bulk of the propagation happens at VMEM bandwidth; a cheap global dilate-min
step between launches carries wavefronts across tile boundaries, and an outer
``while_loop`` iterates to the global fixpoint. Convergence is exact — the
fixpoint test is on the full image.

Blocks default to 256×256 fp32 (256 KiB/buffer; marker+mask+out ≈ 768 KiB of
VMEM, well under the ~16 MiB/core budget), and both block dims are multiples
of the 8×128 VPU tile.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_FILL = -3.0e38  # acts as -inf for propagation fills (plain float: kernels
# must not capture traced constants)


def _shift_block(x: jax.Array, dy: int, dx: int) -> jax.Array:
    """Static-shape shift with -inf fill, using concat (TPU-lowerable)."""
    h, w = x.shape
    if dy == 1:
        x = jnp.concatenate([jnp.full((1, w), _FILL, x.dtype), x[:-1]], axis=0)
    elif dy == -1:
        x = jnp.concatenate([x[1:], jnp.full((1, w), _FILL, x.dtype)], axis=0)
    if dx == 1:
        x = jnp.concatenate([jnp.full((h, 1), _FILL, x.dtype), x[:, :-1]], axis=1)
    elif dx == -1:
        x = jnp.concatenate([x[:, 1:], jnp.full((h, 1), _FILL, x.dtype)], axis=1)
    return x


def _neighbors(conn: int) -> Tuple[Tuple[int, int], ...]:
    if conn == 4:
        return ((1, 0), (-1, 0), (0, 1), (0, -1))
    return ((1, 0), (-1, 0), (0, 1), (0, -1), (1, 1), (1, -1), (-1, 1), (-1, -1))


def _recon_sweep_kernel(marker_ref, mask_ref, out_ref, *, conn: int, inner_iters: int):
    """``inner_iters`` local dilate-min sweeps over one VMEM block."""
    m = marker_ref[...]
    mk = mask_ref[...]

    def body(_, m):
        d = m
        for dy, dx in _neighbors(conn):
            d = jnp.maximum(d, _shift_block(m, dy, dx))
        return jnp.minimum(d, mk)

    out_ref[...] = jax.lax.fori_loop(0, inner_iters, body, m)


@functools.partial(
    jax.jit, static_argnames=("conn", "block", "inner_iters", "interpret")
)
def tile_sweep(
    marker: jax.Array,
    mask: jax.Array,
    *,
    conn: int = 8,
    block: Tuple[int, int] = (256, 256),
    inner_iters: int = 8,
    interpret: bool = False,
) -> jax.Array:
    """One kernel launch: every block independently runs ``inner_iters``
    local reconstruction sweeps. Pads to block multiples with -inf marker /
    -inf mask so padding can never propagate into the image."""
    h, w = marker.shape
    bh = min(block[0], max(8, h))
    bw = min(block[1], max(128, w)) if w >= 128 else w
    hp = -(-h // bh) * bh
    wp = -(-w // bw) * bw
    mk = jnp.pad(marker.astype(jnp.float32), ((0, hp - h), (0, wp - w)), constant_values=float(_FILL))
    ms = jnp.pad(mask.astype(jnp.float32), ((0, hp - h), (0, wp - w)), constant_values=float(_FILL))
    out = pl.pallas_call(
        functools.partial(_recon_sweep_kernel, conn=conn, inner_iters=inner_iters),
        grid=(hp // bh, wp // bw),
        in_specs=[
            pl.BlockSpec((bh, bw), lambda i, j: (i, j)),
            pl.BlockSpec((bh, bw), lambda i, j: (i, j)),
        ],
        out_specs=pl.BlockSpec((bh, bw), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((hp, wp), jnp.float32),
        interpret=interpret,
    )(mk, ms)
    return out[:h, :w]


@functools.partial(
    jax.jit, static_argnames=("conn", "block", "inner_iters", "interpret")
)
def morph_reconstruct_pallas(
    marker: jax.Array,
    mask: jax.Array,
    *,
    conn: int = 8,
    block: Tuple[int, int] = (256, 256),
    inner_iters: int = 8,
    interpret: bool = False,
) -> jax.Array:
    """Full reconstruction to the global fixpoint (kernel sweeps + cross-tile
    exchange). Matches ``ref.morph_reconstruct_ref`` exactly."""
    from repro.kernels import ref as kref

    marker = jnp.minimum(marker.astype(jnp.float32), mask.astype(jnp.float32))
    mask = mask.astype(jnp.float32)

    def body(state):
        m, _ = state
        m1 = tile_sweep(
            m, mask, conn=conn, block=block, inner_iters=inner_iters, interpret=interpret
        )
        m2 = jnp.minimum(kref.dilate(m1, conn=conn), mask)  # cross-tile carry
        return m2, jnp.any(m2 != m)

    out, _ = jax.lax.while_loop(lambda s: s[1], body, (marker, jnp.bool_(True)))
    return out
