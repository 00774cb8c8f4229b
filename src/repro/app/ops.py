"""JAX implementations of the pathology-pipeline operators (paper Fig 1).

The motivating application normalises a whole-slide H&E tile, segments cell
nuclei through a chain of threshold / morphological operators, and compares
each run's mask with the default-parameter mask (Dice). Every operator below
is a pure, jittable function on ``float32``/``bool`` arrays. On a TPU,
fill-holes runs the bit-packed Pallas kernel of ``repro.kernels.fill_holes``
(where the tile's packed planes fit its VMEM budget), and the propagation
loops run the row-blocked Pallas kernel of ``repro.kernels.label``: the
float32 reconstruction (``repro.kernels.morph_recon``), component labels
and the watershed flood settle in VMEM row blocks. Off a TPU each runs its
XLA loop.

Connectivity parameters (FH / RC / WConn in Table I) are 4 or 8 and must be
*static* under jit (they select the structuring element).
"""

from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.kernels.ref import dilate, erode, neighbors as _neighbors, shift2d as _shift

__all__ = [
    "normalize_tile",
    "background_mask",
    "rbc_mask",
    "dilate",
    "erode",
    "morph_reconstruct",
    "fill_holes",
    "label_components",
    "component_sizes",
    "area_filter",
    "distance_transform",
    "watershed_split",
]


@jax.jit
def normalize_tile(rgb: jax.Array) -> jax.Array:
    """Stain/intensity normalisation: per-channel standardisation onto the
    reference mean/std used across the study (shared by every SA run)."""
    x = rgb.astype(jnp.float32)
    mean = jnp.mean(x, axis=(0, 1), keepdims=True)
    std = jnp.std(x, axis=(0, 1), keepdims=True) + 1e-6
    target_mean = jnp.array([200.0, 160.0, 180.0])  # H&E-like reference
    target_std = jnp.array([40.0, 45.0, 40.0])
    return (x - mean) / std * target_std + target_mean


@jax.jit
def background_mask(rgb: jax.Array, b: jax.Array, g: jax.Array, r: jax.Array) -> jax.Array:
    """Background detection (B/G/R thresholds): bright-in-all-channels pixels
    are glass/background. Returns the *foreground* (tissue) mask."""
    bg = (rgb[..., 2] > b) & (rgb[..., 1] > g) & (rgb[..., 0] > r)
    return ~bg


@jax.jit
def rbc_mask(rgb: jax.Array, t1: jax.Array, t2: jax.Array) -> jax.Array:
    """Red-blood-cell detection (T1/T2 ratio thresholds): red-dominant pixels
    with R/G > T1 and R/B > T2 are RBCs, excluded from nuclei candidates."""
    r = rgb[..., 0]
    g = rgb[..., 1] + 1.0
    bl = rgb[..., 2] + 1.0
    return (r / g > t1) & (r / bl > t2)


@functools.partial(jax.jit, static_argnames=("conn",))
def morph_reconstruct(marker: jax.Array, mask: jax.Array, conn: int = 8) -> jax.Array:
    """Grayscale morphological reconstruction by dilation: iterate
    ``marker ← min(dilate(marker), mask)`` to fixpoint. The row-blocked
    Pallas kernel on a TPU, the XLA loop elsewhere (``kernels.ops``)."""
    from repro.kernels import ops as kops

    return kops.morph_reconstruct(marker, mask, conn=conn)


@functools.partial(jax.jit, static_argnames=("conn",))
def fill_holes(mask: jax.Array, conn: int = 4) -> jax.Array:
    """Binary fill-holes: background the border cannot reach becomes
    foreground (FH parameter selects the propagation neighbourhood). The
    Pallas kernel on a TPU, the XLA loop elsewhere (``kernels.ops``)."""
    from repro.kernels import ops as kops

    return kops.fill_holes(mask, conn=conn)


@functools.partial(jax.jit, static_argnames=("conn",))
def label_components(mask: jax.Array, conn: int = 8) -> jax.Array:
    """Connected-component labels by iterative min-label propagation.

    Labels are flat pixel indices (stable, deterministic); background = -1.
    The Pallas kernel on a TPU, the XLA loop elsewhere (``kernels.ops``).
    """
    from repro.kernels import ops as kops

    return kops.label_components(mask, conn=conn)


@jax.jit
def component_sizes(labels: jax.Array) -> jax.Array:
    """Per-pixel size of the component the pixel belongs to (0 for bg)."""
    h, w = labels.shape
    flat = labels.reshape(-1)
    valid = flat >= 0
    counts = jnp.zeros(h * w + 1, dtype=jnp.int32).at[
        jnp.where(valid, flat, h * w)
    ].add(1)
    counts = counts.at[h * w].set(0)
    return counts[jnp.where(valid, flat, h * w)].reshape(h, w)


@functools.partial(jax.jit, static_argnames=("conn",))
def area_filter(
    mask: jax.Array, min_size: jax.Array, max_size: jax.Array, conn: int = 8
) -> jax.Array:
    """Drop components outside [min_size, max_size] (MinSize/MaxSize params)."""
    labels = label_components(mask, conn=conn)
    sizes = component_sizes(labels)
    return mask & (sizes >= min_size) & (sizes <= max_size)


@functools.partial(jax.jit, static_argnames=("conn", "max_iters"))
def distance_transform(mask: jax.Array, conn: int = 4, max_iters: int = 64) -> jax.Array:
    """Chamfer-style distance to background by iterated erosion counting."""
    def body(i, state):
        cur, dist = state
        nxt = erode(cur, conn=conn) * mask.astype(jnp.float32)
        return nxt, dist + nxt

    cur = mask.astype(jnp.float32)
    _, dist = jax.lax.fori_loop(0, max_iters, body, (cur, cur))
    return dist


@functools.partial(jax.jit, static_argnames=("conn",))
def watershed_split(
    mask: jax.Array, min_size_pl: jax.Array, conn: int = 8
) -> jax.Array:
    """Watershed-style splitting of touching nuclei (WConn / MinSizePl).

    Seeds = regional maxima of the distance transform; seeded flood by
    iterative nearest-seed propagation (same engine as the paper's irregular
    wavefront propagation); pixels where two different seeds collide form the
    split lines, which are removed from the mask. Components smaller than
    ``min_size_pl`` are dropped *before* splitting (paper's MinSizePl)."""
    from repro.kernels import ops as kops

    pre = mask & (component_sizes(label_components(mask, conn=conn)) >= min_size_pl)
    dist = distance_transform(pre, conn=4)
    maxima = (dist >= dilate(dist, conn=conn)) & pre & (dist > 1.0)
    h, w = mask.shape
    big = jnp.int32(h * w)
    # merge plateau maxima into one seed per regional maximum
    seed_labels = label_components(maxima, conn=8)
    seeds = jnp.where(maxima, seed_labels, big)
    # competitive multi-source BFS: basins stop at collision fronts
    lab = kops.flood(seeds, pre, conn=conn)
    # split line: a pixel adjacent (4-conn) to a pixel of a different basin
    boundary = jnp.zeros_like(mask)
    for dy, dx in _neighbors(4):
        nb = _shift(lab, dy, dx, big)
        boundary = boundary | ((nb != lab) & (nb != big) & (lab != big))
    return pre & ~boundary
