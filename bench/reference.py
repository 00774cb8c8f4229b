"""Plain reference of the pathology segmentation that the SA study runs.

A straightforward ``jax.numpy`` implementation of the same semantics as the
program's pipeline (arXiv:1910.14548, Fig 1 and Table I), written apart
from it: it imports nothing of the program and takes nothing it made. One
parameter set on one tile is evaluated unmerged, stage after stage, with no
reuse, no cache and no scheduler:

  normalize -> background (B, G, R) -> red cells (T1, T2)
  -> reconstruction (G1, RC) -> threshold + fill holes (G2, FH)
  -> area filter (minS, maxS) -> watershed (minSPL, WConn)
  -> area filter (minSS, maxSS) -> Dice against the default-parameter mask

``dtype`` is the precision of the float planes: float32 is what the
configuration states; bfloat16 is the control, the nearest precision below,
which the comparison in ``bench/check.py`` has to reject.
"""

from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp

N4 = ((1, 0), (-1, 0), (0, 1), (0, -1))
N8 = N4 + ((1, 1), (1, -1), (-1, 1), (-1, -1))


def _nbrs(conn: int):
    return {4: N4, 8: N8}[conn]


def _shift(x, dy: int, dx: int, fill):
    """``out[y, x] = x[y - dy, x - dx]``, and ``fill`` where that is outside."""
    h, w = x.shape
    padded = jnp.pad(
        x,
        ((max(dy, 0), max(-dy, 0)), (max(dx, 0), max(-dx, 0))),
        constant_values=fill,
    )
    y0, x0 = max(-dy, 0), max(-dx, 0)
    return padded[y0 : y0 + h, x0 : x0 + w]


def _dilate(x, conn: int):
    out = x
    for dy, dx in _nbrs(conn):
        out = jnp.maximum(out, _shift(x, dy, dx, -jnp.inf))
    return out


def _erode(x, conn: int):
    out = x
    for dy, dx in _nbrs(conn):
        out = jnp.minimum(out, _shift(x, dy, dx, jnp.inf))
    return out


def _fixpoint(step, x):
    """Iterate ``x <- step(x)`` until nothing changes."""

    def body(carry):
        cur, _ = carry
        new = step(cur)
        return new, jnp.any(new != cur)

    out, _ = jax.lax.while_loop(lambda c: c[1], body, (x, jnp.bool_(True)))
    return out


@functools.partial(jax.jit, static_argnames=("conn",))
def reconstruct(marker, mask, conn: int):
    """Grayscale reconstruction by dilation of ``marker`` under ``mask``."""
    return _fixpoint(
        lambda m: jnp.minimum(_dilate(m, conn), mask), jnp.minimum(marker, mask)
    )


@functools.partial(jax.jit, static_argnames=("conn",))
def fill_holes(cand, conn: int):
    """Background not reachable from the border becomes foreground."""
    inv = (~cand).astype(jnp.float32)
    edge = jnp.zeros(cand.shape, bool).at[0, :].set(True).at[-1, :].set(True)
    edge = edge.at[:, 0].set(True).at[:, -1].set(True)
    outside = reconstruct(jnp.where(edge, inv, 0.0), inv, conn)
    return cand | (outside < 0.5)


@functools.partial(jax.jit, static_argnames=("conn",))
def labels(mask, conn: int):
    """Each pixel of a component gets the least flat index in it; -1 off it."""
    h, w = mask.shape
    big = jnp.int32(h * w)
    start = jnp.where(mask, jnp.arange(h * w, dtype=jnp.int32).reshape(h, w), big)

    def step(lab):
        new = lab
        for dy, dx in _nbrs(conn):
            new = jnp.minimum(new, _shift(lab, dy, dx, big))
        return jnp.where(mask, new, big)

    return jnp.where(mask, _fixpoint(step, start), -1)


@jax.jit
def sizes(lab):
    """Per pixel, the pixel count of its component (0 off every component)."""
    n = lab.size
    flat = jnp.where(lab >= 0, lab, n).reshape(-1)
    counts = jnp.bincount(flat, length=n + 1).at[n].set(0)
    return counts[flat].reshape(lab.shape)


@functools.partial(jax.jit, static_argnames=("conn",))
def area(mask, lo, hi, conn: int = 8):
    s = sizes(labels(mask, conn))
    return mask & (s >= lo) & (s <= hi)


@functools.partial(jax.jit, static_argnames=("conn", "dtype"))
def watershed(mask, min_size, conn: int, dtype):
    """Drop components under ``min_size``, flood from the regional maxima of
    a 64-step 4-connected erosion distance, remove the lines where basins meet."""
    h, w = mask.shape
    big = jnp.int32(h * w)
    pre = mask & (sizes(labels(mask, conn)) >= min_size)
    inside = pre.astype(dtype)

    def erode_step(_, carry):
        cur, dist = carry
        cur = _erode(cur, 4) * inside
        return cur, dist + cur

    _, dist = jax.lax.fori_loop(0, 64, erode_step, (inside, inside))
    peaks = (dist >= _dilate(dist, conn)) & pre & (dist > 1.0)
    seeds = jnp.where(peaks, labels(peaks, 8), big)

    def flood(lab):
        nb = jnp.full(lab.shape, big)
        for dy, dx in _nbrs(conn):
            nb = jnp.minimum(nb, _shift(lab, dy, dx, big))
        return jnp.where((lab == big) & pre, nb, lab)

    lab = _fixpoint(flood, seeds)
    line = jnp.zeros(mask.shape, bool)
    for dy, dx in N4:
        nb = _shift(lab, dy, dx, big)
        line = line | ((nb != lab) & (nb != big) & (lab != big))
    return pre & ~line


@functools.partial(jax.jit, static_argnames=("dtype",))
def normalize(raw, dtype):
    """Per-channel standardisation onto the study's reference mean and std."""
    x = raw.astype(dtype)
    mean = jnp.mean(x, axis=(0, 1), keepdims=True)
    std = jnp.std(x, axis=(0, 1), keepdims=True) + jnp.asarray(1e-6, dtype)
    return (x - mean) / std * jnp.asarray([40.0, 45.0, 40.0], dtype) + jnp.asarray(
        [200.0, 160.0, 180.0], dtype
    )


@jax.jit
def gray_and_marker(rgb, b, g, r, t1, t2, g1):
    """Background, red cells, the hematoxylin proxy and its lowered marker."""
    red, green, blue = rgb[..., 0], rgb[..., 1], rgb[..., 2]
    glass = (blue > b) & (green > g) & (red > r)
    rbc = (red / (green + 1.0) > t1) & (red / (blue + 1.0) > t2)
    gray = (255.0 - blue) * (~glass & ~rbc).astype(rgb.dtype)
    return gray, jnp.maximum(gray - g1, 0.0)


def segment(raw: jax.Array, params: Dict[str, Any], dtype=jnp.float32) -> jax.Array:
    """The final nuclei mask of one parameter set on one tile."""
    p = params
    f = lambda v: jnp.asarray(v, dtype)  # noqa: E731
    rgb = normalize(raw, jnp.dtype(dtype))
    gray, marker = gray_and_marker(
        rgb, f(p["B"]), f(p["G"]), f(p["R"]), f(p["T1"]), f(p["T2"]), f(p["G1"])
    )
    residual = gray - reconstruct(marker, gray, int(p["RC"]))
    mask = fill_holes(residual > f(p["G2"]) * f(0.5), int(p["FH"]))
    mask = area(mask, jnp.int32(p["minS"]), jnp.int32(p["maxS"]))
    mask = watershed(mask, jnp.int32(p["minSPL"]), int(p["WConn"]), jnp.dtype(dtype))
    return area(mask, jnp.int32(p["minSS"]), jnp.int32(p["maxSS"]))


@jax.jit
def dice(a, b):
    """Dice coefficient of two masks; 1 where both are empty."""
    inter = jnp.sum(a & b, dtype=jnp.int32).astype(jnp.float32)
    total = (jnp.sum(a, dtype=jnp.int32) + jnp.sum(b, dtype=jnp.int32)).astype(jnp.float32)
    return jnp.where(total > 0, 2.0 * inter / jnp.maximum(total, 1.0), 1.0)
