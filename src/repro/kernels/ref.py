"""Pure-jnp oracles for every Pallas kernel in this package, plus the shared
morphology helpers (shift / dilate / erode) used by the application layer.

These are the correctness references: kernel tests sweep shapes/dtypes and
``assert_allclose`` against the functions here.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

__all__ = [
    "neighbors",
    "shift2d",
    "dilate",
    "erode",
    "morph_reconstruct_ref",
    "fill_holes_ref",
    "label_components_ref",
    "flood_ref",
    "attention_ref",
    "ssm_scan_ref",
]


def neighbors(conn: int) -> Tuple[Tuple[int, int], ...]:
    if conn == 4:
        return ((1, 0), (-1, 0), (0, 1), (0, -1))
    if conn == 8:
        return ((1, 0), (-1, 0), (0, 1), (0, -1), (1, 1), (1, -1), (-1, 1), (-1, -1))
    raise ValueError(f"connectivity must be 4 or 8, got {conn}")


def shift2d(x: jax.Array, dy: int, dx: int, fill) -> jax.Array:
    """Shift a 2D array by (dy, dx), filling vacated cells with ``fill``."""
    out = jnp.roll(x, (dy, dx), axis=(0, 1))
    if dy > 0:
        out = out.at[:dy, :].set(fill)
    elif dy < 0:
        out = out.at[dy:, :].set(fill)
    if dx > 0:
        out = out.at[:, :dx].set(fill)
    elif dx < 0:
        out = out.at[:, dx:].set(fill)
    return out


@functools.partial(jax.jit, static_argnames=("conn",))
def dilate(x: jax.Array, conn: int = 8) -> jax.Array:
    out = x
    for dy, dx in neighbors(conn):
        out = jnp.maximum(out, shift2d(x, dy, dx, -jnp.inf))
    return out


@functools.partial(jax.jit, static_argnames=("conn",))
def erode(x: jax.Array, conn: int = 8) -> jax.Array:
    out = x
    for dy, dx in neighbors(conn):
        out = jnp.minimum(out, shift2d(x, dy, dx, jnp.inf))
    return out


@functools.partial(jax.jit, static_argnames=("conn",))
def morph_reconstruct_ref(marker: jax.Array, mask: jax.Array, conn: int = 8) -> jax.Array:
    """Grayscale reconstruction by dilation, iterated to the global fixpoint.

    Invariants: marker ≤ mask is enforced on entry; the result r satisfies
    marker ≤ r ≤ mask and r is the largest such fixpoint of
    ``r = min(dilate(r), mask)``.
    """
    marker = jnp.minimum(marker.astype(jnp.float32), mask.astype(jnp.float32))
    mask = mask.astype(jnp.float32)

    def body(state):
        m, _ = state
        new = jnp.minimum(dilate(m, conn=conn), mask)
        return new, jnp.any(new != m)

    out, _ = jax.lax.while_loop(lambda s: s[1], body, (marker, jnp.bool_(True)))
    return out


def fill_holes_ref(mask: jax.Array, conn: int = 4) -> jax.Array:
    """Binary fill-holes via reconstruction of the complement from the
    border (oracle for kernels/fill_holes.py, and the path off a TPU)."""
    inv = (~mask).astype(jnp.float32)
    border = jnp.zeros_like(inv)
    border = border.at[0, :].set(inv[0, :])
    border = border.at[-1, :].set(inv[-1, :])
    border = border.at[:, 0].set(inv[:, 0])
    border = border.at[:, -1].set(inv[:, -1])
    outside = morph_reconstruct_ref(border, inv, conn=conn)
    return mask | (outside < 0.5)


def label_components_ref(mask: jax.Array, conn: int = 8) -> jax.Array:
    """Connected-component labels by iterative min-label propagation
    (oracle for kernels/label.py, and the path off a TPU).

    Labels are flat pixel indices (stable, deterministic); background = -1.
    The loop runs until fixpoint — bounded by the component diameter.
    """
    h, w = mask.shape
    idx = jnp.arange(h * w, dtype=jnp.int32).reshape(h, w)
    big = jnp.int32(h * w)
    labels = jnp.where(mask, idx, big)

    def body(state):
        lab, _ = state
        new = lab
        for dy, dx in neighbors(conn):
            new = jnp.minimum(new, shift2d(lab, dy, dx, big))
        new = jnp.where(mask, new, big)
        return new, jnp.any(new != lab)

    def cond(state):
        return state[1]

    labels, _ = jax.lax.while_loop(cond, body, (labels, jnp.bool_(True)))
    return jnp.where(mask, labels, -1)


def flood_ref(seeds: jax.Array, pre: jax.Array, conn: int = 8) -> jax.Array:
    """The watershed's seeded flood: ``seeds`` holds each seed pixel's label
    and ``h * w`` elsewhere (oracle for kernels/label.py, and the path off
    a TPU)."""
    h, w = seeds.shape
    big = jnp.int32(h * w)

    def body(state):
        """Competitive multi-source BFS: unlabeled pixels take the min
        neighbouring label; labelled pixels never change, so basins stop at
        collision fronts (the watershed lines)."""
        lab, _ = state
        nb = jnp.full_like(lab, big)
        for dy, dx in neighbors(conn):
            nb = jnp.minimum(nb, shift2d(lab, dy, dx, big))
        new = jnp.where((lab == big) & pre, nb, lab)
        return new, jnp.any(new != lab)

    lab, _ = jax.lax.while_loop(lambda s: s[1], body, (seeds, jnp.bool_(True)))
    return lab


# ---------------------------------------------------------------------------
# Attention oracle (for kernels/flash_attention.py)
# ---------------------------------------------------------------------------

def attention_ref(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: int | None = None,
    scale: float | None = None,
) -> jax.Array:
    """Dense reference attention. Shapes: q (B, Sq, H, D); k/v (B, Sk, Hkv, D)
    with H a multiple of Hkv (GQA by repetition). ``window`` is a sliding
    window size (attend to keys within [i-window+1, i])."""
    b, sq, h, d = q.shape
    _, sk, hkv, _ = k.shape
    rep = h // hkv
    k = jnp.repeat(k, rep, axis=2)
    v = jnp.repeat(v, rep, axis=2)
    scale = scale if scale is not None else 1.0 / jnp.sqrt(d).astype(q.dtype)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    qpos = jnp.arange(sq)[:, None] + (sk - sq)  # decode alignment
    kpos = jnp.arange(sk)[None, :]
    m = jnp.ones((sq, sk), dtype=bool)
    if causal:
        m &= kpos <= qpos
    if window is not None:
        m &= kpos > qpos - window
    logits = jnp.where(m[None, None], logits, -jnp.inf)
    p = jax.nn.softmax(logits, axis=-1)
    p = jnp.where(jnp.isnan(p), 0.0, p)  # fully-masked rows
    return jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32)).astype(q.dtype)


# ---------------------------------------------------------------------------
# Chunked linear-attention / SSM scan oracle (for kernels/ssm_scan.py)
# ---------------------------------------------------------------------------

def ssm_scan_ref(
    x: jax.Array, a: jax.Array, b: jax.Array, c: jax.Array, h0: jax.Array | None = None
) -> Tuple[jax.Array, jax.Array]:
    """Diagonal-gated linear recurrence (the common core of Mamba2 / RWKV6):

        h_t = a_t ⊙ h_{t-1} + b_t ⊗ x_t          (state: (N, P) per head)
        y_t = h_t^T · c_t

    Shapes: x (B, S, H, P) values; a (B, S, H) scalar-per-head decay (Mamba2)
    or (B, S, H, N) per-channel decay (RWKV6), in (0,1]; b/c (B, S, H, N)
    input/output projections; h (B, H, N, P). Returns (y, h_final).
    """
    bsz, s, h, p = x.shape
    n = b.shape[-1]
    if a.ndim == 3:
        a = jnp.broadcast_to(a[..., None], (bsz, s, h, n))
    if h0 is None:
        h0 = jnp.zeros((bsz, h, n, p), dtype=jnp.float32)

    def step(hprev, t):
        xt, at, bt, ct = t
        hnew = at[..., None] * hprev + bt[..., None] * xt[..., None, :]
        yt = jnp.einsum("bhnp,bhn->bhp", hnew, ct)
        return hnew, yt

    xs = (
        jnp.moveaxis(x.astype(jnp.float32), 1, 0),
        jnp.moveaxis(a.astype(jnp.float32), 1, 0),
        jnp.moveaxis(b.astype(jnp.float32), 1, 0),
        jnp.moveaxis(c.astype(jnp.float32), 1, 0),
    )
    hf, ys = jax.lax.scan(step, h0, xs)
    return jnp.moveaxis(ys, 0, 1).astype(x.dtype), hf


def ssm_scan_xla(
    x: jax.Array, a: jax.Array, b: jax.Array, c: jax.Array,
    h0: jax.Array | None = None, *, chunk: int = 64, unroll: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Chunked XLA implementation of the same recurrence — identical math to
    the Pallas kernel (matmul-heavy, log-space-stable), used as the non-TPU
    production path. Differentiable (pure jnp)."""
    bsz, s, h, p = x.shape
    n = b.shape[-1]
    per_channel = a.ndim == 4
    cdim = min(chunk, s)
    spad = -(-s // cdim) * cdim
    if spad != s:
        x = jnp.pad(x, ((0, 0), (0, spad - s), (0, 0), (0, 0)))
        pa = ((0, 0), (0, spad - s), (0, 0)) if not per_channel else (
            (0, 0), (0, spad - s), (0, 0), (0, 0))
        a = jnp.pad(a, pa, constant_values=1.0)
        b = jnp.pad(b, ((0, 0), (0, spad - s), (0, 0), (0, 0)))
        c = jnp.pad(c, ((0, 0), (0, spad - s), (0, 0), (0, 0)))
    nch = spad // cdim
    resh = lambda t: jnp.moveaxis(
        t.reshape(bsz, nch, cdim, *t.shape[2:]).astype(jnp.float32), 1, 0
    )
    xc, ac, bc, cc = resh(x), resh(a), resh(b), resh(c)
    if h0 is None:
        h0 = jnp.zeros((bsz, h, n, p), jnp.float32)
    tri = jnp.tril(jnp.ones((cdim, cdim), bool))

    def body(hst, xs):
        xb, ab, bb, cb = xs  # (B, C, H, ...)
        la = jnp.log(jnp.maximum(ab, 1e-37))
        L = jnp.cumsum(la, axis=1)  # (B,C,H[,N]) non-increasing
        if per_channel:
            diff = L[:, :, None] - L[:, None]  # (B,C,C,H,N)
            w = jnp.where(tri[None, :, :, None, None], jnp.exp(diff), 0.0)
            sti = jnp.einsum("btihn,bthn,bihn->bhti", w, cb, bb)
            Ln = L
        else:
            diff = L[:, :, None] - L[:, None]  # (B,C,C,H)
            w = jnp.where(tri[None, :, :, None], jnp.exp(diff), 0.0)
            sti = jnp.einsum("btih,bthn,bihn->bhti", w, cb, bb)
            Ln = jnp.broadcast_to(L[..., None], (*L.shape, n))
        y = jnp.einsum("bhti,bihp->bthp", sti, xb)
        y = y + jnp.einsum("bthn,bhnp->bthp", cb * jnp.exp(Ln), hst)
        dlast = jnp.exp(Ln[:, -1][:, None] - Ln)  # (B,C,H,N) ≤ 1
        hnew = jnp.exp(Ln[:, -1])[..., None] * hst + jnp.einsum(
            "bthn,bthp->bhnp", bb * dlast, xb
        )
        return hnew, y

    hf, ys = jax.lax.scan(body, h0, (xc, ac, bc, cc), unroll=unroll)
    y = jnp.moveaxis(ys, 0, 1).reshape(bsz, spad, h, p)[:, :s]
    return y.astype(x.dtype), hf


def ssm_scan_stub(
    x: jax.Array, a: jax.Array, b: jax.Array, c: jax.Array,
    h0: jax.Array | None = None,
) -> Tuple[jax.Array, jax.Array]:
    """Analysis-mode stand-in: preserves shapes and data dependencies on all
    inputs with O(S) cost. The dry-run adds the closed-form cost of the real
    chunked algorithm (launch/hlo_analysis.ssm_scan_costs) in its place."""
    amean = (a if a.ndim == 4 else a[..., None]).mean(-1, keepdims=True)
    y = x * amean * b.mean(-1, keepdims=True) * c.mean(-1, keepdims=True)
    hf = b[:, -1, :, :, None] * x[:, -1, :, None, :]
    return y.astype(x.dtype), hf.astype(jnp.float32)
