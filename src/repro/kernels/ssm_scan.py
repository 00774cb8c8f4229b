"""Pallas TPU kernel: chunked diagonal-gated linear recurrence (Mamba2 SSD /
RWKV6 core).

TPU adaptation: the token-recurrent form is VPU-serial; the chunked form
rewrites it as dense matmuls (MXU work) with a tiny cross-chunk carry:

  within a chunk (length C), with L_t = Σ_{i≤t} log a_i (L decreasing):
    y_intra[t] = Σ_{i≤t} (c_t · (exp(L_t − L_i) ⊙ b_i)) x_i   — masked matmul
    y_carry[t] = (c_t ⊙ exp(L_t)) · h_prev
    h_next     = exp(L_C) ⊙ h_prev + Σ_i (exp(L_C − L_i) ⊙ b_i) ⊗ x_i

  Every exponent is ≤ 0 (decays ≤ 1), so the log-space form is
  underflow-safe — no division by vanishing cumulative decays.

Grid: (B·H parallel, S/C sequential); the (N, P) fp32 state lives in VMEM
scratch across the sequential chunk dimension. Default C=64, N,P ≤ 128 keeps
every block well inside VMEM (the (C, C, N) intra tensor is the largest at
~1 MiB fp32).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssm_chunk_kernel(x_ref, a_ref, b_ref, c_ref, y_ref, hout_ref, h_scr):
    j = pl.program_id(1)
    nj = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    x = x_ref[0].astype(jnp.float32)  # (C, P)
    a = a_ref[0].astype(jnp.float32)  # (C, N)
    b = b_ref[0].astype(jnp.float32)  # (C, N)
    c = c_ref[0].astype(jnp.float32)  # (C, N)
    cdim = x.shape[0]

    la = jnp.log(jnp.maximum(a, 1e-37))
    L = jnp.cumsum(la, axis=0)  # (C, N), non-increasing
    # intra-chunk: w[t, i, n] = exp(L_t - L_i) for t >= i
    diff = L[:, None, :] - L[None, :, :]  # (C, C, N), ≤ 0 on the lower tri
    tri = (jnp.arange(cdim)[:, None] >= jnp.arange(cdim)[None, :])[..., None]
    w = jnp.where(tri, jnp.exp(diff), 0.0)
    s = jnp.einsum("tin,tn,in->ti", w, c, b)  # (C, C)
    y = s @ x  # (C, P)
    # carry-in from previous chunks
    h = h_scr[...]
    y += (c * jnp.exp(L)) @ h  # (C,N)@(N,P)
    y_ref[0] = y.astype(y_ref.dtype)
    # state update
    decay_last = jnp.exp(L[-1][None, :] - L)  # (C, N), ≤ 1
    h_new = jnp.exp(L[-1])[:, None] * h + (b * decay_last).T @ x
    h_scr[...] = h_new

    @pl.when(j == nj - 1)
    def _emit():
        hout_ref[0] = h_new.astype(hout_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssm_scan_pallas(
    x: jax.Array,                      # (B, S, H, P)
    a: jax.Array,                      # (B, S, H) or (B, S, H, N)
    b: jax.Array,                      # (B, S, H, N)
    c: jax.Array,                      # (B, S, H, N)
    h0: Optional[jax.Array] = None,    # must be None/zeros (kernel owns state)
    *,
    chunk: int = 64,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    bsz, s, h, p = x.shape
    n = b.shape[-1]
    if a.ndim == 3:
        a = jnp.broadcast_to(a[..., None], (bsz, s, h, n))
    if h0 is not None:
        # Kernel owns the state across chunks; non-zero h0 is folded in by
        # the wrapper via a virtual first chunk — unsupported here.
        raise NotImplementedError("ssm_scan_pallas requires h0=None (zeros)")
    cdim = min(chunk, s)
    spad = -(-s // cdim) * cdim
    if spad != s:
        # pad with a=1 (no decay), b=0 (no input) so padding is inert
        x = jnp.pad(x, ((0, 0), (0, spad - s), (0, 0), (0, 0)))
        a = jnp.pad(a, ((0, 0), (0, spad - s), (0, 0), (0, 0)), constant_values=1.0)
        b = jnp.pad(b, ((0, 0), (0, spad - s), (0, 0), (0, 0)))
        c = jnp.pad(c, ((0, 0), (0, spad - s), (0, 0), (0, 0)))

    # (B, S, H, ·) -> (B·H, S, ·)
    def fold(t):
        return jnp.moveaxis(t, 2, 1).reshape(bsz * h, spad, t.shape[-1])

    xf, af, bf, cf = fold(x), fold(a), fold(b), fold(c)
    nchunks = spad // cdim

    y, hout = pl.pallas_call(
        _ssm_chunk_kernel,
        grid=(bsz * h, nchunks),
        in_specs=[
            pl.BlockSpec((1, cdim, p), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, cdim, n), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, cdim, n), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, cdim, n), lambda i, j: (i, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, cdim, p), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, n, p), lambda i, j: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bsz * h, spad, p), x.dtype),
            jax.ShapeDtypeStruct((bsz * h, n, p), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((n, p), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
    )(xf, af, bf, cf)

    y = jnp.moveaxis(y.reshape(bsz, h, spad, p), 1, 2)[:, :s]
    hfinal = hout.reshape(bsz, h, n, p)
    return y, hfinal
