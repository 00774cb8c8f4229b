"""Pallas TPU kernel: FlashAttention-2 (causal, sliding-window, GQA).

Blocked streaming softmax: grid = (batch, q-head, q-block parallel;
k-block sequential). The fp32 running max / sum / accumulator live in VMEM
scratch across the sequential k dimension. Block sizes default to 128×128 —
MXU-aligned and ≤ a few hundred KiB of VMEM per buffer.

Masking is positional (causal + optional window), computed from block
indices; fully-masked k-blocks are skipped via ``pl.when`` on the block
bounds, so causal/windowed FLOPs are ~halved vs dense (exactly the HLO-level
waste the pure-XLA fallback suffers — see EXPERIMENTS.md §Perf).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG = -1e30


def _fa_kernel(
    q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
    *, scale: float, causal: bool, window: Optional[int], block_q: int,
    block_k: int, sk_valid: int, q_offset: int,
):
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_lo = iq * block_q + q_offset          # absolute position of q block
    k_lo = ik * block_k

    # block-level skip: any work in [k_lo, k_hi) for queries [q_lo, q_hi)?
    q_hi = q_lo + block_q - 1
    needed = k_lo <= q_hi if causal else True
    if window is not None:
        needed = jnp.logical_and(needed, (k_lo + block_k) > (q_lo - window + 1))
    needed = jnp.logical_and(needed, k_lo < sk_valid)

    @pl.when(needed)
    def _compute():
        q = q_ref[0, :, 0, :].astype(jnp.float32) * scale  # (bq, D)
        k = k_ref[0, :, 0, :].astype(jnp.float32)          # (bk, D)
        v = v_ref[0, :, 0, :].astype(jnp.float32)
        logits = q @ k.T                                    # (bq, bk)
        qpos = q_lo + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        kpos = k_lo + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        mask = kpos < sk_valid
        if causal:
            mask = jnp.logical_and(mask, kpos <= qpos)
        if window is not None:
            mask = jnp.logical_and(mask, kpos > qpos - window)
        logits = jnp.where(mask, logits, _NEG)
        m_prev, l_prev, acc = m_scr[...], l_scr[...], acc_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(logits, axis=-1, keepdims=True))
        p = jnp.exp(logits - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
        m_scr[...] = m_new
        l_scr[...] = l_new
        acc_scr[...] = acc * corr + p @ v

    @pl.when(ik == nk - 1)
    def _emit():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, :, 0, :] = (acc_scr[...] / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "block_q", "block_k", "interpret", "q_offset"),
)
def flash_attention_pallas(
    q: jax.Array,   # (B, Sq, H, D)
    k: jax.Array,   # (B, Sk, KV, D)
    v: jax.Array,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    q_offset: int = 0,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jax.Array:
    b, sq, h, d = q.shape
    _, sk, kv, _ = k.shape
    rep = h // kv
    bq = min(block_q, sq)
    bk = min(block_k, sk)
    sqp = -(-sq // bq) * bq
    skp = -(-sk // bk) * bk
    if sqp != sq:
        q = jnp.pad(q, ((0, 0), (0, sqp - sq), (0, 0), (0, 0)))
    if skp != sk:
        k = jnp.pad(k, ((0, 0), (0, skp - sk), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, skp - sk), (0, 0), (0, 0)))

    kernel = functools.partial(
        _fa_kernel,
        scale=1.0 / (d**0.5),
        causal=causal,
        window=window,
        block_q=bq,
        block_k=bk,
        sk_valid=sk,
        q_offset=q_offset,
    )
    out = pl.pallas_call(
        kernel,
        grid=(b, h, sqp // bq, skp // bk),
        in_specs=[
            pl.BlockSpec((1, bq, 1, d), lambda ib, ih, iq, ik: (ib, iq, ih, 0)),
            pl.BlockSpec(
                (1, bk, 1, d), lambda ib, ih, iq, ik, rep=rep: (ib, ik, ih // rep, 0)
            ),
            pl.BlockSpec(
                (1, bk, 1, d), lambda ib, ih, iq, ik, rep=rep: (ib, ik, ih // rep, 0)
            ),
        ],
        out_specs=pl.BlockSpec((1, bq, 1, d), lambda ib, ih, iq, ik: (ib, iq, ih, 0)),
        out_shape=jax.ShapeDtypeStruct((b, sqp, h, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(q, k, v)
    return out[:, :sq]
