"""Mixture-of-Experts FFN with capacity-based gather dispatch.

Design (DESIGN.md §5): tokens are already sharded over the mesh (batch over
the dp axes; sequence over 'model' in the training SP layout), so dispatch is
*local per shard* — a shard_map keeps the argsort/cumsum/gather on-device with
zero collectives in the training layout. In the serving layout the expert FFN
dims are tensor-parallel over 'model' and the partial sums are psum-combined.

FLOP count is exact k/E of dense-all-experts (plus the capacity_factor
overhead); dropped tokens (over capacity) fall back to the residual path,
standard top-k-with-capacity semantics.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.layers import COMPUTE_DTYPE, swiglu

__all__ = ["moe_ffn", "moe_ffn_local"]


def moe_ffn_local(
    x: jax.Array,            # (T, D) local tokens
    router_w: jax.Array,     # (D, E)
    w_gate: jax.Array,       # (E, D, F)  (F possibly TP-local)
    w_up: jax.Array,         # (E, D, F)
    w_down: jax.Array,       # (E, F, D)
    *,
    k: int,
    capacity_factor: float = 1.25,
    tp_axis: Optional[str] = None,
    dropless_threshold: int = 4096,
) -> jax.Array:
    t, d = x.shape
    e = router_w.shape[1]
    dt = COMPUTE_DTYPE

    logits = (x.astype(jnp.float32) @ router_w.astype(jnp.float32))
    gates = jax.nn.softmax(logits, axis=-1)
    gval, gidx = jax.lax.top_k(gates, k)              # (T, k)
    gval = gval / jnp.maximum(gval.sum(-1, keepdims=True), 1e-9)

    eflat = gidx.reshape(-1)                          # (T*k,)
    onehot = jax.nn.one_hot(eflat, e, dtype=jnp.int32)
    pos = jnp.take_along_axis(jnp.cumsum(onehot, 0) - 1, eflat[:, None], 1)[:, 0]
    # dropless for small token counts (decode / small prefill: every token
    # fits even if all pick the same expert); capacity-bounded at train scale
    if t * k <= dropless_threshold:
        cap = t
    else:
        cap = max(1, int(t * k / e * capacity_factor))
    keep = pos < cap
    slot = jnp.where(keep, eflat * cap + pos, e * cap)  # overflow -> sink row
    tok = jnp.arange(t * k) // k

    xe = jnp.zeros((e * cap + 1, d), dt).at[slot].set(x[tok].astype(dt))
    xe = xe[: e * cap].reshape(e, cap, d)
    h = swiglu(
        jnp.einsum("ecd,edf->ecf", xe, w_gate.astype(dt)),
        jnp.einsum("ecd,edf->ecf", xe, w_up.astype(dt)),
    )
    ye = jnp.einsum("ecf,efd->ecd", h, w_down.astype(dt))
    if tp_axis is not None:
        ye = jax.lax.psum(ye, tp_axis)                # combine TP partials
    ye = jnp.concatenate([ye.reshape(e * cap, d), jnp.zeros((1, d), dt)], 0)
    out = ye[slot] * (gval.reshape(-1)[:, None] * keep[:, None]).astype(dt)
    return out.reshape(t, k, d).sum(1)


def moe_ffn(
    x: jax.Array,            # (B, S, D) global
    params: Dict[str, jax.Array],
    *,
    k: int,
    capacity_factor: float = 1.25,
    ctx: Optional[Any] = None,   # ParallelCtx (dist/sharding.py) or None
) -> jax.Array:
    """Global MoE FFN. Without a mesh context runs the local path directly
    (smoke tests / single device). With a context, shard_maps so dispatch
    stays per-shard; the layout follows ctx.mode ('train' SP vs 'serve' TP)."""
    b, s, d = x.shape
    rw, wg, wu, wd = params["router"], params["w_gate"], params["w_up"], params["w_down"]

    if ctx is None or ctx.mesh is None:
        y = moe_ffn_local(
            x.reshape(b * s, d), rw, wg, wu, wd, k=k, capacity_factor=capacity_factor
        )
        return y.reshape(b, s, d)

    mesh = ctx.mesh
    dp = tuple(ctx.dp)
    ma = ctx.model_axis
    dp_size = 1
    for a in dp:
        dp_size *= mesh.shape[a]
    bspec = dp if b % max(dp_size, 1) == 0 else None  # batch=1 decode cells
    fsdp_ax = "data" if "data" in mesh.axis_names else None
    if ctx.mode == "train":
        xspec = P(bspec, ma, None)  # SP layout: batch over dp, seq over model
        # expert weights enter at their AT-REST FSDP sharding and are
        # all-gathered INSIDE in bf16; the gather's transpose is a bf16
        # reduce-scatter, replacing the fp32 full-gradient all-reduce that a
        # replicated in_spec would force (EXPERIMENTS.md §Perf it.3).
        wspec = (P(), P(None, fsdp_ax, ma), P(None, fsdp_ax, ma), P(None, fsdp_ax, ma))
        tp_axis = None
        gather_axes = [a for a in (fsdp_ax, ma) if a]
    else:
        xspec = P(bspec, None, None)  # serve layout: TP experts over model
        wspec = (P(), P(None, None, ma), P(None, None, ma), P(None, ma, None))
        tp_axis = ma
        gather_axes = []

    def _gather_w(w):
        # at-rest (E, D|F, F|D) sharded P(None, fsdp_ax, ma): axis1 ← fsdp,
        # axis2 ← model
        if gather_axes and fsdp_ax:
            w = jax.lax.all_gather(w, fsdp_ax, axis=1, tiled=True)
        if gather_axes:
            w = jax.lax.all_gather(w, ma, axis=2, tiled=True)
        return w

    def local(xl, rwl, wgl, wul, wdl):
        wgl, wul, wdl = _gather_w(wgl), _gather_w(wul), _gather_w(wdl)
        bl, sl, _ = xl.shape
        y = moe_ffn_local(
            xl.reshape(bl * sl, d), rwl, wgl, wul, wdl,
            k=k, capacity_factor=capacity_factor, tp_axis=tp_axis,
        )
        return y.reshape(bl, sl, d)

    return jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(xspec,) + wspec,
        out_specs=xspec,
        check_vma=False,
    )(x, rw, wg, wu, wd)
