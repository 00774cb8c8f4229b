"""Mesh-aware sharding (DESIGN.md §5).

One :class:`ParallelCtx` describes how a step runs on a mesh: which axes
carry data parallelism (``dp`` — 'pod' and 'data' when present) and which
axis carries model parallelism (``model``). ``ctx=None`` everywhere means
single-device execution — every helper here degrades to a no-op / fully
replicated layout in that case, and every constraint is divisibility-guarded
so an awkward shape silently falls back to replication on that dim instead
of failing to compile.

Layout rules:

* **params at rest** — FSDP: the largest divisible dim of every rank-≥2 leaf
  is sharded over 'data'; rank-<2 leaves (norms, biases) are replicated.
* **activations** — batch over ``dp``; attention heads over 'model'
  (``constrain_qkv``); the hidden dim stays unsharded so GSPMD picks the
  collective placement (``constrain_hidden``).
* **KV caches** — batch dim over ``dp``, kv-head dim over 'model'.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = [
    "ParallelCtx",
    "make_ctx",
    "param_shardings",
    "input_shardings",
    "cache_shardings",
    "constrain_qkv",
    "constrain_hidden",
]


@dataclasses.dataclass(frozen=True)
class ParallelCtx:
    """How one step is parallelised over a mesh."""

    mesh: Optional[Mesh]
    mode: str = "train"  # "train" (SP/FSDP layouts) | "serve" (TP layouts)
    dp: Tuple[str, ...] = ()
    model_axis: Optional[str] = None
    analysis: bool = False  # unroll scans so HLO analysis sees every layer


def make_ctx(mesh: Optional[Mesh], *, mode: str = "train") -> ParallelCtx:
    if mesh is None:
        return ParallelCtx(mesh=None, mode=mode)
    names = tuple(mesh.axis_names)
    dp = tuple(a for a in ("pod", "data") if a in names)
    model_axis = "model" if "model" in names else None
    return ParallelCtx(mesh=mesh, mode=mode, dp=dp, model_axis=model_axis)


def _axis_size(mesh: Mesh, axes) -> int:
    size = 1
    for a in axes if isinstance(axes, (tuple, list)) else (axes,):
        size *= mesh.shape[a]
    return size


def _dp_if_divisible(ctx: ParallelCtx, dim: int):
    if ctx.dp and dim % _axis_size(ctx.mesh, ctx.dp) == 0:
        return ctx.dp
    return None


def _model_if_divisible(ctx: ParallelCtx, dim: int):
    if ctx.model_axis and dim % _axis_size(ctx.mesh, ctx.model_axis) == 0:
        return ctx.model_axis
    return None


# ---------------------------------------------------------------------------
# At-rest layouts
# ---------------------------------------------------------------------------


def param_shardings(tree: Any, ctx: Optional[ParallelCtx]) -> Any:
    """FSDP at-rest layout: shard the largest divisible dim of each rank-≥2
    leaf over 'data'. Accepts arrays or ShapeDtypeStructs; returns a
    matching pytree of NamedShardings (or None off-mesh)."""
    if ctx is None or ctx.mesh is None:
        return None
    mesh = ctx.mesh
    data = "data" if "data" in mesh.axis_names else None

    def leaf_sharding(x) -> NamedSharding:
        shape = tuple(x.shape)
        if data is None or len(shape) < 2:
            return NamedSharding(mesh, P())
        size = mesh.shape[data]
        divisible = [d for d in range(len(shape)) if shape[d] % size == 0 and shape[d] > 0]
        if not divisible:
            return NamedSharding(mesh, P())
        d = max(divisible, key=lambda i: shape[i])
        spec = [None] * len(shape)
        spec[d] = data
        return NamedSharding(mesh, P(*spec))

    return jax.tree.map(leaf_sharding, tree)


def input_shardings(cfg, shape, ctx: Optional[ParallelCtx]) -> Dict[str, P]:
    """Batch-over-dp PartitionSpecs for every input of this step shape."""
    from repro.launch.inputs import input_specs

    specs = input_specs(cfg, shape)
    if ctx is None or ctx.mesh is None:
        return {k: P() for k in specs}
    out: Dict[str, P] = {}
    for name, sds in specs.items():
        batch = _dp_if_divisible(ctx, sds.shape[0])
        out[name] = P(*([batch] + [None] * (len(sds.shape) - 1)))
    return out


def cache_shardings(cfg, shape, ctx: Optional[ParallelCtx]) -> Callable[[Any], Any]:
    """Returns a pytree-mapper: KV-cache leaves get batch-over-dp and
    kv-heads-over-model (leading layer dim replicated)."""

    def mapper(tree: Any) -> Any:
        if ctx is None or ctx.mesh is None:
            return jax.tree.map(lambda x: None, tree)
        kv = getattr(cfg, "num_kv_heads", 0)

        def leaf_sharding(x) -> NamedSharding:
            spec = [None] * len(x.shape)
            for d, n in enumerate(x.shape):
                if d > 0 and n == shape.global_batch and spec[d] is None:
                    spec[d] = _dp_if_divisible(ctx, n)
                    break
            for d in range(len(x.shape) - 1, 0, -1):
                if x.shape[d] == kv and spec[d] is None:
                    spec[d] = _model_if_divisible(ctx, x.shape[d])
                    break
            return NamedSharding(ctx.mesh, P(*spec))

        return jax.tree.map(leaf_sharding, tree)

    return mapper


# ---------------------------------------------------------------------------
# In-flight constraints
# ---------------------------------------------------------------------------


def _constrain(x, ctx: ParallelCtx, spec: P):
    return jax.lax.with_sharding_constraint(x, NamedSharding(ctx.mesh, spec))


def constrain_qkv(q, k, v, ctx: Optional[ParallelCtx]):
    """Shard attention heads over 'model' and batch over dp: (b, s, h, hd)."""
    if ctx is None or ctx.mesh is None:
        return q, k, v

    def one(t):
        b, _, h, _ = t.shape
        return _constrain(
            t, ctx, P(_dp_if_divisible(ctx, b), None, _model_if_divisible(ctx, h), None)
        )

    return one(q), one(k), one(v)


def constrain_hidden(x, cfg, ctx: Optional[ParallelCtx]):
    """Batch-over-dp for the (b, s, d) hidden stream; the hidden dim stays
    unsharded (GSPMD chooses where the matmul collectives land)."""
    if ctx is None or ctx.mesh is None:
        return x
    return _constrain(x, ctx, P(_dp_if_divisible(ctx, x.shape[0]), None, None))
