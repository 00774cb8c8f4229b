"""Jitted dispatching wrappers for the Pallas kernels.

With ``use_kernel=None`` a wrapper runs the Pallas kernel on a TPU backend
and the pure-XLA reference path elsewhere. ``use_kernel=True`` asks for the
kernel, which is an error off a TPU: the Pallas interpreter runs only where
a caller of the kernel itself asks for it by name (``interpret=True``, as
the kernel tests do). ``fill_holes``, ``label_components`` and ``flood``
take no such option: ``fill_holes`` runs its kernel on a TPU wherever the
tile's packed planes fit the kernel's VMEM budget, the other two run theirs
on a TPU at every shape (their row blocks follow from the width), and each
runs its XLA loop elsewhere.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax

from repro import device
from repro.kernels import ref as kref


def _want_kernel(use_kernel: Optional[bool], name: str) -> bool:
    on_tpu = device.on_tpu()
    if use_kernel is None:
        return on_tpu
    if use_kernel and not on_tpu:
        raise RuntimeError(
            f"the {name} Pallas kernel was asked for off a TPU "
            f"(backend {jax.default_backend()!r}); use_kernel=False runs the "
            "XLA reference"
        )
    return use_kernel


def morph_reconstruct(
    marker: jax.Array,
    mask: jax.Array,
    *,
    conn: int = 8,
    use_kernel: Optional[bool] = None,
    block: Tuple[int, int] = (256, 256),
    inner_iters: int = 8,
) -> jax.Array:
    """Morphological reconstruction by dilation (see kernels/morph_recon.py)."""
    if _want_kernel(use_kernel, "morph_reconstruct"):
        from repro.kernels.morph_recon import morph_reconstruct_pallas

        return morph_reconstruct_pallas(
            marker,
            mask,
            conn=conn,
            block=block,
            inner_iters=inner_iters,
        )
    return kref.morph_reconstruct_ref(marker, mask, conn=conn)


def fill_holes(mask: jax.Array, *, conn: int = 4) -> jax.Array:
    """Binary fill-holes (see kernels/fill_holes.py): on a TPU the bit-packed
    VMEM kernel where its two planes fit ``VMEM_BUDGET``, else the XLA loop."""
    from repro.kernels import fill_holes as kfill

    if _want_kernel(None, "fill_holes") and kfill.fits_vmem(*mask.shape):
        return kfill.fill_holes_pallas(mask, conn=conn)
    return kref.fill_holes_ref(mask, conn=conn)


def label_components(mask: jax.Array, *, conn: int = 8) -> jax.Array:
    """Connected-component labels (see kernels/label.py): the row-blocked
    VMEM kernel on a TPU, else the XLA loop."""
    if _want_kernel(None, "label_components"):
        from repro.kernels.label import label_components_pallas

        return label_components_pallas(mask, conn=conn)
    return kref.label_components_ref(mask, conn=conn)


def flood(seeds: jax.Array, pre: jax.Array, *, conn: int = 8) -> jax.Array:
    """The watershed's seeded flood through ``pre`` (see kernels/label.py):
    the row-blocked VMEM kernel on a TPU, else the XLA loop."""
    if _want_kernel(None, "flood"):
        from repro.kernels.label import flood_pallas

        return flood_pallas(seeds, pre, conn=conn)
    return kref.flood_ref(seeds, pre, conn=conn)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    use_kernel: Optional[bool] = None,
    block_q: int = 128,
    block_k: int = 128,
) -> jax.Array:
    """Blocked FlashAttention-2 (see kernels/flash_attention.py)."""
    if _want_kernel(use_kernel, "flash_attention"):
        from repro.kernels.flash_attention import flash_attention_pallas

        return flash_attention_pallas(
            q,
            k,
            v,
            causal=causal,
            window=window,
            block_q=block_q,
            block_k=block_k,
        )
    return kref.attention_ref(q, k, v, causal=causal, window=window)


def ssm_scan(
    x: jax.Array,
    a: jax.Array,
    b: jax.Array,
    c: jax.Array,
    h0: Optional[jax.Array] = None,
    *,
    use_kernel: Optional[bool] = None,
    chunk: int = 64,
    analysis: bool = False,
):
    """Chunked diagonal-gated linear recurrence (see kernels/ssm_scan.py).
    ``analysis=True`` swaps in a shape-preserving stub whose true cost the
    roofline harness adds in closed form (XLA cost analysis cannot see
    through the sequential chunk loop)."""
    if analysis:
        return kref.ssm_scan_stub(x, a, b, c, h0)
    if _want_kernel(use_kernel, "ssm_scan"):
        from repro.kernels.ssm_scan import ssm_scan_pallas

        return ssm_scan_pallas(x, a, b, c, h0, chunk=chunk)
    return kref.ssm_scan_xla(x, a, b, c, h0, chunk=chunk)
