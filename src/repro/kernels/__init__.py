"""Pallas TPU kernels for the compute hot-spots, with jnp oracles in ref.py
and dispatching wrappers in ops.py.

* ``label``            — the row-block driver that settles a propagation
                         fixpoint in VMEM: component labels and the
                         watershed flood (int32), and the reconstruction.
* ``morph_recon``      — seg2's float32 reconstruction by dilation on that
                         driver (the paper's propagation hot-spot).
* ``fill_holes``       — binary fill-holes on a bit-packed VMEM tile.
* ``flash_attention``  — blocked FlashAttention-2 (causal + sliding window +
                         GQA) for the LM prefill path.
* ``ssm_scan``         — chunked diagonal-gated linear recurrence for the
                         Mamba2 / RWKV6 architectures.
"""
