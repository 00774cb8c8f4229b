"""Least HBM bytes of the propagation operators, from shapes and dtypes.

What any implementation of the operator must read and write once: its
argument planes and its result. At an ``h x w`` tile:

- ``fill_holes``, ``area_filter``, ``watershed_split``: a bool plane in and a
  bool plane out, 2 bytes per pixel (the int32 scalars add 8 bytes a call).
- ``morph_reconstruct_ref`` (the reconstruction in ``seg2_recon``): two
  float32 planes in and one out, 12 bytes per pixel.

The keys are the jitted functions' names as the trace shows them
(``jit_<name>``). The counts are floors, so a share over 100% means the
timing is wrong.
"""

BYTES_PER_PX = {
    "fill_holes": 2,
    "area_filter": 2,
    "watershed_split": 2,
    "morph_reconstruct_ref": 12,
}
EXTRA_BYTES = {"area_filter": 8, "watershed_split": 4}
PROPAGATION = tuple(BYTES_PER_PX)


def least_bytes(px: int):
    """``{module: least bytes of one call}`` at ``px`` pixels a tile."""
    return {k: b * px + EXTRA_BYTES.get(k, 0) for k, b in BYTES_PER_PX.items()}
