"""Compile the pipeline's device programs for one TPU v5e chip at the
paper's 4096x4096 tile size, without a chip attached.

The TPU compiler is installed with JAX: it compiles for a described
``v5e:2x2`` topology and refuses what the chip would refuse (kernels that
cannot be lowered, programs that do not fit). Nothing runs, so these tests
say nothing about results or times. The topology is described inside a
module fixture, never at import time: only one process at a time may load
the TPU library, and every test worker imports this file.
"""

import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.app import ops
from repro.kernels import label as klabel
from repro.kernels.fill_holes import fill_holes_pallas
from repro.kernels.label import flood_pallas, label_components_pallas
from repro.kernels.morph_recon import morph_reconstruct_pallas
from repro.kernels.ref import morph_reconstruct_ref

SIZE = 4096
HBM_BYTES = 16 << 30  # one v5e chip
FEW_MIB = 8 << 20  # HBM a VMEM-resident kernel may need besides its planes


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else the compiler logs under /tmp
    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means no description
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # A compile for a described device is written to the persistent cache
    # but cannot be read back without the chip; keep the cache out of it.
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def _plane(dtype, sharding):
    return jax.ShapeDtypeStruct((SIZE, SIZE), dtype, sharding=sharding)


def _scalar(dtype, sharding):
    return jax.ShapeDtypeStruct((), dtype, sharding=sharding)


# name -> (jitted fn, static kwargs, argument builder, holds a Pallas kernel)
CASES = {
    "morph_reconstruct_pallas": (
        morph_reconstruct_pallas, {"conn": 8, "interpret": False},
        lambda s: (_plane(jnp.float32, s), _plane(jnp.float32, s)), True,
    ),
    "morph_reconstruct_pallas_conn4": (
        morph_reconstruct_pallas, {"conn": 4, "interpret": False},
        lambda s: (_plane(jnp.float32, s), _plane(jnp.float32, s)), True,
    ),
    "morph_reconstruct_ref": (
        morph_reconstruct_ref, {"conn": 8},
        lambda s: (_plane(jnp.float32, s), _plane(jnp.float32, s)), False,
    ),
    "fill_holes": (
        ops.fill_holes, {"conn": 4},
        lambda s: (_plane(jnp.bool_, s),), False,
    ),
    "fill_holes_pallas_conn4": (
        fill_holes_pallas, {"conn": 4, "interpret": False},
        lambda s: (_plane(jnp.bool_, s),), True,
    ),
    "fill_holes_pallas_conn8": (
        fill_holes_pallas, {"conn": 8, "interpret": False},
        lambda s: (_plane(jnp.bool_, s),), True,
    ),
    "label_components_pallas_conn4": (
        label_components_pallas, {"conn": 4, "interpret": False},
        lambda s: (_plane(jnp.bool_, s),), True,
    ),
    "label_components_pallas_conn8": (
        label_components_pallas, {"conn": 8, "interpret": False},
        lambda s: (_plane(jnp.bool_, s),), True,
    ),
    "flood_pallas_conn4": (
        flood_pallas, {"conn": 4, "interpret": False},
        lambda s: (_plane(jnp.int32, s), _plane(jnp.bool_, s)), True,
    ),
    "flood_pallas_conn8": (
        flood_pallas, {"conn": 8, "interpret": False},
        lambda s: (_plane(jnp.int32, s), _plane(jnp.bool_, s)), True,
    ),
    "area_filter": (
        ops.area_filter, {"conn": 8},
        lambda s: (_plane(jnp.bool_, s), _scalar(jnp.int32, s), _scalar(jnp.int32, s)),
        False,
    ),
    "watershed_split": (
        ops.watershed_split, {"conn": 8},
        lambda s: (_plane(jnp.bool_, s), _scalar(jnp.int32, s)), False,
    ),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_compiles_for_one_v5e_chip_at_4k(name, one_chip):
    fn, static, args, has_kernel = CASES[name]
    compiled = fn.lower(*args(one_chip), **static).compile()
    if has_kernel:
        assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    total = (
        mem.argument_size_in_bytes
        + mem.output_size_in_bytes
        + mem.temp_size_in_bytes
        + mem.generated_code_size_in_bytes
    )
    assert total < HBM_BYTES, f"{name} needs {total} bytes on one chip"


@pytest.mark.parametrize("conn", [4, 8])
def test_fill_holes_kernel_needs_few_mib_of_hbm_besides_its_planes(conn, one_chip):
    """The packed planes live in VMEM: HBM holds the bool mask in, the bool
    result out and little else (the XLA loop holds float32 planes)."""
    mem = fill_holes_pallas.lower(_plane(jnp.bool_, one_chip), conn=conn).compile().memory_analysis()
    plane = SIZE * SIZE  # one bool plane
    assert mem.argument_size_in_bytes == plane
    extra = mem.output_size_in_bytes - plane + mem.temp_size_in_bytes
    assert extra <= FEW_MIB, f"fill_holes kernel needs {extra} bytes of HBM besides its planes"


@pytest.mark.parametrize("conn", [4, 8])
def test_label_kernels_need_no_hbm_plane_beyond_the_relaxed_ones(conn, one_chip):
    """The blocks settle in VMEM: besides its arguments and result, HBM
    holds the padded planes being relaxed (one for labels, distance and
    label for the flood) and little else (the XLA label loop holds about
    thirteen int32 planes)."""
    rows, blocks, wp = klabel.layout(SIZE, SIZE, 2)
    padded = (blocks * rows + 2 * klabel.HALO) * wp * 4  # one padded int32 plane
    bools, ints = _plane(jnp.bool_, one_chip), _plane(jnp.int32, one_chip)
    mem = label_components_pallas.lower(bools, conn=conn).compile().memory_analysis()
    assert mem.argument_size_in_bytes == SIZE * SIZE
    assert mem.output_size_in_bytes == 4 * SIZE * SIZE
    assert mem.temp_size_in_bytes <= padded, mem.temp_size_in_bytes
    mem = flood_pallas.lower(ints, bools, conn=conn).compile().memory_analysis()
    assert mem.argument_size_in_bytes == 5 * SIZE * SIZE
    assert mem.output_size_in_bytes == 4 * SIZE * SIZE
    assert mem.temp_size_in_bytes <= 2 * padded, mem.temp_size_in_bytes


@pytest.mark.parametrize("conn", [4, 8])
def test_recon_kernel_needs_no_hbm_plane_beyond_the_relaxed_ones(conn, one_chip):
    """The reconstruction settles in VMEM: besides the marker and mask in
    and the result out, HBM holds the two padded float32 planes (the
    reconstruction and the mask) and little else."""
    rows, blocks, wp = klabel.layout(SIZE, SIZE, 2)
    padded = (blocks * rows + 2 * klabel.HALO) * wp * 4  # one padded float32 plane
    f32 = _plane(jnp.float32, one_chip)
    mem = morph_reconstruct_pallas.lower(f32, f32, conn=conn).compile().memory_analysis()
    assert mem.argument_size_in_bytes == 8 * SIZE * SIZE
    assert mem.output_size_in_bytes == 4 * SIZE * SIZE
    assert mem.temp_size_in_bytes <= 2 * padded, mem.temp_size_in_bytes
