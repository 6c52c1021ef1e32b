"""Compile the main path's programs for a described TPU v5e, with no chip.

The TPU compiler is installed with jax; it compiles for a topology that is
described rather than attached, and refuses what the chip would refuse: a
block that breaks Mosaic's tiling rule, a kernel over its VMEM, a program
over the 16 GiB of HBM. Nothing runs, so these tests say nothing about
results or times.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
every test file. Keep these tests in this one file for the same reason.
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding

from repro.core.layouts import AXIS_DATA, AXIS_MODEL, GRID
from repro.kernels import matmul as kmatmul
from repro.kernels import ops
from repro.kernels import relayout_pad
from repro.linalg import gemm
from repro.linalg import svd

HBM_BYTES = 16 * 2**30  # one v5e chip


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # A compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip: keep the cache out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # any failure means "cannot describe"
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _total_bytes(compiled) -> int:
    mem = compiled.memory_analysis()
    return (
        mem.argument_size_in_bytes
        + mem.output_size_in_bytes
        + mem.temp_size_in_bytes
        - mem.alias_size_in_bytes
    )


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_tiled_matmul_compiles_at_table1_size(one_chip, dtype):
    # Paper Table 1's first case, 10k x 10k x 10k, at the production 512^3 blocks.
    a = jax.ShapeDtypeStruct((10_000, 10_000), dtype, sharding=one_chip)
    compiled = kmatmul.matmul.lower(a, a, bm=512, bn=512, bk=512).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert _total_bytes(compiled) < HBM_BYTES


@pytest.mark.parametrize(
    "logical,physical",
    [
        # tall: the chip stores it column-major, so the kernel's row-major
        # operands cost a layout copy each way — four copies must fit
        ((78_124, 10_000), (78_125, 10_000)),
        ((4_999, 9_999), (5_000, 10_000)),  # both dims uneven
        ((6, 7), (8, 8)),  # smaller than one tile
    ],
)
def test_pad_strip_kernels_compile_at_uneven_shapes(one_chip, logical, physical):
    x = jax.ShapeDtypeStruct(logical, jnp.float32, sharding=one_chip)
    padded = relayout_pad.pad_to.lower(x, physical).compile()
    assert "tpu_custom_call" in padded.as_text()
    y = jax.ShapeDtypeStruct(physical, jnp.float32, sharding=one_chip)
    stripped = relayout_pad.strip_to.lower(y, logical).compile()
    assert "tpu_custom_call" in stripped.as_text()


@pytest.mark.parametrize("grid", [(1, 1), (2, 2)])
def test_truncated_svd_fits_at_paper_size(topo, grid):
    # Paper section 4.2's smallest case, 312,500 x 10,000, stored as f32
    # (12.5 GB), rank 20, on a worker group as the engine builds it.
    devices = topo.devices[: grid[0] * grid[1]]
    mesh = Mesh(np.asarray(devices).reshape(grid), (AXIS_DATA, AXIS_MODEL))
    a = jax.ShapeDtypeStruct(
        (312_500, 10_000), jnp.float32, sharding=NamedSharding(mesh, GRID.partition_spec(mesh))
    )
    compiled = svd.truncated_svd.lower(a, k=20, mesh=mesh).compile()
    assert _total_bytes(compiled) < HBM_BYTES


def test_summa_on_2x2_mesh_uses_the_kernel(topo, monkeypatch):
    # The backend was probed on this CPU host at import; steer the local
    # GEMMs to the kernel as a TPU host would.
    monkeypatch.setattr(ops, "_BACKEND", "pallas")
    mesh = Mesh(np.asarray(topo.devices).reshape(2, 2), (AXIS_DATA, AXIS_MODEL))
    grid = NamedSharding(mesh, GRID.partition_spec(mesh))
    a = jax.ShapeDtypeStruct((10_000, 10_000), jnp.float32, sharding=grid)
    compiled = gemm.multiply.lower(a, a, mesh, schedule="summa").compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "all-reduce" in text  # the panel broadcasts cross chips
    assert _total_bytes(compiled) < HBM_BYTES
