"""Compile-only rehearsals of the sweep kernels for a described TPU v5e.

Nothing runs: each test lowers and compiles for one chip of a ``v5e:2x2``
topology that the TPU compiler describes without hardware, so a kernel the
chip's compiler (Mosaic) would refuse — a block that breaks the (8, 128)
tiling, a scalar-prefetch stream larger than SMEM, more VMEM than a core
has — fails here at no chip time.  The kernels compile at the shapes the
engine passes them at Graph500 scale 22 (4,194,304 vertices; 5,062,455
blocks of 32 lanes).  The whole jitted engine sweep compiles on a small
described store (8,192 vertices, 2,048 blocks): at scale 22 its lane
sort makes that compile take about half a minute, too long for a test.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and under several test workers only
the worker given this file loads it.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.core import cblist
from repro.core.engine import process_edge_push
from repro.kernels import gather_rows
from repro.kernels.common import cdiv
from repro.kernels.segment_matmul.kernel import segment_matmul_sorted

NV = 4_194_304            # Graph500 scale 22 vertex ids
NB = 5_062_455            # blocks GraphService.from_coo provisions for it
LANES = 32                # block width
HBM_BYTES = 16 * 10 ** 9  # one v5e chip


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    mp = pytest.MonkeyPatch()
    if "TPU_LOG_DIR" not in os.environ:     # else libtpu logs under /tmp
        mp.setenv("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        mp.undo()
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of these tests
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()
    mp.undo()


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()     # the Mosaic kernel
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < HBM_BYTES
    return compiled


def test_block_gather_vertex_vector_scale22(one_chip):
    """The push sweep's per-block source gather: x[owner] over every block."""
    x = jax.ShapeDtypeStruct((NV,), jnp.float32, sharding=one_chip)
    ids = jax.ShapeDtypeStruct((NB,), jnp.int32, sharding=one_chip)
    _compile(lambda x, i: gather_rows(x, i, impl="pallas"), x, ids)


@pytest.mark.parametrize("F,lanes", [(1, NB * LANES), (16, 1 << 22)])
def test_segment_matmul_kernel_scale22(one_chip, F, lanes):
    """The sorted-stream segment-sum kernel over every edge lane (F=1: the
    scalar sweeps) and a feature push (F=16) into 4,194,304 rows."""
    NT = cdiv(lanes, 1024) + NV // 128
    oi = jax.ShapeDtypeStruct((NT,), jnp.int32, sharding=one_chip)
    rows = jax.ShapeDtypeStruct((1, NT * 1024), jnp.int32, sharding=one_chip)
    data = jax.ShapeDtypeStruct((F, NT * 1024), jnp.float32,
                                sharding=one_chip)
    _compile(lambda o, r, d: segment_matmul_sorted(
        o, r, d, num_blocks=NV // 128), oi, rows, data)


def test_process_edge_push_pallas_sweep(one_chip):
    """One jitted engine sweep with impl="pallas" (stream sort and tile
    padding included): gather and segment-sum both lower to Mosaic
    kernels."""
    shapes = jax.eval_shape(lambda: cblist.empty(8192, 2048, LANES))
    cbl = jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                                      sharding=one_chip),
                       shapes)
    x = jax.ShapeDtypeStruct((8192,), jnp.float32, sharding=one_chip)
    compiled = _compile(lambda c, v: process_edge_push(c, v, impl="pallas"),
                        cbl, x)
    text = compiled.as_text()
    assert "block_gather" in text and "gtchain_segment_matmul" in text
