"""Scalar-prefetched block gather (paged row fetch).

The purest port of the paper's software prefetch: the row indices (block
table entries / CBList chain block ids / embedding row ids) are
data-dependent — a hardware-style sequential pipeline cannot predict them.
Feeding them through ``PrefetchScalarGridSpec`` lets the Pallas pipeline
issue the DMA for the tile holding ``ids[i+k]`` while the kernel reads the
one holding ``ids[i]`` (k = pipeline lookahead): interleaved execution
without coroutines.

Layout: Mosaic moves (8, 128)-tiled blocks, so one grid step fetches the
whole tile that holds the requested unit and selects it in VMEM:

  * a 1-D vector is viewed as ``[*, 128]``; its (8, 128) tile holds 1024
    consecutive values, and the unit is one value;
  * a 2-D ``[R, F]`` table is fetched as (8, F) tiles of 8 consecutive
    rows, and the unit is one row.

Outputs are written lane-dense in the same tiling, so neither side ever
holds an ``(N, 1)`` column (padded 128x in lanes).  The selection moves the
unit's raw bits (an integer masked sum), so every value — signed zeros and
NaN payloads included — comes back exactly.

The id stream is scalar-prefetched into SMEM (1 MiB on v5e), so ids are
gathered ``CHUNK`` at a time, one call per chunk.

Used for: CBList vertex-value gathers in the engine's sweeps, and row-group
gathers (paged / embedding rows) through :func:`repro.kernels.gather_rows`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro import compat
from repro.kernels.common import cdiv

SUB, LANE = 8, 128
TILE = SUB * LANE       # values per (8, 128) tile of a 1-D vector
# ids per pallas_call: the scalar-prefetched id slice (4 B each) sits in SMEM
CHUNK = 65536

_BITS = {4: jnp.int32, 2: jnp.int16}


def _kernel(ids_ref, tile_ref, o_ref, *, flat: bool):
    i = pl.program_id(0)
    k = ids_ref[i]
    shape = tile_ref.shape
    sub = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    if flat:
        pos = sub * LANE + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        src, dst, per_block = pos == k % TILE, pos == i % TILE, TILE
        axes = (0, 1)
    else:
        src, dst, per_block = sub == k % SUB, sub == i % SUB, SUB
        axes = (0,)
    bits_t = _BITS[tile_ref.dtype.itemsize]
    bits = jax.lax.bitcast_convert_type(tile_ref[...], bits_t)
    unit = jnp.sum(jnp.where(src, bits, 0), axis=axes, keepdims=True,
                   dtype=bits_t)
    val = jax.lax.bitcast_convert_type(unit, tile_ref.dtype)

    @pl.when(i % per_block == 0)
    def _fresh():
        o_ref[...] = jnp.zeros(shape, o_ref.dtype)

    o_ref[...] = jnp.where(dst, val, o_ref[...])


@functools.partial(jax.jit, static_argnames=("interpret",))
def block_gather(table: jax.Array, ids: jax.Array, *,
                 interpret: bool = False) -> jax.Array:
    """``out[i] = table[ids[i]]`` for a 1-D vector or a 2-D row table.

    ``ids``: i32[N] in ``[0, len(table))`` (callers clip).  Returns
    ``[N]`` for a vector and ``[N, F]`` for a table.
    """
    N = ids.shape[0]
    flat = table.ndim == 1
    unit = TILE if flat else SUB
    C = min(CHUNK, cdiv(max(N, 1), TILE) * TILE)
    n_chunks = cdiv(max(N, 1), C)
    ids = jnp.pad(ids.astype(jnp.int32), (0, n_chunks * C - N))
    R = table.shape[0]
    table = jnp.pad(table, ((0, cdiv(R, unit) * unit - R),)
                    + ((0, 0),) * (table.ndim - 1))
    if flat:
        table = table.reshape(-1, LANE)
        blk, out_shape = (SUB, LANE), (C // LANE, LANE)
    else:
        blk, out_shape = (SUB, table.shape[1]), (C, table.shape[1])
    grid_spec = compat.prefetch_grid_spec(
        num_scalar_prefetch=1,
        grid=(C,),
        in_specs=[pl.BlockSpec(blk, lambda i, ids: (ids[i] // unit, 0))],
        out_specs=pl.BlockSpec(blk, lambda i, ids: (i // unit, 0)),
    )
    call = pl.pallas_call(
        functools.partial(_kernel, flat=flat),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(out_shape, table.dtype),
        compiler_params=compat.tpu_compiler_params(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="block_gather",
    )
    out = jax.lax.map(lambda chunk_ids: call(chunk_ids, table),
                      ids.reshape(n_chunks, C))
    if flat:
        return out.reshape(-1)[:N]
    return out.reshape(n_chunks * C, -1)[:N]
