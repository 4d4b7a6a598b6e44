"""GTChain segment-sum Pallas kernel (the paper's interleaved-execution port).

Computes ``y[:, r] = sum_{e: seg[e]==r} data[:, e]`` over an edge stream
sorted by destination row, blocked exactly like CBList: the grid walks edge
*tiles* (``tile`` edges each) while a **scalar-prefetched** stream
``out_idx`` names the output row-block each tile accumulates into.

Prefetch co-design, stated in TPU terms:

  * the *data* tiles stream sequentially (BlockSpec ``i -> (0, i)``) — the
    hardware-prefetch analogue; Pallas double-buffers the next tile's DMA
    automatically while the MXU reduces the current one;
  * the *output* block indices are data-dependent (pointer-chasing in the
    paper) — they are delivered through ``PrefetchScalarGridSpec`` so the
    pipeline knows future destinations ahead of time and can schedule the
    output-block DMAs early: this is the software-prefetch-via-coroutines
    mechanism (§5.1) without coroutines;
  * consecutive tiles hitting the same output block revisit it in VMEM —
    the accumulation never round-trips HBM (the GTChain sortedness is what
    makes the revisit pattern dense).

Layout: every stream is edge-major along the *lane* axis — row ids as
``i32[1, E]``, payloads as ``f32[F, E]``, the output as ``f32[F, rows]`` —
because Mosaic tiles the last two dims by (8, 128): a 1-D int32 stream or an
``(E, 1)`` column would be refused or padded 128x in lanes.  The segment
reduction is then one ``[F, tile] x [rows_per_block, tile]^T`` MXU product
against a one-hot built by broadcasting the row-id row over sublanes.

The scalar-prefetched ``out_idx`` lives in SMEM (1 MiB on v5e), so the tile
stream runs as a loop of ``CHUNK_TILES``-tile calls that accumulate into one
aliased output; a block whose tiles straddle two calls resumes from the
value the previous call wrote.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro import compat
from repro.kernels.common import cdiv

# tiles per pallas_call: its out_idx slice (4 B per tile) must sit in SMEM
# beside the kernel's own scalars
CHUNK_TILES = 32768


def _kernel(out_idx_ref, resume_ref, rows_ref, data_ref, acc_ref, o_ref, *,
            rows_per_block: int, tile: int):
    i = pl.program_id(0)
    blk = out_idx_ref[i]
    local = rows_ref[...] - blk * rows_per_block                   # [1, tile]
    onehot_t = (local == jax.lax.broadcasted_iota(
        jnp.int32, (rows_per_block, tile), 0)).astype(jnp.float32)
    # HIGHEST: the one-hot is exact in any precision, the payload must keep
    # all f32 mantissa bits (the default would round it to bf16)
    contrib = jax.lax.dot_general(
        data_ref[...], onehot_t, (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)                        # [F, rpb]
    first = (i == 0) | (blk != out_idx_ref[jnp.maximum(i - 1, 0)])
    resume = (i == 0) & (resume_ref[0] == 1)

    @pl.when(first & jnp.logical_not(resume))
    def _init():
        o_ref[...] = contrib

    @pl.when(resume)
    def _resume():
        o_ref[...] = acc_ref[...] + contrib

    @pl.when(jnp.logical_not(first))
    def _acc():
        o_ref[...] += contrib


@functools.partial(jax.jit, static_argnames=("num_blocks", "rows_per_block",
                                             "tile", "interpret"))
def segment_matmul_sorted(out_idx: jax.Array, rows_p: jax.Array,
                          data_p: jax.Array, *, num_blocks: int,
                          rows_per_block: int = 128, tile: int = 1024,
                          interpret: bool = False) -> jax.Array:
    """Run the kernel over a pre-padded sorted stream.

    Args:
      out_idx: i32[NT] output block per tile (scalar-prefetch stream,
        non-decreasing; every block owns at least one tile).
      rows_p:  i32[1, NT*tile] destination row per edge (-1 = hole).
      data_p:  f32[F, NT*tile] edge payloads (0 in holes).
    Returns f32[F, num_blocks*rows_per_block].
    """
    NT = out_idx.shape[0]
    F = data_p.shape[0]
    C = min(CHUNK_TILES, NT)
    n_chunks = cdiv(NT, C)
    pad = n_chunks * C - NT
    if pad:   # trailing hole tiles resume the last block and add nothing
        out_idx = jnp.concatenate(
            [out_idx, jnp.full((pad,), out_idx[-1], jnp.int32)])
        rows_p = jnp.pad(rows_p, ((0, 0), (0, pad * tile)),
                         constant_values=-1)
        data_p = jnp.pad(data_p, ((0, 0), (0, pad * tile)))
    n_rows = num_blocks * rows_per_block
    grid_spec = compat.prefetch_grid_spec(
        num_scalar_prefetch=2,
        grid=(C,),
        in_specs=[
            pl.BlockSpec((1, tile), lambda i, oi, rs: (0, i)),
            pl.BlockSpec((F, tile), lambda i, oi, rs: (0, i)),
            pl.BlockSpec((F, rows_per_block), lambda i, oi, rs: (0, oi[i])),
        ],
        out_specs=pl.BlockSpec((F, rows_per_block),
                               lambda i, oi, rs: (0, oi[i])),
    )
    call = pl.pallas_call(
        functools.partial(_kernel, rows_per_block=rows_per_block, tile=tile),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((F, n_rows), jnp.float32),
        input_output_aliases={4: 0},
        compiler_params=compat.tpu_compiler_params(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="gtchain_segment_matmul",
    )

    def chunk(c, acc):
        start = c * C
        oi = jax.lax.dynamic_slice(out_idx, (start,), (C,))
        prev = out_idx[jnp.maximum(start - 1, 0)]
        resume = ((c > 0) & (oi[0] == prev)).astype(jnp.int32)[None]
        rows = jax.lax.dynamic_slice(rows_p, (0, start * tile), (1, C * tile))
        data = jax.lax.dynamic_slice(data_p, (0, start * tile), (F, C * tile))
        return call(oi, resume, rows, data, acc)

    return jax.lax.fori_loop(0, n_chunks, chunk,
                             jnp.zeros((F, n_rows), jnp.float32))
