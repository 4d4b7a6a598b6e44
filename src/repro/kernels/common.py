"""Shared utilities for the Pallas kernels: sorted-stream padding and tiling.

The GTChain execution contract: edge streams arrive sorted by destination
row-block; each output block's edges are padded to whole tiles so the kernel
grid is static and every output block is visited at least once.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp


def cdiv(a: int, b: int) -> int:
    return -(-a // b)


def exclusive_cumsum(x: jax.Array) -> jax.Array:
    return jnp.concatenate([jnp.zeros((1,), x.dtype), jnp.cumsum(x)[:-1]])


def pad_sorted_stream(seg: jax.Array, num_rows: int, rows_per_block: int,
                      tile: int) -> Tuple[jax.Array, jax.Array, jax.Array, int]:
    """Pad a sorted segment stream so each output block owns whole tiles.

    Args:
      seg: i32[E] destination rows, **sorted ascending**; entries outside
        [0, num_rows) are padding and dropped.
      num_rows / rows_per_block: output geometry; tile: edges per grid step.

    Returns (out_idx[NT], perm[NT*tile], rows_p[NT*tile], NT) where
      * ``perm`` scatters the (sorted) edge stream into the padded stream
        (entries == E are holes),
      * ``rows_p`` is the padded row-id stream (-1 in holes),
      * ``out_idx[t]`` is the output block of tile t (scalar-prefetch stream),
      * NT is the **static** tile count: cdiv(E, tile) + num_blocks.
    """
    E = seg.shape[0]
    nblk = cdiv(num_rows, rows_per_block)
    NT = cdiv(E, tile) + nblk

    valid = (seg >= 0) & (seg < num_rows)
    blk = jnp.where(valid, seg // rows_per_block, nblk)
    c = jax.ops.segment_sum(valid.astype(jnp.int32), blk, num_segments=nblk)
    nt = jnp.maximum(-(-c // tile), 1)            # >=1 so every block is visited
    tile_off = exclusive_cumsum(nt)
    total_tiles = nt.sum()

    # per-edge rank within its block (seg sorted => ranks are positional)
    estart = exclusive_cumsum(c)
    blk_safe = jnp.minimum(blk, nblk - 1)
    rank = jnp.arange(E, dtype=jnp.int32) - estart[blk_safe] \
        + jnp.where(valid, 0, 0)
    # positions of valid edges in the padded stream
    pos = jnp.where(valid, tile_off[blk_safe] * tile + rank, NT * tile)

    # perm: padded position -> source edge index (E = hole)
    perm = jnp.full((NT * tile,), E, jnp.int32).at[pos].set(
        jnp.arange(E, dtype=jnp.int32), mode="drop")
    rows_p = jnp.full((NT * tile,), -1, jnp.int32).at[pos].set(
        jnp.where(valid, seg, -1), mode="drop")

    # tile -> output block; padding tiles (t >= total) go to the last block
    t = jnp.arange(NT, dtype=jnp.int32)
    cum_nt = jnp.cumsum(nt)
    out_idx = jnp.minimum(jnp.searchsorted(cum_nt, t, side="right"),
                          nblk - 1).astype(jnp.int32)
    return out_idx, perm, rows_p, NT
