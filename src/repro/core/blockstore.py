"""Generic fixed-capacity blocked dynamic storage (the CBList allocator substrate).

This is the TPU adaptation of GastCoCo's chunk/B+-node allocator: a pool of
fixed-width blocks (width padded to TPU lane multiples) with

  * a free-stack allocator (O(1) vectorized pop/push of k blocks),
  * singly-linked per-owner chains (``next``) — the B+ leaf chain analogue,
  * per-block owner + sequence number so the Global Traversal Chain order is
    derivable by a single argsort instead of a pointer walk.

Everything is a pytree of fixed-shape arrays; all mutators are pure
(return a new store) and jit-compatible.  The same substrate backs the graph
edge storage (:mod:`repro.core.cblist`), the paged KV cache
(:mod:`repro.models.transformer.kvcache`) and dynamic embedding tables.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

# Padding value for empty key lanes.  Chosen as int32 max so that an
# ascending sort pushes pads to the end of a block.
PAD = jnp.iinfo(jnp.int32).max
NULL = -1  # null block / vertex id


class BlockStore(NamedTuple):
    """Pool of ``num_blocks`` blocks of ``block_width`` int32 keys + f32 values."""

    keys: jax.Array      # i32[NB, B]  sorted ascending within block, PAD-filled
    vals: jax.Array      # f32[NB, B]  payload per key lane
    count: jax.Array     # i32[NB]     live lanes per block
    owner: jax.Array     # i32[NB]     owning logical id (NULL when free)
    nxt: jax.Array       # i32[NB]     next block in the owner chain (NULL at end)
    seq: jax.Array       # i32[NB]     position within the owner chain
    free_stack: jax.Array  # i32[NB]   stack of free block ids
    free_top: jax.Array  # i32[]       number of free blocks

    @property
    def num_blocks(self) -> int:
        return self.keys.shape[0]

    @property
    def block_width(self) -> int:
        return self.keys.shape[1]


def make_store(num_blocks: int, block_width: int) -> BlockStore:
    """An empty store; all blocks on the free stack (top of stack = block 0)."""
    return BlockStore(
        keys=jnp.full((num_blocks, block_width), PAD, jnp.int32),
        vals=jnp.zeros((num_blocks, block_width), jnp.float32),
        count=jnp.zeros((num_blocks,), jnp.int32),
        owner=jnp.full((num_blocks,), NULL, jnp.int32),
        nxt=jnp.full((num_blocks,), NULL, jnp.int32),
        seq=jnp.zeros((num_blocks,), jnp.int32),
        # free_stack[top-1] is the next block handed out; initialize so blocks
        # are allocated in ascending physical order (GTChain contiguity).
        free_stack=jnp.arange(num_blocks - 1, -1, -1, dtype=jnp.int32),
        free_top=jnp.asarray(num_blocks, jnp.int32),
    )


def alloc_blocks(store: BlockStore, k_max: int, k: jax.Array):
    """Pop up to ``k`` blocks (static bound ``k_max``) from the free stack.

    Returns ``(store, ids)`` where ``ids`` is i32[k_max]; entries >= k are NULL.
    Popping more blocks than are free yields NULL ids for the excess (callers
    must check :func:`free_blocks_left` / grow offline).
    """
    slots = jnp.arange(k_max, dtype=jnp.int32)
    idx = store.free_top - 1 - slots
    ok = (slots < k) & (idx >= 0)
    ids = jnp.where(ok, store.free_stack[jnp.maximum(idx, 0)], NULL)
    new_top = store.free_top - jnp.minimum(k, store.free_top)
    return store._replace(free_top=new_top), ids


def free_blocks(store: BlockStore, ids: jax.Array) -> BlockStore:
    """Push block ids (NULL entries ignored) back onto the free stack and reset them."""
    valid = ids != NULL
    k = valid.sum(dtype=jnp.int32)
    # compact the valid ids to the front, preserving order
    order = jnp.argsort(~valid, stable=True)
    ids_c = ids[order]
    pos = store.free_top + jnp.arange(ids.shape[0], dtype=jnp.int32)
    # out-of-range positions (invalid entries pushed past the end) are dropped
    pos = jnp.where(jnp.arange(ids.shape[0]) < k, pos, store.free_stack.shape[0])
    fs = store.free_stack.at[pos].set(ids_c, mode="drop")
    # invalid entries are routed out of bounds and dropped by the scatter
    safe = jnp.where(valid, ids, store.num_blocks)
    return store._replace(
        free_stack=fs,
        free_top=store.free_top + k,
        keys=store.keys.at[safe].set(PAD, mode="drop"),
        vals=store.vals.at[safe].set(0.0, mode="drop"),
        count=store.count.at[safe].set(0, mode="drop"),
        owner=store.owner.at[safe].set(NULL, mode="drop"),
        nxt=store.nxt.at[safe].set(NULL, mode="drop"),
        seq=store.seq.at[safe].set(0, mode="drop"),
    )


def free_blocks_left(store: BlockStore) -> jax.Array:
    return store.free_top


def grow_store(store: BlockStore, new_num_blocks: int) -> BlockStore:
    """Grow the pool to ``new_num_blocks`` blocks (pure pad, no data motion).

    Existing blocks keep their physical ids, so every chain pointer, owner
    record and vertex head/tail stays valid.  The new blocks are pushed
    *under* the existing free entries: allocation keeps handing out the old
    free blocks first (in their original order), then the new ids in
    ascending physical order (GTChain-friendly).

    This is the maintenance scheduler's capacity-grow action — a host-side
    reshape executed between jitted steps (shapes change, so it cannot run
    inside jit; see DESIGN.md §8).
    """
    nb = store.num_blocks
    if new_num_blocks < nb:
        raise ValueError(f"grow_store: {new_num_blocks} < current {nb}")
    if new_num_blocks == nb:
        return store
    k = new_num_blocks - nb
    bw = store.block_width

    def pad_rows(x, fill):
        return jnp.concatenate(
            [x, jnp.full((k,) + x.shape[1:], fill, x.dtype)])

    # stack layout: [new ids descending | old stack entries]; pops come from
    # index free_top-1 downward, so old free blocks drain first.
    fresh = jnp.arange(new_num_blocks - 1, nb - 1, -1, dtype=jnp.int32)
    free_stack = jnp.concatenate([fresh, store.free_stack])
    return BlockStore(
        keys=pad_rows(store.keys, PAD),
        vals=pad_rows(store.vals, jnp.float32(0.0)),
        count=pad_rows(store.count, jnp.int32(0)),
        owner=pad_rows(store.owner, jnp.int32(NULL)),
        nxt=pad_rows(store.nxt, jnp.int32(NULL)),
        seq=pad_rows(store.seq, jnp.int32(0)),
        free_stack=free_stack,
        free_top=store.free_top + k,
    )


def gtchain_order(store: BlockStore) -> jax.Array:
    """Block ids in Global-Traversal-Chain order (owner-major, chain-seq minor).

    Free blocks sort to the end.  A single argsort replaces the paper's
    pointer walk — this is what lets whole-graph scans stream blocks.
    """
    owner = jnp.where(store.owner == NULL, PAD, store.owner)
    return jnp.lexsort((store.seq, owner)).astype(jnp.int32)


def gtchain_contiguity(store: BlockStore) -> jax.Array:
    """Fraction of GTChain-adjacent live block pairs that are physically adjacent.

    This is the tuner's ``P_h`` statistic — the probability that the
    "hardware prefetch" analogue (sequential streaming of the block array)
    covers the next block of the chain.  1.0 right after build/compact,
    and for a store with fewer than two live blocks (no pair to miss).
    """
    order = gtchain_order(store)
    live = store.owner[order] != NULL
    adj = (order[1:] - order[:-1]) == 1
    pair_live = live[1:] & live[:-1]
    n = pair_live.sum()
    return jnp.where(n > 0, (adj & pair_live).sum() / jnp.maximum(n, 1), 1.0)


def sort_blocks(store: BlockStore, block_ids: jax.Array) -> BlockStore:
    """Re-sort the key lanes of the given blocks (dupes allowed, PAD trails).

    NULL ids are routed out of bounds and dropped by the scatter — they must
    never be clamped to a real row (a stale duplicate write could otherwise
    race the sorted write and win).
    """
    gather_safe = jnp.clip(block_ids, 0, store.num_blocks - 1)
    rows_k = store.keys[gather_safe]
    rows_v = store.vals[gather_safe]
    order = jnp.argsort(rows_k, axis=1)
    rows_k = jnp.take_along_axis(rows_k, order, axis=1)
    rows_v = jnp.take_along_axis(rows_v, order, axis=1)
    scatter_idx = jnp.where(block_ids == NULL, store.num_blocks, block_ids)
    keys = store.keys.at[scatter_idx].set(rows_k, mode="drop")
    vals = store.vals.at[scatter_idx].set(rows_v, mode="drop")
    return store._replace(keys=keys, vals=vals)


def compact(store: BlockStore) -> BlockStore:
    """Physically permute blocks into GTChain order (defragmentation).

    After compact, chain-sequential block reads are sequential HBM reads, so
    the automatic (hardware-analogue) pipeline covers them; the tuner's
    contiguity statistic returns to 1.0.
    """
    order = gtchain_order(store)                      # new position -> old id
    inv = jnp.argsort(order).astype(jnp.int32)        # old id -> new position
    remap = lambda ids: jnp.where(ids == NULL, NULL, inv[jnp.maximum(ids, 0)])
    n_live = (store.owner != NULL).sum(dtype=jnp.int32)
    nb = store.num_blocks
    return BlockStore(
        keys=store.keys[order],
        vals=store.vals[order],
        count=store.count[order],
        owner=store.owner[order],
        nxt=remap(store.nxt[order]),
        seq=store.seq[order],
        free_stack=jnp.arange(nb - 1, -1, -1, dtype=jnp.int32),
        free_top=nb - n_live,
    )
