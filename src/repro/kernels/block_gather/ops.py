"""Public wrapper for the scalar-prefetched row-group gather."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro import compat
from repro.kernels.block_gather.kernel import block_gather
from repro.kernels.block_gather.ref import block_gather_ref


@functools.partial(jax.jit, static_argnames=("rows_per_step", "impl"))
def gather_rows(table: jax.Array, ids: jax.Array, *, rows_per_step: int = 1,
                impl: str = "xla") -> jax.Array:
    """Gather row groups of ``rows_per_step`` rows from ``table`` by group
    index (the kernels also take a 1-D ``table`` of single values).

    impl: "xla" | "pallas" (the compiled Mosaic kernel) |
    "pallas_interpret" (kernel body interpreted, for CPU validation).
    """
    if impl == "xla":
        return block_gather_ref(table, ids, rows_per_step)
    rows = (ids[:, None] * rows_per_step
            + jnp.arange(rows_per_step, dtype=ids.dtype)).reshape(-1)
    return block_gather(table, rows, interpret=compat.resolve_interpret(impl))
