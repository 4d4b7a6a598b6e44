"""Jit'd public wrapper: unsorted segment-sum -> sorted padded stream -> kernel.

``segment_matmul(data, seg, num_rows)`` is a drop-in for
``jax.ops.segment_sum`` with the GTChain layout contract enforced here
(sort + per-output-block tile padding from :mod:`repro.kernels.common`,
payloads turned edge-major along lanes).  ``impl="xla"`` is the oracle —
the tuner's All-Hard path.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro import compat
from repro.kernels import common
from repro.kernels.segment_matmul.kernel import segment_matmul_sorted
from repro.kernels.segment_matmul.ref import segment_sum_ref


@functools.partial(jax.jit, static_argnames=("num_rows", "rows_per_block",
                                             "tile", "impl", "assume_sorted"))
def segment_matmul(data: jax.Array, seg: jax.Array, num_rows: int, *,
                   rows_per_block: int = 128, tile: int = 1024,
                   impl: str = "xla", assume_sorted: bool = False) -> jax.Array:
    """Segment-sum of ``data`` (``[E]`` or ``[E, F]``) by ``seg``.

    impl: "xla" (oracle / All-Hard), "pallas" (the compiled Mosaic kernel),
    "pallas_interpret" (kernel body interpreted, for CPU validation).
    On the chip ``tile`` and ``rows_per_block`` must be multiples of 128.
    """
    if impl == "xla":
        return segment_sum_ref(data, seg, num_rows)
    interpret = compat.resolve_interpret(impl)
    E = seg.shape[0]
    data_t = data[None, :] if data.ndim == 1 else data.T      # [F, E]
    if assume_sorted:
        order = None
    else:
        # invalid / padding segments must sort LAST (ranks are positional)
        key = jnp.where((seg >= 0) & (seg < num_rows), seg,
                        jnp.iinfo(jnp.int32).max)
        order = jnp.argsort(key)
        seg = seg[order]
    out_idx, perm, rows_p, NT = common.pad_sorted_stream(
        seg, num_rows, rows_per_block, tile)
    hole = perm == E
    src = jnp.minimum(perm, E - 1)
    if order is not None:
        src = order[src]
    data_p = jnp.where(hole[None, :], 0.0,
                       data_t[:, src].astype(jnp.float32))
    out = segment_matmul_sorted(out_idx, rows_p[None, :], data_p,
                                num_blocks=common.cdiv(num_rows,
                                                       rows_per_block),
                                rows_per_block=rows_per_block, tile=tile,
                                interpret=interpret)[:, :num_rows]
    return out[0] if data.ndim == 1 else out.T
