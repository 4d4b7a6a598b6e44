"""The Pallas-TPU and mesh spellings the kernels and launch code share.

Written against the installed JAX (0.9): one place names the Mosaic
options class, the scalar-prefetch grid spec, VMEM scratch shapes, the
ambient-mesh context and ``shard_map``, so kernels and launch code use one
spelling.

``resolve_interpret(impl)`` maps the repo-wide ``impl=`` convention
("xla" | "pallas" | "pallas_interpret") to ``pallas_call``'s
``interpret=``: ``"pallas"`` always means the compiled Mosaic kernel (it
fails to lower where there is no TPU) and only ``"pallas_interpret"``
runs the kernel body in the interpreter.
"""
from __future__ import annotations

from typing import Any, Optional, Sequence

import jax
from jax.experimental.pallas import tpu as pltpu


def tpu_compiler_params(*, dimension_semantics: Optional[Sequence[str]] = None,
                        **kwargs: Any):
    """``compiler_params`` for ``pl.pallas_call`` (``pltpu.CompilerParams``)."""
    if dimension_semantics is not None:
        kwargs["dimension_semantics"] = tuple(dimension_semantics)
    return pltpu.CompilerParams(**kwargs)


def prefetch_grid_spec(**kwargs: Any):
    """``pltpu.PrefetchScalarGridSpec``: the scalar-prefetch pipeline used by
    every pointer-chasing kernel."""
    return pltpu.PrefetchScalarGridSpec(**kwargs)


def vmem(shape: Sequence[int], dtype) -> Any:
    """A VMEM scratch-shape spec (``pltpu.VMEM``) for ``pallas_call``."""
    return pltpu.VMEM(tuple(shape), dtype)


def resolve_interpret(impl: str) -> bool:
    """Map the repo ``impl=`` convention to ``pallas_call(interpret=...)``.

    ``"pallas"`` compiles the Mosaic kernel, on every backend: off-TPU it
    fails to lower instead of quietly switching to the interpreter.
    ``"pallas_interpret"`` always interprets.
    """
    if impl == "pallas_interpret":
        return True
    if impl == "pallas":
        return False
    raise ValueError(f"not a pallas impl: {impl!r}")


def set_mesh(mesh):
    """Enter ``mesh`` as the ambient mesh (``jax.set_mesh``), so bare
    ``PartitionSpec`` constraints and ``shard_map`` resolve against it."""
    return jax.set_mesh(mesh)


def shard_map(f, *, mesh=None, in_specs, out_specs, axis_names=None,
              check_vma: bool = True):
    """``jax.shard_map``; ``axis_names`` lists the mesh axes mapped
    manually (the unmentioned ones stay automatic), all of them when None."""
    kw = {"in_specs": in_specs, "out_specs": out_specs,
          "check_vma": check_vma}
    if mesh is not None:
        kw["mesh"] = mesh
    if axis_names is not None:
        kw["axis_names"] = set(axis_names)
    return jax.shard_map(f, **kw)
