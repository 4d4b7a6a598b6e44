"""Compat-layer coverage: the Pallas params spelling, interpret resolution
(no fallback), mesh context, shard_map."""
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import compat


# --------------------------------------------------------------------------
# tpu_compiler_params
# --------------------------------------------------------------------------

class _NewStyleParams:
    """Modern spelling: pltpu.CompilerParams(dimension_semantics=...)."""

    def __init__(self, dimension_semantics=None, vmem_limit_bytes=None):
        self.dimension_semantics = dimension_semantics
        self.vmem_limit_bytes = vmem_limit_bytes


def test_compiler_params_new_spelling(monkeypatch):
    monkeypatch.setattr(compat, "pltpu",
                        types.SimpleNamespace(CompilerParams=_NewStyleParams))
    p = compat.tpu_compiler_params(dimension_semantics=("arbitrary",),
                                   vmem_limit_bytes=1 << 20)
    assert isinstance(p, _NewStyleParams)
    assert p.dimension_semantics == ("arbitrary",)
    assert p.vmem_limit_bytes == 1 << 20


def test_installed_jax_accepts_compat_params():
    """The params object must feed pallas_call (interpreted on this host)."""
    import jax.experimental.pallas as pl

    def k(x_ref, o_ref):
        o_ref[...] = x_ref[...] * 2.0

    x = jnp.arange(8.0, dtype=jnp.float32).reshape(1, 8)
    out = pl.pallas_call(
        k, out_shape=jax.ShapeDtypeStruct((1, 8), jnp.float32),
        compiler_params=compat.tpu_compiler_params(
            dimension_semantics=("arbitrary",)),
        interpret=compat.resolve_interpret("pallas_interpret"),
        grid=(1,),
    )(x)
    np.testing.assert_allclose(np.asarray(out), np.arange(8.0).reshape(1, 8) * 2)


# --------------------------------------------------------------------------
# interpret resolution
# --------------------------------------------------------------------------

def test_resolve_interpret():
    """"pallas" is the compiled kernel on every backend — no quiet switch to
    the interpreter off-TPU; only "pallas_interpret" interprets."""
    assert compat.resolve_interpret("pallas_interpret") is True
    assert compat.resolve_interpret("pallas") is False
    with pytest.raises(ValueError):
        compat.resolve_interpret("xla")


# --------------------------------------------------------------------------
# mesh context + shard_map
# --------------------------------------------------------------------------

def test_set_mesh_enters_ambient_context():
    mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:1]), ("data",))
    assert jax.sharding.get_abstract_mesh().empty
    with compat.set_mesh(mesh):
        assert jax.sharding.get_abstract_mesh() == mesh.abstract_mesh
    assert jax.sharding.get_abstract_mesh().empty


def test_shard_map_new_signature_on_any_jax():
    """axis_names lists the manual axes; the mesh comes from the context."""
    from jax.sharding import PartitionSpec as P
    mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:1]), ("data",))
    x = jnp.arange(8.0, dtype=jnp.float32)
    with compat.set_mesh(mesh):
        y = compat.shard_map(lambda v: v * 2.0, in_specs=P("data"),
                             out_specs=P("data"),
                             axis_names={"data"})(x)
    np.testing.assert_allclose(np.asarray(y), np.arange(8.0) * 2)
