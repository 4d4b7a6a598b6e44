"""Engine co-design path: the Pallas kernels must match the XLA segment-op
oracle on real CBList graphs for all three ProcessEdge sweeps — the paper's
interleaved-execution mode as an exercised code path, not commented intent.

``impl="pallas_interpret"`` checks the kernel bodies on any backend.
``impl="pallas"`` is the compiled Mosaic kernel: on a TPU it is checked the
same way, elsewhere it must refuse to lower (it never falls back to the
interpreter in silence).  ``tests/test_chip_compile.py`` compiles it for a
described v5e."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (build_from_coo, batch_update, process_edge_pull,
                        process_edge_push, process_edge_push_feat)
from repro.core.engine import in_degrees
from repro.core.tuner import (MIN_PALLAS_LANES, SYSTEM_PROBES,
                              choose_engine_impl, choose_plan, system_probe)
from repro.data import rmat_edges

rng = np.random.default_rng(0)


def _build(nv=200, ne=1500, num_blocks=2048, block_width=8, weights=True,
           seed=0):
    src, dst = rmat_edges(nv, ne, seed=seed)
    w = (jnp.asarray(rng.random(len(src)).astype(np.float32))
         if weights else None)
    return build_from_coo(jnp.asarray(src), jnp.asarray(dst), w,
                          num_vertices=nv, num_blocks=num_blocks,
                          block_width=block_width)


@pytest.fixture(scope="module")
def cbl():
    """The tests/test_system.py graph shape: RMAT 200v/1500e on 2048x8."""
    return _build()


@pytest.fixture(scope="module")
def cbl_fragmented(cbl):
    """Same graph after update batches (chains no longer contiguous)."""
    c = cbl
    for i in range(3):
        us = jnp.asarray(rng.integers(0, 200, 64).astype(np.int32))
        ud = jnp.asarray(rng.integers(0, 200, 64).astype(np.int32))
        c = batch_update(c, us, ud, jnp.ones((64,), jnp.float32))
    return c


def _x(nv=200):
    return jnp.asarray(rng.random(nv).astype(np.float32))


def _sweep(fn, pallas_impl):
    """``fn(impl)`` under the requested Pallas impl; off-TPU the compiled
    kernel must refuse to lower (None is returned after checking that)."""
    if pallas_impl == "pallas" and jax.default_backend() != "tpu":
        with pytest.raises(ValueError, match="interpret mode"):
            fn(pallas_impl)
        return None
    return fn(pallas_impl)


def _sum_rtol(c):
    """Relative tolerance of a reordered f32 sum.  Each output adds at most
    D (the largest in-degree) positive terms, and recursive summation in any
    order errs by at most D * eps relative to the sum; the kernel and the
    oracle only differ in that order.  A bf16 accumulation (eps 2**-8) is
    thousands of times outside this bound."""
    deg = np.asarray(in_degrees(c))
    return max(int(deg.max()), int(np.asarray(c.v_deg).max()), 1) \
        * float(np.finfo(np.float32).eps)


@pytest.mark.parametrize("pallas_impl", ["pallas", "pallas_interpret"])
def test_push_parity(cbl, pallas_impl):
    x = _x(cbl.capacity_vertices)
    ref = process_edge_push(cbl, x, impl="xla")
    out = _sweep(lambda impl: process_edge_push(cbl, x, impl=impl),
                 pallas_impl)
    if out is not None:
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=_sum_rtol(cbl), atol=0)


@pytest.mark.parametrize("pallas_impl", ["pallas", "pallas_interpret"])
def test_pull_parity(cbl, pallas_impl):
    x = _x(cbl.capacity_vertices)
    ref = process_edge_pull(cbl, x, impl="xla")
    out = _sweep(lambda impl: process_edge_pull(cbl, x, impl=impl),
                 pallas_impl)
    if out is not None:
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=_sum_rtol(cbl), atol=0)


@pytest.mark.parametrize("pallas_impl", ["pallas", "pallas_interpret"])
def test_push_feat_parity(cbl, pallas_impl):
    xf = jnp.asarray(rng.random((cbl.capacity_vertices, 16)).astype(np.float32))
    ref = process_edge_push_feat(cbl, xf, impl="xla")
    out = _sweep(lambda impl: process_edge_push_feat(cbl, xf, impl=impl),
                 pallas_impl)
    if out is not None:
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-6, atol=1e-6)


def test_push_parity_bit_for_bit_unit_weights():
    """With unit weights every vertex sum is a small integer — exact in f32
    regardless of accumulation order, so the kernel must match bit-for-bit."""
    c = _build(weights=False)
    x = jnp.ones((c.capacity_vertices,), jnp.float32)
    ref = process_edge_push(c, x, impl="xla")
    out = process_edge_push(c, x, impl="pallas_interpret")
    assert jnp.array_equal(ref, out)


def test_parity_survives_updates_and_masks(cbl_fragmented):
    c = cbl_fragmented
    x = _x(c.capacity_vertices)
    active = jnp.asarray(rng.random(c.capacity_vertices) < 0.5)
    for f_ref, f_pal in [
        (process_edge_push(c, x, active, impl="xla"),
         process_edge_push(c, x, active, impl="pallas_interpret")),
        (process_edge_pull(c, x, active, impl="xla"),
         process_edge_pull(c, x, active, impl="pallas_interpret")),
    ]:
        np.testing.assert_allclose(np.asarray(f_pal), np.asarray(f_ref),
                                   rtol=0, atol=1e-6)


def test_min_max_combines_fall_back_to_oracle(cbl):
    """The MXU accumulation kernel is additive: a min/max sweep under a
    Pallas impl is refused (no quiet swap to the oracle), the oracle answers
    it, and the tuner keeps min/max programs on the oracle."""
    from repro.graph.algorithms import CONNECTED_COMPONENTS, PAGERANK
    x = _x(cbl.capacity_vertices)
    for combine in ("min", "max"):
        for sweep in (process_edge_push, process_edge_pull):
            with pytest.raises(ValueError, match="only sums"):
                sweep(cbl, x, combine=combine, impl="pallas_interpret")
    frag = _build()
    for i in range(3):
        frag = batch_update(frag, jnp.arange(64, dtype=jnp.int32) % 200,
                            jnp.full((64,), 7 + i, jnp.int32))
    fast = dataclasses.replace(system_probe(), pallas_sweep_faster=True)
    assert choose_plan(frag, PAGERANK, fast, on_tpu=True).impl == "pallas"
    assert choose_plan(frag, CONNECTED_COMPONENTS, fast,
                       on_tpu=True).impl == "xla"


def test_tuner_picks_oracle_off_tpu(cbl):
    assert choose_engine_impl(cbl, backend="cpu") == "xla"


def test_tuner_picks_pallas_on_tpu_for_fragmented_sweeps(cbl_fragmented):
    """Fragmented GTChain + dense sweep + TPU backend -> the prefetch path,
    on a device whose probe says the compiled kernel was measured faster.
    The v5e probe does not (the kernel measured slower there), so v5e keeps
    the sweep on the oracle."""
    lanes = (cbl_fragmented.store.num_blocks
             * cbl_fragmented.store.block_width)
    assert lanes >= MIN_PALLAS_LANES
    fast = dataclasses.replace(system_probe(), pallas_sweep_faster=True)
    plan = choose_plan(cbl_fragmented, "scan_all", fast, on_tpu=True)
    assert plan.strategy != "all_hard"
    assert plan.impl == "pallas"
    v5e = SYSTEM_PROBES["TPU v5 lite"]
    assert choose_plan(cbl_fragmented, "scan_all", v5e,
                       on_tpu=True).impl == "xla"
    # and a freshly built (fully contiguous) graph stays on the oracle
    fresh = _build()
    assert choose_plan(fresh, "scan_all", fast,
                       on_tpu=True).strategy == "all_hard"
    assert choose_plan(fresh, "scan_all", fast, on_tpu=True).impl == "xla"


def test_tuner_small_graph_stays_on_oracle():
    """Below the lane floor the kernel launch cost can't amortize."""
    small = _build(nv=16, ne=64, num_blocks=32, block_width=8)
    assert choose_plan(small, "scan_all", on_tpu=True).impl == "xla"
