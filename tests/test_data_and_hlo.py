"""Data generators + HLO cost parser calibration."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.data import rmat_edges, sasrec_batches, token_stream, update_stream
from repro.launch.hlo_cost import parse_hlo


def test_rmat_power_law_skew():
    src, dst = rmat_edges(1024, 8192, seed=0)
    assert src.shape == (8192,) and src.max() < 1024
    deg = np.bincount(src, minlength=1024)
    # RMAT should be skewed: max degree far above mean
    assert deg.max() > 8 * deg.mean()


def test_update_stream_consistency():
    src, dst = rmat_edges(256, 1024, seed=1)
    batches = list(update_stream(256, (src, dst), 64, 4, seed=2))
    assert len(batches) == 4
    for s, d, w, op in batches:
        assert s.shape == (64,) and set(np.unique(op)) <= {-1, 1}


def test_token_and_sasrec_streams():
    t, l = next(token_stream(100, 4, 16))
    assert t.shape == (4, 16) and t.max() < 100
    s, p, n = next(sasrec_batches(50, 4, 8))
    assert s.shape == (4, 8) and p.max() <= 50 and (s >= 0).all()


def test_hlo_parser_flops_exact_on_scan():
    """Calibration: parser must recover trip-count-corrected dot FLOPs."""
    def body(x, w):
        return jnp.tanh(x @ w), None

    def scanned(x, ws):
        y, _ = jax.lax.scan(body, x, ws)
        return y

    x = jax.ShapeDtypeStruct((128, 256), jnp.float32)
    ws = jax.ShapeDtypeStruct((8, 256, 256), jnp.float32)
    compiled = jax.jit(scanned).lower(x, ws).compile()
    parsed = parse_hlo(compiled.as_text())
    expected = 2 * 128 * 256 * 256 * 8
    assert abs(parsed["flops"] - expected) / expected < 1e-6
    # raw XLA count misses the trip count (the reason this parser exists)
    raw = compiled.cost_analysis()["flops"]
    assert raw < parsed["flops"] / 4


def test_hlo_parser_collectives_counted():
    import os
    # this test runs under the default 1-device runtime: use psum via vmap?
    # simplest: parse a synthetic HLO snippet
    hlo = """
HloModule test, entry_computation_layout={()->f32[]}

ENTRY %main (p: f32[1024]) -> f32[1024] {
  %p = f32[1024]{0} parameter(0)
  ROOT %all-reduce = f32[1024]{0} all-reduce(%p), replica_groups={{0,1,2,3}}, to_apply=%add
}
"""
    parsed = parse_hlo(hlo)
    assert parsed["collective_bytes_total"] == 4096.0
    assert parsed["collectives"][0]["group"] == 4
