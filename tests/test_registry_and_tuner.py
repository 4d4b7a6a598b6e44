"""Cell registry: 40 cells, skips documented, spec/param-count sanity.
Adaptation-layer tuner: strategy selection matches the paper's decision
rules."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.core import build_from_coo, choose_plan, system_probe
from repro.core import batch_update


def test_forty_cells_three_skips():
    cells = registry.list_cells()
    assert len(cells) == 40
    skips = [c for c in cells if c.skip_reason]
    assert len(skips) == 3
    assert {(c.arch, c.shape) for c in skips} == {
        ("qwen3-moe-30b-a3b", "long_500k"),
        ("kimi-k2-1t-a32b", "long_500k"),
        ("qwen1.5-4b", "long_500k")}


@pytest.mark.parametrize("arch,lo,hi", [
    ("qwen3-moe-30b-a3b", 29e9, 32e9),
    ("kimi-k2-1t-a32b", 0.95e12, 1.15e12),
    ("gemma2-27b", 26e9, 31e9),
    ("qwen1.5-4b", 3.5e9, 5.5e9),
    ("gemma3-27b", 26e9, 32e9),
])
def test_lm_param_counts_match_names(arch, lo, hi):
    cb = registry.build_cell(arch, "train_4k")
    n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(cb.arg_specs[0]))
    assert lo < n < hi, f"{arch}: {n / 1e9:.2f}B"


def test_every_live_cell_builds_specs():
    for c in registry.list_cells():
        if c.skip_reason:
            continue
        cb = registry.build_cell(c.arch, c.shape)
        assert callable(cb.step_fn)
        assert len(jax.tree.leaves(cb.arg_specs)) > 0


def test_tuner_prefers_hard_on_contiguous():
    src = jnp.arange(64, dtype=jnp.int32) % 16
    dst = (jnp.arange(64, dtype=jnp.int32) * 7) % 16
    cbl = build_from_coo(jnp.sort(src), dst, None, num_vertices=16,
                         num_blocks=64, block_width=8)
    plan = choose_plan(cbl, "scan_all")
    # freshly-built CBList has contiguity 1.0 -> hardware analogue suffices
    assert plan.strategy == "all_hard"
    assert plan.partition == "gtchain"


def test_tuner_switches_after_fragmentation():
    src = jnp.arange(64, dtype=jnp.int32) % 16
    dst = (jnp.arange(64, dtype=jnp.int32) * 7) % 16
    cbl = build_from_coo(jnp.sort(src), dst, None, num_vertices=16,
                         num_blocks=64, block_width=4)
    # fragment via updates
    for i in range(4):
        cbl = batch_update(cbl, jnp.arange(8, dtype=jnp.int32) * 2,
                           jnp.full((8,), 100 + i, jnp.int32) % 16 + i)
    plan = choose_plan(cbl, "scan_all",
                       dataclasses.replace(system_probe(),
                                           block_fetch_overhead_us=5.0))
    assert plan.strategy != "all_hard"
    # frontier tasks always use the vertex partition (paper §5.2)
    plan_f = choose_plan(cbl, "frontier")
    assert plan_f.partition == "vertex"
    assert choose_plan(cbl, "batch_update").strategy in (
        "hybrid_hot", "all_hard")


def test_tuner_lookahead_scales_with_block_bytes():
    from repro.core.tuner import choose_lookahead
    probe = system_probe()
    small = choose_lookahead(probe, 1024)
    large = choose_lookahead(probe, 1 << 20)
    assert small >= large
