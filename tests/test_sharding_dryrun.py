"""Multi-device machinery tests.

The production dry-run needs 512 host devices, which must be forced before
jax initializes — so these tests run the real ``launch/dryrun.py`` in a
subprocess for one representative cheap cell per family, on both meshes.
(The full 40-cell x 2-mesh sweep is the §Dry-run deliverable, run via
``python -m repro.launch.dryrun --all --mesh both``.)
"""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent


@pytest.mark.parametrize("arch,shape", [("gin-tu", "molecule"),
                                        ("sasrec", "serve_p99")])
def test_dryrun_cell_compiles_both_meshes(arch, shape, tmp_path):
    # the children run on host devices: a child that reached for the chip
    # would contend with this process, which may hold it
    env = dict(os.environ, PYTHONPATH=str(REPO / "src"), JAX_PLATFORMS="cpu")
    res = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch", arch,
         "--shape", shape, "--mesh", "both", "--out", str(tmp_path)],
        env=env, capture_output=True, text=True, timeout=560, cwd=REPO)
    assert res.returncode == 0, res.stdout[-2000:] + res.stderr[-2000:]
    for mesh in ("pod", "multipod"):
        rec = json.loads((tmp_path / f"{arch}__{shape}__{mesh}.json")
                         .read_text())
        assert rec["hlo_corrected"]["flops"] > 0
        assert rec["memory_analysis"]["argument_size_in_bytes"] > 0


def test_elastic_reshard_subprocess(tmp_path):
    """Save under a (4, 2) mesh, restore under (2, 2) — elastic shrink."""
    script = f"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from repro.checkpoint import save, restore
state = {{"w": jnp.arange(64, dtype=jnp.float32).reshape(8, 8)}}
mesh1 = Mesh(np.asarray(jax.devices()[:8]).reshape(4, 2), ("data", "model"))
sharded = jax.device_put(state, NamedSharding(mesh1, P("data", "model")))
save(r"{tmp_path}", 1, sharded)
mesh2 = Mesh(np.asarray(jax.devices()[:4]).reshape(2, 2), ("data", "model"))
sh2 = {{"w": NamedSharding(mesh2, P("data", "model"))}}
back = restore(r"{tmp_path}", state, shardings=sh2)
assert back["w"].sharding.mesh.shape == {{"data": 2, "model": 2}}
np.testing.assert_array_equal(np.asarray(back["w"]),
                              np.arange(64, dtype=np.float32).reshape(8, 8))
print("ELASTIC_OK")
"""
    # the children run on host devices: a child that reached for the chip
    # would contend with this process, which may hold it
    env = dict(os.environ, PYTHONPATH=str(REPO / "src"), JAX_PLATFORMS="cpu")
    res = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=240)
    assert res.returncode == 0, res.stdout + res.stderr
    assert "ELASTIC_OK" in res.stdout


def test_moe_ep_shard_map_matches_baseline(tmp_path):
    """§Perf iter 3: shard_map EP dispatch == capacity-bucket MoE (dropless)."""
    script = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from repro.models.transformer.layers import LMConfig, init_moe, apply_moe
mesh = Mesh(np.asarray(jax.devices()[:8]).reshape(4, 2), ("data", "model"))
cfg = LMConfig(name="ep", n_layers=1, d_model=32, n_heads=2, n_kv_heads=2,
               d_ff=16, vocab=64, moe=True, n_experts=8, top_k=2,
               capacity_factor=16.0, dtype=jnp.float32)
p = init_moe(jax.random.PRNGKey(0), cfg)
x = jax.random.normal(jax.random.PRNGKey(1), (8, 16, 32), jnp.float32)
y_base, _ = apply_moe(p, cfg, x)
cfg_ep = dataclasses.replace(cfg, act_shard_axes=("data",), ep_shard_map=True,
                             data_axis_size=4, model_axis_size=2)
from repro.compat import set_mesh
with set_mesh(mesh):
    y_ep, _ = jax.jit(lambda pp, xx: apply_moe(pp, cfg_ep, xx),
                      in_shardings=(NamedSharding(mesh, P()),
                                    NamedSharding(mesh, P("data", None, None))),
                      )(p, x)
err = float(jnp.abs(y_base - y_ep).max())
assert err < 1e-4, err
print("EP_OK", err)
"""
    # the children run on host devices: a child that reached for the chip
    # would contend with this process, which may hold it
    env = dict(os.environ, PYTHONPATH=str(REPO / "src"), JAX_PLATFORMS="cpu")
    res = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=420)
    assert res.returncode == 0, res.stdout + res.stderr
    assert "EP_OK" in res.stdout
