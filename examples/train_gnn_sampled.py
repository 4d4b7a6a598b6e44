"""Train a GIN on a dynamic CBList graph with real neighbor sampling.

The minibatch_lg pipeline end to end: CBList stores the (updatable) graph,
the fanout sampler draws layered subgraphs from its chains, and the GIN
trains on the sampled GraphBatches — while edge updates stream in between
epochs (the dynamic-graph training loop).

  PYTHONPATH=src python examples/train_gnn_sampled.py --steps 30
"""
import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro import compile_cache
from repro.core import batch_update, build_from_coo
from repro.data import rmat_edges
from repro.graph import sample_subgraph
from repro.models.gnn import gin
from repro.models.gnn.common import GraphBatch
from repro.optim import AdamWConfig, adamw_update, init_opt_state


def main():
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--vertices", type=int, default=512)
    ap.add_argument("--edges", type=int, default=8192)
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--seeds", type=int, default=32)
    ap.add_argument("--fanout", type=int, nargs=2, default=[10, 5])
    args = ap.parse_args()

    rng = np.random.default_rng(0)
    src, dst = rmat_edges(args.vertices, args.edges, seed=0)
    cbl = build_from_coo(jnp.asarray(src), jnp.asarray(dst), None,
                         num_vertices=args.vertices,
                         num_blocks=args.edges // 4, block_width=32)
    feats = jnp.asarray(rng.standard_normal(
        (args.vertices, 32)).astype(np.float32))
    labels = jnp.asarray((np.arange(args.vertices) % 4).astype(np.int32))

    cfg = gin.GINConfig(d_in=32, d_hidden=32, n_classes=4, n_layers=2)
    params = gin.init_params(jax.random.PRNGKey(0), cfg)
    opt_cfg = AdamWConfig(lr=3e-3)
    opt = init_opt_state(params, opt_cfg)

    @jax.jit
    def train_step(params, opt, g):
        loss, grads = jax.value_and_grad(
            lambda p: gin.loss_fn(p, cfg, g))(params)
        params, opt = adamw_update(params, grads, opt, opt_cfg)
        return params, opt, loss

    key = jax.random.PRNGKey(1)
    first = last = None
    for step in range(args.steps):
        key, k1, k2 = jax.random.split(key, 3)
        seeds = jax.random.choice(k1, args.vertices, (args.seeds,),
                                  replace=False).astype(jnp.int32)
        sg = sample_subgraph(cbl, seeds, k2, fanout=tuple(args.fanout))
        nodes = jnp.concatenate([sg.src, sg.dst])
        g = GraphBatch(x=feats, edge_src=sg.src, edge_dst=sg.dst,
                       edge_valid=sg.valid,
                       node_valid=jnp.ones(args.vertices, bool),
                       graph_id=jnp.zeros(args.vertices, jnp.int32),
                       labels=labels)
        params, opt, loss = train_step(params, opt, g)
        if first is None:
            first = float(loss)
        last = float(loss)
        # dynamic graph: stream a few new edges between steps
        if step % 10 == 9:
            us = jnp.asarray(rng.integers(0, args.vertices, 16), jnp.int32)
            ud = jnp.asarray(rng.integers(0, args.vertices, 16), jnp.int32)
            cbl = batch_update(cbl, us, ud)
    print(f"GIN sampled training: loss {first:.4f} -> {last:.4f} "
          f"over {args.steps} steps (graph updated live)")
    assert last < first


if __name__ == "__main__":
    main()
