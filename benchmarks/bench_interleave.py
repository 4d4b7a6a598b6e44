"""Figure 14 / Table 3 analogue: execution-mode and layout effects.

The paper's SE-vs-IE+SP deltas are CPU cache-stall effects; the portable,
measurable analogues on this container are the *layout* halves of the
co-design (on TPU the interleaving half is the Pallas DMA pipeline,
analyzed statically in EXPERIMENTS.md §Roofline):

  * GTChain-ordered blocks vs shuffled blocks — same data, same op, only
    physical order differs (hardware-prefetch friendliness; paper Fig. 5);
  * sorted-by-destination segment reduction vs random-order (the GTChain
    sortedness that enables revisit-accumulation in the kernel);
  * batch updates classified by source vs unclassified single-edge loop
    (the coroutine batching win of §5.1, here as vectorization).
"""
import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import build_cbl, dataset, emit, time_fn
from repro.core import (batch_update, gtchain_contiguity, process_edge_pull,
                        process_edge_push, process_edge_push_feat)
from repro.core import blockstore as bs
from repro.core.tuner import choose_engine_impl


def shuffle_blocks(cbl, seed=0):
    """Physically permute live blocks randomly (destroys GTChain order but
    preserves the logical graph — chains follow the permutation)."""
    st = cbl.store
    nb = st.num_blocks
    rng = np.random.default_rng(seed)
    perm = jnp.asarray(rng.permutation(nb).astype(np.int32))   # new->old
    inv = jnp.argsort(perm).astype(jnp.int32)                  # old->new
    remap = lambda ids: jnp.where(ids == bs.NULL, bs.NULL,
                                  inv[jnp.maximum(ids, 0)])
    st2 = st._replace(keys=st.keys[perm], vals=st.vals[perm],
                      count=st.count[perm], owner=st.owner[perm],
                      nxt=remap(st.nxt[perm]), seq=st.seq[perm],
                      free_stack=remap(st.free_stack))
    return cbl._replace(store=st2, v_head=remap(cbl.v_head),
                        v_tail=remap(cbl.v_tail))


def run():
    nv, src, dst, w = dataset("rmat_small")
    cbl = build_cbl(nv, src, dst, w)
    x = jnp.asarray(np.random.default_rng(0).random(nv).astype(np.float32))

    # --- layout: GTChain vs shuffled ---------------------------------------
    t_ord = time_fn(lambda: process_edge_push(cbl, x))
    cbl_sh = shuffle_blocks(cbl)
    np.testing.assert_allclose(np.array(process_edge_push(cbl_sh, x)),
                               np.array(process_edge_push(cbl, x)), atol=1e-4)
    t_shuf = time_fn(lambda: process_edge_push(cbl_sh, x))
    emit("interleave/sweep_gtchain_order", t_ord,
         f"contig={float(gtchain_contiguity(cbl.store)):.2f}")
    emit("interleave/sweep_shuffled", t_shuf,
         f"contig={float(gtchain_contiguity(cbl_sh.store)):.2f},"
         f"slowdown={t_shuf / t_ord:.2f}x")

    # --- engine impl: XLA oracle vs Pallas coroutine-prefetch path ---------
    # On TPU the pallas rows time the compiled scalar-prefetch pipeline; off
    # TPU they time the Pallas interpreter (named in the row), which says
    # nothing about the chip — parity is asserted either way.
    pallas = "pallas" if jax.default_backend() == "tpu" else "pallas_interpret"
    xf = jnp.asarray(np.random.default_rng(3)
                     .random((nv, 32)).astype(np.float32))
    sweeps = {
        "push": lambda impl: process_edge_push(cbl, x, impl=impl),
        "pull": lambda impl: process_edge_pull(cbl, x, impl=impl),
        "push_feat": lambda impl: process_edge_push_feat(cbl, xf, impl=impl),
    }
    impl_ratios = {}
    for name, sweep in sweeps.items():
        np.testing.assert_allclose(np.array(sweep(pallas)),
                                   np.array(sweep("xla")), atol=1e-3)
        t_xla = time_fn(lambda: sweep("xla"))
        t_pal = time_fn(lambda: sweep(pallas), iters=3, warmup=1)
        impl_ratios[name] = t_pal / t_xla
        emit(f"interleave/{name}_xla", t_xla)
        emit(f"interleave/{name}_pallas", t_pal,
             f"ratio={t_pal / t_xla:.2f}x,impl={pallas},"
             f"backend={jax.default_backend()}")
    emit("interleave/tuner_impl", 0.0,
         f"choice={choose_engine_impl(cbl, 'scan_all')}")

    # --- sorted vs unsorted segment reduction ------------------------------
    E = len(src)
    F = 32
    data = jnp.asarray(np.random.default_rng(1)
                       .random((E, F)).astype(np.float32))
    seg_sorted = jnp.sort(dst)
    seg_rand = dst
    f_sorted = jax.jit(lambda d, s: jax.ops.segment_sum(
        d, s, num_segments=nv, indices_are_sorted=True))
    f_rand = jax.jit(lambda d, s: jax.ops.segment_sum(d, s, num_segments=nv))
    t_s = time_fn(lambda: f_sorted(data, seg_sorted))
    t_r = time_fn(lambda: f_rand(data, seg_rand))
    emit("interleave/segsum_sorted", t_s)
    emit("interleave/segsum_random", t_r, f"slowdown={t_r / t_s:.2f}x")

    # --- batched classify-by-source vs per-edge updates --------------------
    rng = np.random.default_rng(2)
    n_up = 256
    us = jnp.asarray(rng.integers(0, nv, n_up).astype(np.int32))
    ud = jnp.asarray(rng.integers(0, nv, n_up).astype(np.int32))
    uw = jnp.ones((n_up,), jnp.float32)
    t_batch = time_fn(lambda: batch_update(cbl, us, ud, uw), iters=3)

    def sequential():
        c = cbl
        for i in range(16):                      # 16 single-edge updates
            c = batch_update(c, us[i:i + 1], ud[i:i + 1], uw[i:i + 1])
        return c.v_deg
    t_seq16 = time_fn(sequential, iters=2)
    per_edge_seq = t_seq16 / 16
    per_edge_batch = t_batch / n_up
    emit("interleave/update_batched", t_batch,
         f"per_edge_us={per_edge_batch * 1e6:.1f}")
    emit("interleave/update_sequential16", t_seq16,
         f"per_edge_us={per_edge_seq * 1e6:.1f},"
         f"speedup={per_edge_seq / per_edge_batch:.1f}x")
    return {"layout_slowdown": t_shuf / t_ord,
            "segsort_slowdown": t_r / t_s,
            "batch_speedup": per_edge_seq / per_edge_batch,
            "pallas_vs_xla": impl_ratios}


if __name__ == "__main__":
    run()
