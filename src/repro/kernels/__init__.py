"""Pallas TPU kernels for the GastCoCo hot paths.

Each kernel directory ships kernel.py (pl.pallas_call + BlockSpec VMEM
tiling), ops.py (jit'd dispatching wrapper) and ref.py (pure-jnp oracle).
All kernels validate under ``impl="pallas_interpret"`` on CPU;
``impl="pallas"`` is the compiled Mosaic kernel, which needs a TPU (the
tuner's All-Soft / Hybrid strategies).
"""
from repro.kernels.segment_matmul import segment_matmul, segment_sum_ref
from repro.kernels.block_gather import gather_rows, block_gather_ref
from repro.kernels.embedding_bag import embedding_bag, embedding_bag_ref
from repro.kernels.flash_attention import attention, attention_ref
from repro.kernels.paged_attention import decode_attention, paged_attention_ref
