"""GastCoCo core: CBList storage + prefetch co-design (paper contribution)."""
from repro.core.blockstore import (BlockStore, NULL, PAD, alloc_blocks, compact,
                                   free_blocks, free_blocks_left,
                                   grow_store, gtchain_contiguity,
                                   gtchain_order, make_store, sort_blocks)
from repro.core.cblist import (CBList, block_fences, build_from_coo,
                               compact_cbl, degrees, empty, grow, rebuild,
                               to_coo)
from repro.core.updates import (DELETE, INSERT, NOP, UpdateStats, add_vertices,
                                batch_update, batch_update_stats,
                                delete_vertices, read_edges, upsert_edges)
from repro.core.engine import (SEMIRINGS, Semiring, in_degrees, out_degrees,
                               process_edge_pull, process_edge_push,
                               process_edge_push_feat, process_vertex)
from repro.core.program import (ProgramContext, Sweep, VertexProgram,
                                get_program, has_program, register_program,
                                registered_programs, run_program)
from repro.core.traversal import (Partition, PlacementPlan, gtchain_partition,
                                  lane_mask, make_placement_plan,
                                  partition_balance, scan_edges, scan_vertices,
                                  scan_vertices_cond, vertex_table_partition,
                                  read_vertex)
from repro.core.tuner import (ExecPlan, SystemProbe, choose_engine_impl,
                              choose_plan, system_probe)
from repro.core.csr import (CSRGraph, csr_build, csr_build_counted,
                            csr_degrees, csr_empty, csr_in_degrees,
                            csr_pagerank_sweep, csr_pull, csr_push,
                            csr_push_feat, csr_query, csr_sample_neighbors,
                            csr_to_coo)
from repro.core.tiered import (TieredGraph, cold_mask, seal, tier_from_cbl,
                               tiered_grow, unseal)
