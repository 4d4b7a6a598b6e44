"""Serve a small LM with batched requests over the paged KV cache.

The CBList-for-sequences path: prompts prefill into page chains, decode
steps attend through the paged KV cache (``--impl pallas_interpret`` runs
the scalar-prefetched paged kernel in the Pallas interpreter), finished
requests free their pages (continuous batching).

  PYTHONPATH=src python examples/serve_paged_lm.py --requests 6 --decode 12
"""
from repro import compile_cache
from repro.launch.serve import main

if __name__ == "__main__":
    compile_cache.enable()
    main()
