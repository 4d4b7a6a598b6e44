"""Controls: runs of a cell whose answers must come out not correct.

A control puts something that breaks what the configuration states in the
program's place, drives the rest of a run unchanged, and reports the
numbers the checks compared.  It is how each limit in a configuration's
``limits`` was shown to separate sound runs from unsound ones:

* ``pagerank_bf16``: the reference PageRank computed in bfloat16, the
  precision below the float32 the configuration states, answers the
  PageRank request;
* ``weights_bf16``: every weight a read returns is rounded to bfloat16,
  the precision below the float32 the configuration states.

    python3 bench/control.py --workload <cell> --control <name> \\
        --seeds 1 2 3 --seconds <s>

The benchmark's own runs never install a control.  Each seed prints one
JSON line with the control, the seed and the checks.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
from functools import partial

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from bench import deploy, run  # noqa: E402


@partial(jax.jit, static_argnames=("nv", "iters", "dtype"))
def pagerank_lowp(src, dst, *, nv: int, iters: int, damping: float, dtype):
    """The reference's PageRank with every sum and product in ``dtype``."""
    deg0 = jnp.zeros(nv, jnp.int32).at[src].add(1)
    inv = (1.0 / jnp.maximum(deg0, 1)).astype(dtype)
    dangling = deg0 == 0
    n = jnp.asarray(nv, dtype)
    d = jnp.asarray(damping, dtype)

    def body(_, r):
        acc = jnp.zeros(nv, dtype).at[dst].add((r * inv)[src])
        mass = jnp.where(dangling, r, jnp.zeros((), dtype)).sum(dtype=dtype)
        return ((1 - d) / n + d * (acc + mass / n)).astype(dtype)

    r = jax.lax.fori_loop(0, iters, body, jnp.full(nv, 1.0 / nv, dtype))
    return r.astype(jnp.float32)


def _bf16(x):
    return np.asarray(x, np.float32).astype(jnp.bfloat16).astype(np.float32)


def install(control: str, dep) -> None:
    """Plant ``control`` in a freshly built deployment."""
    front, svc = dep.front, dep.service
    if control == "pagerank_bf16":
        src, dst, _ = (jnp.asarray(a) for a in dep.host_edges)
        real = svc.analytics

        def analytics(name, source=None, **kw):
            if name != "pagerank":
                return real(name, source=source, **kw)
            return pagerank_lowp(src, dst, nv=dep.nv, iters=kw["max_iters"],
                                 damping=kw.get("damping", 0.85),
                                 dtype=jnp.bfloat16)
        svc.analytics = analytics
    elif control == "weights_bf16":
        real = front._finalize

        def finalize(ticket, fields, payload):
            out = real(ticket, fields, payload)
            if isinstance(out, dict) and "w" in out:
                out = dict(out, w=_bf16(out["w"]))
            return out
        front._finalize = finalize
    else:
        raise ValueError(f"unknown control {control!r}")


def run_control(workload: str, control: str, seed: int, seconds: float,
                **kw) -> dict:
    """One run of ``workload`` with ``control`` planted."""
    real_build = deploy.build

    def build(*a, **k):
        dep = real_build(*a, **k)
        install(control, dep)
        return dep
    deploy.build = build
    try:
        return run.run_cell(workload, seed, seconds, False, **kw)
    finally:
        deploy.build = real_build


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--control", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    for seed in args.seeds:
        gc.collect()            # the last seed's store, held by its control
        res = run_control(args.workload, args.control, seed, args.seconds)
        print(json.dumps({"control": args.control, "seed": seed,
                          "correct": res["correct"],
                          "checks": res["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
