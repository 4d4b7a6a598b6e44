"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

Proves the distribution config is coherent without hardware: every cell's
``jax.jit(step).lower(**ShapeDtypeStructs).compile()`` must succeed on the
single-pod 16x16 mesh and the 2x16x16 multi-pod mesh.  Per cell we record

  * ``compiled.memory_analysis()``  — per-device bytes (fits-in-HBM proof)
  * ``compiled.cost_analysis()``    — raw XLA numbers (while-body counted 1x)
  * trip-count-corrected FLOPs + collective bytes from the compiled HLO
    (launch/hlo_cost.py)

into ``experiments/dryrun/<arch>__<shape>__<mesh>.json`` (incremental: cells
with an existing JSON are skipped unless --force).

Usage:
  python -m repro.launch.dryrun --all                 # every live cell, 1 pod
  python -m repro.launch.dryrun --all --mesh both
  python -m repro.launch.dryrun --arch gin-tu --shape molecule --mesh multi
"""
import argparse
import gzip
import os
import json
import time
import traceback
from pathlib import Path

import jax

from repro.configs import registry
from repro.launch import hlo_cost
from repro.launch.mesh import make_production_mesh

OUT_DIR = Path("experiments/dryrun")


def run_cell(arch: str, shape: str, multi_pod: bool, out_dir: Path,
             force: bool = False, save_hlo: bool = False,
             opt: bool = False) -> dict:
    mesh_name = "multipod" if multi_pod else "pod"
    tag = f"{arch}__{shape}__{mesh_name}".replace("/", "_")
    if opt:
        tag += "__opt"
    out_path = out_dir / f"{tag}.json"
    if out_path.exists() and not force:
        return json.loads(out_path.read_text())

    # imported here: the graph package makes arrays at import, which starts
    # the backend, and main() must set the host device count before that
    from repro.distributed.sharding import (out_shardings_for_cell,
                                            shardings_for_cell)
    t0 = time.time()
    cb = registry.build_cell(arch, shape, opt=(mesh_name if opt else ""))
    mesh = make_production_mesh(multi_pod=multi_pod)
    in_sh = shardings_for_cell(mesh, cb)

    out_sh = out_shardings_for_cell(mesh, cb, in_sh)
    # shard_map and bare-PartitionSpec constraints resolve against this mesh
    with jax.set_mesh(mesh):
        lowered = jax.jit(cb.step_fn, in_shardings=in_sh,
                          out_shardings=out_sh).lower(*cb.arg_specs)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()

    hlo_text = compiled.as_text()
    parsed = hlo_cost.parse_hlo(hlo_text)

    record = {
        "arch": arch, "shape": shape, "mesh": mesh_name,
        "opt": bool(opt),
        "kind": cb.kind, "family": cb.family,
        "n_devices": mesh.size,
        "timing": {"lower_s": round(t_lower, 1),
                   "compile_s": round(t_compile, 1)},
        "memory_analysis": {
            k: int(getattr(mem, k))
            for k in ("argument_size_in_bytes", "output_size_in_bytes",
                      "temp_size_in_bytes", "generated_code_size_in_bytes",
                      "alias_size_in_bytes")
            if hasattr(mem, k)
        },
        "cost_analysis_raw": {k: float(v) for k, v in cost.items()
                              if isinstance(v, (int, float))
                              and k in ("flops", "bytes accessed",
                                        "transcendentals", "optimal_seconds")},
        "hlo_corrected": {
            "flops": parsed["flops"],
            "collective_bytes_total": parsed["collective_bytes_total"],
            "collective_bytes_by_type": parsed["collective_bytes_by_type"],
            "n_collectives": len(parsed["collectives"]),
            "memory_bytes": parsed["memory_bytes"],
            "param_bytes": parsed["param_bytes"],
        },
        "collectives": parsed["collectives"][:400],
    }
    out_dir.mkdir(parents=True, exist_ok=True)
    out_path.write_text(json.dumps(record, indent=1))
    if save_hlo:
        with gzip.open(out_dir / f"{tag}.hlo.gz", "wt") as f:
            f.write(hlo_text)
    print(f"[dryrun] {tag}: OK  compile={t_compile:.0f}s "
          f"flops={parsed['flops']:.3e} "
          f"coll={parsed['collective_bytes_total']:.3e}B", flush=True)
    return record


def main():
    # 512 host devices for the production meshes; set before the backend
    # starts, which the first device query below does
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--mesh", choices=["pod", "multi", "both"], default="pod")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--save-hlo", action="store_true")
    ap.add_argument("--opt", action="store_true",
                    help="SPMD-optimized variant (§Perf hillclimb)")
    ap.add_argument("--out", default=str(OUT_DIR))
    args = ap.parse_args()
    out_dir = Path(args.out)

    meshes = {"pod": [False], "multi": [True], "both": [False, True]}[args.mesh]
    if args.all:
        cells = [(c.arch, c.shape) for c in registry.list_cells()
                 if c.skip_reason is None]
    else:
        cells = [(args.arch, args.shape)]

    failures = []
    for arch, shape in cells:
        for mp in meshes:
            try:
                run_cell(arch, shape, mp, out_dir, force=args.force,
                         save_hlo=args.save_hlo, opt=args.opt)
            except Exception as e:  # noqa: BLE001 — record and continue
                failures.append((arch, shape, mp, repr(e)))
                print(f"[dryrun] {arch}__{shape}__"
                      f"{'multipod' if mp else 'pod'}: FAIL {e!r}", flush=True)
                traceback.print_exc()
    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for f in failures:
            print(" ", f)
        raise SystemExit(1)
    print("\nall requested dry-run cells compiled OK")


if __name__ == "__main__":
    main()
