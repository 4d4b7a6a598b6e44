"""Benchmark harness: one bench per paper table/figure + roofline report.

Prints ``name,us_per_call,derived`` CSV and, per bench module, writes a
machine-readable ``BENCH_<name>.json`` (same rows + the module's summary
dict) so CI runs accumulate a perf trajectory.  Scale with
REPRO_BENCH_SCALE (default 1.0; CI uses 0.25).

Every dump carries a ``"meta"`` key stamped once at harness start (git
sha, backend, device count, bench scale, wall timestamp) so BENCH files
from different PRs/commits are comparable; the timestamp is taken here on
the host and passed down — never inside timed code.

  Fig 10 -> bench_query      Fig 11 -> bench_analysis
  Fig 12 -> bench_update     Fig 13 -> bench_batchsize
  Fig 14 / Table 3 -> bench_interleave
  tiered storage (repro.core.tiered) -> bench_tier
  serving layer (repro.stream) -> bench_stream
  graph sharding (repro.distributed.graph) -> bench_shard
  vertex-program runtime (repro.core.program) -> bench_program
  request frontend (repro.serve) -> bench_serve
  §Roofline (dry-run derived) -> roofline (requires experiments/dryrun/)
"""
import json
import os
import subprocess
import sys
import time
import traceback

BENCH_META_SCHEMA = 1


def bench_meta() -> dict:
    """Run metadata stamped into every BENCH_*.json (computed once, on the
    host, before any bench runs)."""
    import jax
    try:
        sha = subprocess.run(
            ["git", "rev-parse", "HEAD"], capture_output=True, text=True,
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            timeout=10).stdout.strip() or "unknown"
    except Exception:
        sha = "unknown"
    return {
        "schema": BENCH_META_SCHEMA,
        "git_sha": sha,
        "backend": jax.default_backend(),
        "device_count": jax.device_count(),
        "bench_scale": float(os.environ.get("REPRO_BENCH_SCALE", "1.0")),
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
    }


def _dump(short: str, rows, summary, meta: dict) -> None:
    payload = {"bench": short, "meta": meta, "rows": rows}
    if isinstance(summary, dict):
        payload["summary"] = {
            k: v for k, v in summary.items()
            if isinstance(v, (int, float, str, bool, dict, list))}
    path = f"BENCH_{short}.json"
    with open(path, "w") as f:
        json.dump(payload, f, indent=1, default=float)
    print(f"wrote {path}", file=sys.stderr)


def main() -> None:
    import repro.obs as obs
    from repro import compile_cache
    compile_cache.enable()
    from benchmarks import (bench_analysis, bench_batchsize, bench_interleave,
                            bench_program, bench_query, bench_serve,
                            bench_shard, bench_stream, bench_tier,
                            bench_update, common)
    meta = bench_meta()
    print("name,us_per_call,derived")
    ok = True
    for mod in (bench_query, bench_analysis, bench_update, bench_batchsize,
                bench_interleave, bench_tier, bench_stream, bench_shard,
                bench_program, bench_serve):
        short = mod.__name__.split(".")[-1].removeprefix("bench_")
        start = len(common.ROWS)
        try:
            summary = mod.run()
        except Exception:
            ok = False
            print(f"{mod.__name__},FAILED,", file=sys.stderr)
            traceback.print_exc()
            continue
        _dump(short, common.ROWS[start:], summary, meta)
    try:
        from pathlib import Path

        from benchmarks import roofline
        if Path("experiments/dryrun").exists():
            start = len(common.ROWS)
            roofline.run()
            _dump("roofline", common.ROWS[start:], None, meta)
        else:
            print("roofline,skipped,no experiments/dryrun (run "
                  "python -m repro.launch.dryrun --all first)")
    except Exception:
        ok = False
        traceback.print_exc()
    if obs.enabled():
        # REPRO_OBS=1 runs leave a Perfetto-loadable trace of everything
        # the benches dispatched next to the BENCH files
        print(f"wrote {obs.dump_trace('TRACE_bench.json')}", file=sys.stderr)
    if not ok:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
