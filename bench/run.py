"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``BENCHMARK.json``; its configuration, traffic mix,
parameters and metric readers are files found by name (:mod:`bench.spec`).
A run builds the deployment from the seed, warms up every shape its traffic
will use, offers the traffic for ``--seconds``, then checks every answer of
the window against the plain reference.  With ``--trace 0`` it reports the
cell's end-to-end metrics; with ``--trace 1`` it profiles the window and
reports the per-layer metrics, the device's busy time and a breakdown.

The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, ``breakdown`` when
traced, and ``checks`` last); the last lines of standard error are the
same checks, one per line.  Without a TPU, or with fewer chips than the
cell asks for, the run exits non-zero before any work and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from contextlib import nullcontext  # noqa: E402
from types import SimpleNamespace  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import checks, spec, tracing, traffic  # noqa: E402


# loaded edges that no write touched, read back after an ingest window
READBACK_SAMPLE = 65536


class NoChip(RuntimeError):
    pass


def require_chip(chips: int):
    """The first ``chips`` TPU devices; :class:`NoChip` otherwise."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"needs a TPU, found {devs[0].platform!r} devices")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} TPU devices, found {len(devs)}")
    return devs[:chips]


def enable_compile_cache(root: str) -> str:
    """JAX's persistent compilation cache at a fixed path in the checkout,
    whatever the environment names, so that two checkouts share nothing;
    every program is kept, however fast it compiled, so that only a cell's
    first run in a checkout compiles."""
    import jax
    path = os.path.join(root, ".bench_cache", "jax")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileClock:
    """Backend compiles and persistent-cache hits, from JAX's monitoring
    events: a window should see none of either."""

    def __init__(self):
        import jax
        from jax._src import dispatch
        self.secs, self.hits, self.misses = 0.0, 0, 0
        event = dispatch.BACKEND_COMPILE_EVENT

        def on_duration(name, secs, **_):
            if name == event:
                self.secs += secs

        def on_event(name, **_):
            if name == "/jax/compilation_cache/cache_hits":
                self.hits += 1
            elif name == "/jax/compilation_cache/cache_misses":
                self.misses += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def mark(self):
        return (self.secs, self.hits, self.misses)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def warm_up(dep, gen, loop) -> list:
    """Compile every shape the window uses; returns the writes it made."""
    from bench.deploy import warm_analytics
    seen = set()
    for e in gen.mix.get("prologue", []) + gen.mix.get("repeat", []):
        if e["kind"] == "analytics" and e["program"] not in seen:
            seen.add(e["program"])
            warm_analytics(dep, e)
    writes = []
    for pl in loop.warm_requests(gen):
        loop.settle(dep.front, pl, dep.front.submit(traffic.to_request(pl)))
        if pl.kind == "update":
            d = pl.data
            writes.append((d["src"], d["dst"], d["w"], d["op"]))
    return writes


def obs_window_spans():
    """Host seconds of each ``repro.obs`` span recorded in the window."""
    import repro.obs as obs
    out = {}
    for ev in obs.tracer().events:
        if ev.get("ph") == "X":
            out.setdefault(ev["name"], []).append(float(ev["dur"]))
    return out


def run_cell(name: str, seed: int, seconds: float, trace: bool,
             root: str = None, bench_dir: str = None) -> dict:
    """One run of cell ``name`` of ``root/BENCHMARK.json`` (the checkout
    by default); returns the result line as a dict."""
    root, bench_dir = root or spec.ROOT, bench_dir or spec.BENCH_DIR
    cell = spec.load_cell(name, root, bench_dir)
    devs = require_chip(cell.chips)
    import jax
    cache = enable_compile_cache(root)
    clock = CompileClock()
    from bench import deploy
    from bench.peaks import peak
    kind = devs[0].device_kind
    peaks = peak(kind) if devs[0].platform == "tpu" else {}
    log(f"cell {name} seed {seed} seconds {seconds} trace {int(trace)} "
        f"device {devs[0].platform}/{kind} x{len(devs)} cache {cache}")

    dep = deploy.build(cell.config, cell.params, seed)
    log(f"graph: scale {cell.config['scale']}: {dep.nv} vertex ids, "
        f"{dep.edges_generated} tuples generated, {len(dep.host_edges[0])} "
        f"arcs stored, each edge both ways, after dropping self-loops and "
        f"repeats")
    gen = traffic.Generator(cell.traffic, cell.params, dep.pools, seed,
                            bench_dir)
    loop = spec.law(cell.traffic["loop"], bench_dir)
    if trace:               # instrumented paths compile in set-up too
        import repro.obs as obs
        obs.enable()
        obs.tracer().jax_annotations = True
    warm_writes = warm_up(dep, gen, loop)
    epoch0 = dep.service.epoch
    flushes0 = dep.service.stats.flushes
    log(f"set-up done: epoch {epoch0}, {len(warm_writes)} warm-up writes, "
        f"compile {clock.secs:.1f}s hits {clock.hits} misses {clock.misses}")

    tdir = None
    if trace:
        obs.reset()
        tdir = tempfile.mkdtemp(prefix="bench_trace_")
    # what set-up allocated stays out of the collector's way, so that a
    # full collection over the benchmark's own objects cannot stall the
    # window
    gc.collect()
    gc.freeze()
    mark = clock.mark()
    annotate = jax.profiler.TraceAnnotation
    ctxmgr = tracing.capture(tdir) if trace else nullcontext()
    jax.config.update("jax_log_compiles", True)     # names any compile
    with ctxmgr:
        with annotate(tracing.WINDOW):
            t0 = time.perf_counter()
            served = loop.run(dep.front, loop.requests(gen), seconds,
                              annotate=annotate)
            t1 = max([t0] + [r.done_t for r in served if r.done_t])
    jax.config.update("jax_log_compiles", False)
    gc.unfreeze()
    setup_s = (served[0].sent_t if served else t0) - T_START
    in_window = (clock.secs - mark[0], clock.hits - mark[1],
                 clock.misses - mark[2])
    spans = obs_window_spans() if trace else {}
    if trace:
        obs.disable()
    memory_peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                      for d in devs)
    flushes = dep.service.stats.flushes - flushes0
    log(f"window: {t1 - t0:.3f}s, {len(served)} requests, {flushes} flushes, "
        f"compiles in window {in_window[0]:.3f}s hits {in_window[1]} "
        f"misses {in_window[2]}")
    for i, r in enumerate(served):
        log(f"request {i}: {r.plan.label} of {r.plan.size}, sent at "
            f"{r.sent_t - t0:.3f}s, took "
            f"{(r.done_t or float('nan')) - r.sent_t:.3f}s")
    for r in served:                       # answers to the host, then free
        if r.value is not None and not isinstance(r.value, dict):
            r.value = jax.device_get(r.value)

    # ---- correctness: every answer of the window against the reference
    from bench.reference import Reference
    t_check = time.perf_counter()
    kinds = {r.plan.kind for r in served}
    found = []
    published = [r for r in served if r.plan.kind == "update"
                 and r.plan.wait == "publish" and r.done_t is not None]
    if published:            # read back through the program, then free it
        found += checks.readback(
            dep.front, warm_writes + [tuple(r.plan.data[k] for k in (
                "src", "dst", "w", "op")) for r in published],
            Reference(*dep.host_edges, dep.nv), gen.rng(3), READBACK_SAMPLE)
    nv, n_edges, out_deg = dep.nv, len(dep.host_edges[0]), dep.pools.out_deg
    host_edges = dep.host_edges
    del dep, gen
    if "analytics" in kinds:
        found += checks.analytics(served, Reference(*host_edges, nv),
                                  cell.config.get("limits", {}))
    unanswered = sum(1 for r in served if r.done_t is None or r.ticket.shed)
    log(f"checks took {time.perf_counter() - t_check:.1f}s")

    # ---- metrics
    ctx = SimpleNamespace(cell=cell, seed=seed, seconds=seconds,
                          served=served, t0=t0, t1=t1, setup_s=setup_s,
                          nv=nv, n_edges=n_edges, out_deg=out_deg,
                          peaks=peaks, spans=spans, flushes=flushes,
                          trace=None, win=None)
    device = {"platform": devs[0].platform, "kind": kind,
              "count": len(devs), "memory_peak_bytes": int(memory_peak)}
    result = {"correct": False, "attempted": len(served),
              "failed": unanswered, "metrics": {}, "device": device}
    if trace:
        t_trace = time.perf_counter()
        tr = tracing.Trace(tracing.load(tdir))
        shutil.rmtree(tdir, ignore_errors=True)
        log(f"trace: {len(tr.device)} device operations, "
            f"{len(tr.host)} host events, read in "
            f"{time.perf_counter() - t_trace:.1f}s")
        lo, hi = tr.window()
        ctx.trace, ctx.win = tr, (lo, hi)
        device["busy_s"] = tr.busy_s(lo, hi)
        device["window_s"] = (hi - lo) / 1e9
        result["breakdown"] = {"device_ops": tr.top_ops(lo, hi),
                               "idle_gaps": tr.idle_gaps(lo, hi)}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = spec.metric_reader(m["name"], bench_dir)(ctx)
        if value is not None:
            result["metrics"][m["name"]] = {"value": float(value),
                                            "unit": m["unit"]}
    result["correct"] = all(c.ok for c in found) and bool(served)
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                        for c in found}
    for c in found:
        log(f"check {c.name}: {c.value} <= {c.limit}: "
            f"{'pass' if c.ok else 'FAIL'}")
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace))
    except NoChip as e:
        log(f"bench: {e}")
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
