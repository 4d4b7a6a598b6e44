"""From a profiler trace to numbers: device busy time, the operations that
took it, and the host spans open while the device sat idle.

A traced run captures its whole measured window with ``jax.profiler`` and
reduces it here in two steps, so the second can be tested on a small
recorded trace (``tests/trace_small.json``):

1. :func:`load` reads the ``.xplane.pb`` into plain lists: device
   operations and device programs (``XLA Ops`` / ``XLA Modules`` lines of
   each ``/device:TPU:n`` plane) and host events of the thread that holds
   the ``bench.window`` annotation, all in nanoseconds on one clock.
2. :class:`Trace` answers questions over a time range: busy seconds (the
   union of the operations' intervals), the operations that took most
   time, a program's time, and idle gaps labelled by the innermost host
   event that covers each gap's midpoint.
"""
from __future__ import annotations

import bisect
import glob
import json
import os
from collections import defaultdict
from contextlib import contextmanager
from typing import Dict, List, Optional, Tuple

WINDOW = "bench.window"
DEVICE_PREFIX = "/device:TPU:"


@contextmanager
def capture(log_dir: str):
    """Profile the enclosed block (no Python function tracing: it would
    cost more than the work it watches)."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(log_dir, profiler_options=opts):
        yield


def load(log_dir: str) -> dict:
    """The trace under ``log_dir`` as ``{"device", "modules", "host"}``.

    ``device`` and ``modules`` hold ``[plane, name, start_ns, dur_ns]``;
    ``host`` holds ``[name, start_ns, dur_ns]`` of the thread that ran the
    window.
    """
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    pd = ProfileData.from_file(paths[0])
    out = {"device": [], "modules": [], "host": []}
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            lines = {line.name: line for line in plane.lines}
            mods = sorted(([plane.name, e.name.split("(")[0], int(e.start_ns),
                            int(e.duration_ns)]
                           for e in lines["XLA Modules"].events),
                          key=lambda m: m[2]) if "XLA Modules" in lines else []
            out["modules"] += mods
            starts = [m[2] for m in mods]
            if "XLA Ops" in lines:
                out["device"] += [[plane.name, _op_name(e, mods, starts),
                                   int(e.start_ns), int(e.duration_ns)]
                                  for e in lines["XLA Ops"].events]
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                evs = [[e.name, int(e.start_ns), int(e.duration_ns)]
                       for e in line.events]
                if any(e[0] == WINDOW for e in evs):
                    out["host"] = evs
    return out


def _op_name(event, modules, starts) -> str:
    """``<program>/<op>``: the HLO instruction's name (the text before
    `` = ``), under the program that ran it (``modules`` sorted by start,
    ``starts`` their starts)."""
    op = event.name.split(" = ")[0]
    j = bisect.bisect_right(starts, event.start_ns) - 1
    if j >= 0 and event.start_ns < modules[j][2] + modules[j][3]:
        return f"{modules[j][1]}/{op}"
    return op


def _merge(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


class Trace:
    def __init__(self, data: dict):
        self.device = data["device"]
        self.modules = data["modules"]
        self.host = data["host"]
        self.planes = sorted({e[0] for e in self.device})

    @classmethod
    def from_file(cls, path: str) -> "Trace":
        with open(path) as f:
            return cls(json.load(f))

    def spans(self, name: str) -> List[Tuple[int, int]]:
        """Host intervals of the events called ``name``."""
        return [(s, s + d) for n, s, d in self.host if n == name]

    def window(self) -> Tuple[int, int]:
        return self.spans(WINDOW)[0]

    def _busy(self, plane: str, lo: int, hi: int):
        return _merge([(max(s, lo), min(s + d, hi)) for p, _, s, d
                       in self.device if p == plane and s < hi and s + d > lo])

    def busy_s(self, lo: int, hi: int) -> float:
        """Seconds in which an operation ran, averaged over the devices."""
        if not self.planes:
            return 0.0
        tot = sum(b - a for p in self.planes for a, b in self._busy(p, lo, hi))
        return tot / len(self.planes) / 1e9

    def top_ops(self, lo: int, hi: int, k: int = 10) -> List[list]:
        acc: Dict[str, int] = defaultdict(int)
        for _, name, s, d in self.device:
            if s < hi and s + d > lo:
                acc[name] += min(s + d, hi) - max(s, lo)
        top = sorted(acc.items(), key=lambda kv: -kv[1])[:k]
        return [[n, v / 1e9 / max(len(self.planes), 1)] for n, v in top]

    def module_s(self, substr: str, lo: int, hi: int) -> Optional[float]:
        """Device seconds of the programs whose name holds ``substr`` and
        that start inside [lo, hi), averaged over the devices; None when
        no such program ran."""
        hit = [d for _, n, s, d in self.modules
               if substr in n and lo <= s < hi]
        return sum(hit) / 1e9 / max(len(self.planes), 1) if hit else None

    def idle_gaps(self, lo: int, hi: int, k: int = 10) -> List[list]:
        """Idle device time on the first device, summed by the innermost
        host event open at each gap's midpoint, longest first."""
        if not self.planes:
            return []
        busy = self._busy(self.planes[0], lo, hi)
        gaps, t = [], lo
        for a, b in busy:
            if a > t:
                gaps.append((t, a))
            t = max(t, b)
        if t < hi:
            gaps.append((t, hi))
        # one sweep over host events and gap midpoints, both by time: the
        # events of one thread nest, so the innermost open event is the top
        # of a stack of those not yet ended
        acc: Dict[str, int] = defaultdict(int)
        host = sorted(((s, s + d, n) for n, s, d in self.host),
                      key=lambda e: (e[0], -e[1]))     # outer first
        stack: List[tuple] = []
        i = 0
        for a, b in sorted(gaps, key=lambda g: g[0] + g[1]):
            mid = (a + b) // 2
            while i < len(host) and host[i][0] <= mid:
                while stack and stack[-1][1] <= host[i][0]:
                    stack.pop()
                stack.append(host[i])
                i += 1
            while stack and stack[-1][1] <= mid:
                stack.pop()
            acc[stack[-1][2] if stack else "(no host event)"] += b - a
        top = sorted(acc.items(), key=lambda kv: -kv[1])[:k]
        return [[n, v / 1e9] for n, v in top]
