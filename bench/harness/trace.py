"""From a profiler trace of the timed window to device busy time, idle time
and the breakdown.

The window is the host span ``bench.window`` the session writes around the
timed rounds.  Busy time is the union of the intervals of the device's
operations (line ``XLA Ops`` of each ``/device:TPU:<n>`` plane) inside the
window, averaged over the chips that ran any; idle is the rest.  Each idle
gap is labelled by the shortest host event that covers its midpoint: what the
host was doing while the device waited.
"""

from __future__ import annotations

import glob
import os
from collections import defaultdict
from dataclasses import dataclass, field

WINDOW = "bench.window"
OPS_LINE = "XLA Ops"


@dataclass
class Trace:
    ops: dict = field(default_factory=dict)  # device -> [(start_ns, end_ns, name)]
    host: list = field(default_factory=list)  # [(start_ns, end_ns, name)]


def find(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def op_name(hlo: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    tr = Trace()
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    tr.ops[plane.name] = [(e.start_ns, e.start_ns + e.duration_ns,
                                           op_name(e.name)) for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                tr.host.extend((e.start_ns, e.start_ns + e.duration_ns, e.name)
                               for e in line.events)
    return tr


def union(intervals, lo, hi) -> list:
    """Merged [start, end) intervals clipped to [lo, hi], in order."""
    out = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e, *_ in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def self_times(ops) -> dict:
    """Time of each op name not covered by the ops nested inside it (a
    loop's own time, less its body's), in ns; ``ops`` are (start, end, name)."""
    out = defaultdict(float)
    stack = []  # (end, name) of the ops enclosing the current one
    for s, e, n in sorted(ops, key=lambda o: (o[0], -o[1])):
        while stack and stack[-1][0] <= s:
            stack.pop()
        if stack:
            out[stack[-1][1]] -= min(e, stack[-1][0]) - s
        stack.append((e, n))
        out[n] += e - s
    return out


def window(tr: Trace) -> tuple:
    spans = [(s, e) for s, e, n in tr.host if n == WINDOW]
    if len(spans) != 1:
        raise ValueError(f"expected one {WINDOW!r} host span, found {len(spans)}")
    return spans[0]


SMALL_GAP_NS = 10_000  # gaps shorter than this are pooled, not labelled


def _labeller(host, lo, hi):
    import numpy as np

    ev = [(s, e, n) for s, e, n in host if e > lo and s < hi and n != WINDOW]
    starts = np.array([s for s, _, _ in ev], np.float64)
    ends = np.array([e for _, e, _ in ev], np.float64)
    names = [n for _, _, n in ev]

    def label(t) -> str:
        hit = np.nonzero((starts <= t) & (t < ends))[0]
        if not len(hit):
            return "(no host event)"
        return names[hit[np.argmin(ends[hit] - starts[hit])]]

    return label


def reduce(tr: Trace, top: int = 10) -> dict:
    """busy_s, window_s and the breakdown of the window; busy_s is None where
    no device operation ran in it."""
    lo, hi = window(tr)
    busy, per_op = [], defaultdict(float)
    merged_first = None
    for dev, ops in sorted(tr.ops.items()):
        merged = union(ops, lo, hi)
        if not merged:
            continue
        busy.append(sum(e - s for s, e in merged) / 1e9)
        merged_first = merged_first or merged
        clipped = [(max(s, lo), min(e, hi), n) for s, e, n in ops if min(e, hi) > max(s, lo)]
        for n, t in self_times(clipped).items():
            per_op[n] += t / 1e9
    gaps = defaultdict(lambda: [0.0, 0])
    if merged_first:
        label = _labeller(tr.host, lo, hi)
        edges = [lo] + [t for iv in merged_first for t in iv] + [hi]
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                name = (label((s + e) / 2) if e - s >= SMALL_GAP_NS
                        else "(gaps under 10 us)")
                g = gaps[name]
                g[0] += (e - s) / 1e9
                g[1] += 1
    ops_top = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    gaps_top = sorted(gaps.items(), key=lambda kv: -kv[1][0])[:top]
    return {"busy_s": sum(busy) / len(busy) if busy else None,
            "window_s": (hi - lo) / 1e9,
            "breakdown": {"device_ops": [[n, s] for n, s in ops_top],
                          "idle_gaps": [[f"{n} ({c} gaps)", s] for n, (s, c) in gaps_top]}}
