"""From a profiler trace of the timed window to the program's own layers:
device time under each named scope of the round program, host time in each
``fedsllm.*`` span of the campaign, and the device's idle time by the span
the host was in.

The program names its device work with ``jax.named_scope`` (``SCOPES``) and
its host steps with ``jax.profiler.TraceAnnotation`` (``fedsllm.*``).  A
device op's scope path is its ``op_name`` metadata, which the trace keeps as
the ``tf_op`` stat of the op's event metadata on the device plane; a fusion
carries its root's.  ``ProfileData`` does not expose event metadata, so
``op_paths`` reads it from the ``.xplane.pb`` by the protobuf wire format.
HLO instruction names repeat across programs, so each op is keyed by the
program whose interval on the device's ``XLA Modules`` line holds it.

Device time is self time (``trace.self_times``) inside the window, averaged
over the chips that ran any op, and inclusive over scopes: an op counts
toward every scope on its path, so ``lora.merge`` and ``model.attention``
also count inside ``fedsllm.client`` or ``fedsllm.server``.
"""

from __future__ import annotations

import bisect
import re
from collections import defaultdict
from dataclasses import dataclass, field

from harness import trace

SCOPES = ("fedsllm.client", "fedsllm.server", "lora.merge", "fedsllm.aggregate",
          "model.attention", "model.ssd")
ROUND_SCOPES = ("fedsllm.client", "fedsllm.server", "fedsllm.aggregate")  # the round's parts
SPAN = "fedsllm."  # prefix of the program's host spans
CAMPAIGN, READ = "fedsllm.campaign", "fedsllm.read"
MODULES_LINE = "XLA Modules"
UNSCOPED = "(unscoped)"
NO_SPAN = "(no fedsllm span)"


@dataclass
class LayerTrace:
    ops: dict = field(default_factory=dict)  # device -> [(start, end, op, program, path)]
    modules: dict = field(default_factory=dict)  # device -> [(start, end, program)]
    host: list = field(default_factory=list)  # [(start, end, name)], every host event
    spans: list = field(default_factory=list)  # [(start, end, name, stats)], fedsllm.*


# --- the wire format of the trace's protobuf ---------------------------------

def _varint(b, i: int) -> tuple:
    x = shift = 0
    while True:
        c = b[i]
        i += 1
        x |= (c & 0x7F) << shift
        shift += 7
        if c < 0x80:
            return x, i


def _fields(b):
    """(field number, value) of a protobuf message: an int for a varint, a
    memoryview for a length-delimited or fixed-width field."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 2:
            ln, i = _varint(b, i)
            v, i = b[i:i + ln], i + ln
        elif wire in (1, 5):
            w = 8 if wire == 1 else 4
            v, i = b[i:i + w], i + w
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield key >> 3, v


def _map_values(entries):
    """Values of a protobuf map<int64, message> (entry: key 1, value 2)."""
    for entry in entries:
        for f, v in _fields(entry):
            if f == 2:
                yield v


def op_paths(path: str) -> dict:
    """{(device plane, program id, HLO op name): op_name scope path} from the
    device planes' event metadata (XSpace.planes 1; XPlane name 2,
    event_metadata 4, stat_metadata 5; XEventMetadata name 2, stats 5; XStat
    metadata_id 1, uint64 3, str 5, ref 7)."""
    with open(path, "rb") as fh:
        space = memoryview(fh.read())
    out = {}
    for f, plane in _fields(space):
        if f != 1:
            continue
        parts = defaultdict(list)
        for pf, pv in _fields(plane):
            if pf in (2, 4, 5):
                parts[pf].append(pv)
        name = bytes(parts[2][0]).decode() if parts[2] else ""
        if not name.startswith("/device:"):
            continue
        stat_names = {}
        for sm in _map_values(parts[5]):
            d = dict(_fields(sm))
            stat_names[d.get(1, 0)] = bytes(d.get(2, b"")).decode()
        for em in _map_values(parts[4]):
            op, stats = "", {}
            for ef, ev in _fields(em):
                if ef == 2:
                    op = trace.op_name(bytes(ev).decode())
                elif ef == 5:
                    s = dict(_fields(ev))
                    key = stat_names.get(s.get(1, 0))
                    if key == "program_id":
                        stats[key] = s.get(3)
                    elif key == "tf_op":
                        stats[key] = (bytes(s[5]).decode() if 5 in s
                                      else stat_names.get(s.get(7), ""))
            if "program_id" in stats:
                out[(name, stats["program_id"], op)] = stats.get("tf_op", "")
    return out


# --- loading ------------------------------------------------------------------

_PROGRAM = re.compile(r"\((\d+)\)$")


def load(path: str) -> LayerTrace:
    from jax.profiler import ProfileData

    paths = op_paths(path)
    lt = LayerTrace()
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            ops = []
            for line in plane.lines:
                if line.name == MODULES_LINE:
                    lt.modules[plane.name] = sorted(
                        (e.start_ns, e.start_ns + e.duration_ns, int(m.group(1)))
                        for e in line.events if (m := _PROGRAM.search(e.name)))
                elif line.name == trace.OPS_LINE:
                    ops = [(e.start_ns, e.start_ns + e.duration_ns, trace.op_name(e.name))
                           for e in line.events]
            mods = lt.modules.get(plane.name, [])
            starts = [s for s, _, _ in mods]
            placed = []
            for s, e, op in ops:
                k = bisect.bisect_right(starts, s) - 1
                program = mods[k][2] if k >= 0 and s < mods[k][1] else None
                placed.append((s, e, op, program,
                               paths.get((plane.name, program, op), "")))
            if placed:
                lt.ops[plane.name] = placed
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    lt.host.append((e.start_ns, e.start_ns + e.duration_ns, e.name))
                    if e.name.startswith(SPAN):
                        lt.spans.append((e.start_ns, e.start_ns + e.duration_ns, e.name,
                                         dict(e.stats)))
    return lt


def as_trace(lt: LayerTrace) -> trace.Trace:
    """The same events as ``trace.load`` gives them, for ``trace.reduce``."""
    return trace.Trace(ops={d: [(s, e, op) for s, e, op, _, _ in ops] for d, ops in lt.ops.items()},
                       host=lt.host)


def in_path(scope: str, op_path: str) -> bool:
    """``scope`` is a component of the path, bare or inside a transform's
    parentheses (``transpose(jvp(fedsllm.client))``, the backward pass); the
    trace ends a path with ``:<op type>``."""
    return re.search(rf"(^|[/(]){re.escape(scope)}($|[/):])", op_path) is not None


# --- reductions ---------------------------------------------------------------

def window(lt: LayerTrace) -> tuple:
    return trace.window(trace.Trace(host=lt.host))


def op_self_times(lt: LayerTrace, lo: int, hi: int) -> dict:
    """{(op, program, path): self seconds in [lo, hi]}, averaged over the
    devices that ran any op there."""
    out, n = defaultdict(float), 0
    for ops in lt.ops.values():
        clipped = [(max(s, lo), min(e, hi), (op, prog, p))
                   for s, e, op, prog, p in ops if min(e, hi) > max(s, lo)]
        if not clipped:
            continue
        n += 1
        for k, t in trace.self_times(clipped).items():
            out[k] += t / 1e9
    return {k: v / n for k, v in out.items()}


def scope_seconds(self_s: dict) -> dict:
    """{scope: device seconds of ops on its path} for each scope of
    ``SCOPES`` that any op carries, and ``UNSCOPED`` for ops under none."""
    out = defaultdict(float)
    for (_, _, p), t in self_s.items():
        hit = [s for s in SCOPES if in_path(s, p)]
        for s in hit or [UNSCOPED]:
            out[s] += t
    return dict(out)


def span_seconds(lt: LayerTrace, lo: int, hi: int) -> dict:
    """{span name: host seconds inside [lo, hi]} of the ``fedsllm.*`` spans."""
    out = defaultdict(float)
    for s, e, name, _ in lt.spans:
        if min(e, hi) > max(s, lo):
            out[name] += (min(e, hi) - max(s, lo)) / 1e9
    return dict(out)


def idle_gaps(lt: LayerTrace, lo: int, hi: int) -> list:
    """[(start, end)] of the window in which the first device that ran any op
    ran none (the gaps ``trace.reduce`` labels)."""
    for _, ops in sorted(lt.ops.items()):
        merged = trace.union(ops, lo, hi)
        if merged:
            edges = [lo] + [t for iv in merged for t in iv] + [hi]
            return [(s, e) for s, e in zip(edges[::2], edges[1::2]) if e > s]
    return []


def _gap_labeller(lt: LayerTrace, lo: int, hi: int):
    """gap -> (innermost fedsllm span, runtime label) at its midpoint; the
    runtime label is the shortest host event there other than the program's
    and the benchmark's spans, pooled as ``trace.reduce`` pools them for gaps
    under 10 us."""
    runtime = trace._labeller([h for h in lt.host if not h[2].startswith((SPAN, "bench."))],
                              lo, hi)
    spans = [(s, e, n) for s, e, n, _ in lt.spans if e > lo and s < hi]

    def label(gap) -> tuple:
        t = (gap[0] + gap[1]) / 2
        inner = [(se - ss, n) for ss, se, n in spans if ss <= t < se]
        return (min(inner)[1] if inner else NO_SPAN,
                runtime(t) if gap[1] - gap[0] >= trace.SMALL_GAP_NS else "(gaps under 10 us)")

    return label


def label_gap(lt: LayerTrace, lo: int, hi: int, gap) -> tuple:
    return _gap_labeller(lt, lo, hi)(gap)


def idle_by_span(lt: LayerTrace, lo: int, hi: int) -> dict:
    """{(innermost fedsllm span, runtime label): [seconds, gaps]} of the idle
    gaps, each placed by its midpoint."""
    label = _gap_labeller(lt, lo, hi)
    out = defaultdict(lambda: [0.0, 0])
    for s, e in idle_gaps(lt, lo, hi):
        g = out[label((s, e))]
        g[0] += (e - s) / 1e9
        g[1] += 1
    return dict(out)


def keys(lt: LayerTrace) -> dict:
    """The per-layer readers' inputs: ``scope_s`` {scope: device s},
    ``span_s`` {span: host s} and ``idle_span_s`` {innermost span: idle s}
    over the window.  A key is left out where the trace has nothing for it
    (a program without scopes or spans)."""
    lo, hi = window(lt)
    out = {}
    scopes = {k: v for k, v in scope_seconds(op_self_times(lt, lo, hi)).items()
              if k != UNSCOPED}
    if scopes:
        out["scope_s"] = scopes
    spans = span_seconds(lt, lo, hi)
    if CAMPAIGN in spans:
        out["span_s"] = spans
        if any(lt.ops.values()):
            idle = defaultdict(float)
            for (span, _), (sec, _) in idle_by_span(lt, lo, hi).items():
                idle[span] += sec
            out["idle_span_s"] = dict(idle)
    return out


def round_program(self_s: dict):
    """The program id whose ops carry the scopes (the round function), or
    None."""
    progs = defaultdict(float)
    for (_, prog, p), t in self_s.items():
        if any(in_path(s, p) for s in SCOPES):
            progs[prog] += t
    return max(progs, key=progs.get) if progs else None


def coverage(self_s: dict, program, top: int = 5) -> tuple:
    """(share of ``program``'s device time on a path of ``ROUND_SCOPES``,
    its ``top`` ops outside them as [(op, path, s)])."""
    total, inside, outside = 0.0, 0.0, []
    for (op, prog, p), t in self_s.items():
        if prog != program:
            continue
        total += t
        if any(in_path(s, p) for s in ROUND_SCOPES):
            inside += t
        else:
            outside.append((op, p, t))
    return (inside / total if total else None,
            sorted(outside, key=lambda o: -o[2])[:top])


def clock_check(lt: LayerTrace, lo: int, hi: int, program) -> list:
    """For each ``fedsllm.round`` that starts in the window: (ns from its
    ``fedsllm.dispatch`` start to the start of the run of ``program`` on the
    first device nearest it, ns from that run's end to the round's
    ``fedsllm.read`` end).  Both are positive where the device's clock and
    the host's agree."""
    mods = sorted(lt.modules.items())[0][1] if lt.modules else []
    runs = [(s, e) for s, e, prog in mods if prog == program]

    def first(name, rs, re_):
        return next(((s, e) for s, e, n, _ in sorted(lt.spans) if n == name and rs <= s < re_),
                    None)

    out = []
    for rs, re_, name, _ in lt.spans:
        if name != "fedsllm.round" or not lo <= rs < hi or not runs:
            continue
        d, q = first("fedsllm.dispatch", rs, re_), first(READ, rs, re_)
        if d and q:
            s, e = min(runs, key=lambda run: abs(run[0] - d[0]))
            out.append((s - d[0], q[1] - e))
    return out


def per_round_ms(m: dict, key: str, name: str):
    """ms per round of ``m[key][name]``; None where either is absent."""
    v = m.get(key, {}).get(name)
    if v is None or not m.get("rounds"):
        return None
    return 1e3 * v / m["rounds"]
