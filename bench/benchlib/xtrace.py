"""Reduce a JAX profiler trace (``*.xplane.pb``) to busy time, op times,
idle gaps and the host spans that were open during them.

Device planes are ``/device:TPU:<n>``; their ``XLA Ops`` line holds one
event per executed operation, named by its HLO instruction (the text
before `` = `` of the event's name, without the ``%``).  Host spans are the benchmark's own
``TraceAnnotation``s (``bench.window``, ``engine.step``,
``scheduler.pack``) on the host plane.  All times are in the trace's own
clock, in nanoseconds.
"""
from __future__ import annotations

import dataclasses
import glob
import os
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

Span = Tuple[float, float]
HOST_SPANS = ("bench.window", "engine.step", "scheduler.pack")


@dataclasses.dataclass
class Trace:
    window: Optional[Span]                       # bench.window, or None
    ops: List[List[Tuple[str, float, float]]]    # per device: (name, t0, t1)
    host: Dict[str, List[Span]]                  # span name → intervals


def find(logdir: str) -> Optional[str]:
    hits = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                            recursive=True))
    return hits[-1] if hits else None


def op_name(name: str) -> str:
    return name.split(" = ", 1)[0].lstrip("%")


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops, host = [], defaultdict(list)
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops.append([(op_name(e.name), e.start_ns, e.end_ns)
                                for e in line.events])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in HOST_SPANS:
                        host[e.name].append((e.start_ns, e.end_ns))
    for v in host.values():
        v.sort()
    win = host.get("bench.window")
    return Trace(window=win[0] if win else None, ops=ops, host=dict(host))


def union(spans: List[Span]) -> List[Span]:
    out: List[List[float]] = []
    for a, b in sorted(spans):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def overlap(merged: List[Span], lo: float, hi: float) -> float:
    """Length of ``merged`` (disjoint, sorted) inside [lo, hi]."""
    return sum(max(0.0, min(b, hi) - max(a, lo)) for a, b in merged)


def clip(ops, lo: float, hi: float):
    return [(n, max(a, lo), min(b, hi)) for n, a, b in ops if b > lo and a < hi]


def busy_ns(t: Trace) -> Optional[float]:
    """Device busy time in the window, averaged over the devices."""
    if t.window is None or not t.ops:
        return None
    lo, hi = t.window
    return sum(overlap(union([(a, b) for _, a, b in dev]), lo, hi)
               for dev in t.ops) / len(t.ops)


def op_seconds(t: Trace, match=None) -> Dict[str, float]:
    """Seconds per op name in the window (device 0), leaf ops only: an
    event that encloses another on the same line (a loop around its body)
    is not counted, so nothing is counted twice."""
    if t.window is None or not t.ops:
        return {}
    lo, hi = t.window
    ev = sorted(clip(t.ops[0], lo, hi), key=lambda e: (e[1], -e[2]))
    out: Dict[str, float] = defaultdict(float)
    for i, (n, a, b) in enumerate(ev):
        if i + 1 < len(ev) and ev[i + 1][1] < b and ev[i + 1][2] <= b:
            continue                              # encloses the next event
        if match is None or match(n):
            out[n] += (b - a) * 1e-9
    return dict(out)


def idle_gaps(t: Trace) -> List[Tuple[str, float]]:
    """Idle gaps on device 0 in the window, longest first, each named by
    the innermost benchmark span open at its midpoint."""
    if t.window is None or not t.ops:
        return []
    lo, hi = t.window
    busy = union([(a, b) for _, a, b in clip(t.ops[0], lo, hi)])
    edges = [lo] + [x for ab in busy for x in ab] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    out = []
    for a, b in gaps:
        mid = (a + b) / 2
        name = "outside engine.step"
        for span in ("engine.step", "scheduler.pack"):
            if any(s <= mid <= e for s, e in t.host.get(span, ())):
                name = span
        out.append((name, (b - a) * 1e-9))
    return sorted(out, key=lambda g: -g[1])


def breakdown(t: Trace, n: int = 10) -> dict:
    ops = sorted(op_seconds(t).items(), key=lambda kv: -kv[1])[:n]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in idle_gaps(t)[:n]]}
