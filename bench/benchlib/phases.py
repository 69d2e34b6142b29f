"""The program's own spans and device scopes in a profiler trace.

``xtrace`` reduces a trace by the benchmark's spans (``bench.window``,
``engine.step``, ``scheduler.pack``) and by op names.  The serving stack
writes spans of its own (``serve.*``: the phases of each engine step and of
the serve loop, ``repro/serving/tracing.py``) and names the device regions
of its step with ``jax.named_scope`` (``SCOPES``).  XLA keeps a scope in
the ``op_name`` metadata of the compiled HLO, not in the trace: a v5e
trace's ``XLA Ops`` events carry the instruction's text without metadata.
So an op's scope is read from the compiled module that ran it: each
``XLA Modules`` event holds the ops that start inside it, and its module
is the compiled HLO text (one per step shape) that holds all of them,
matched by instruction name and result shape.

- ``load`` reads what ``xtrace.load`` reads, plus the ``serve.*`` host
  spans, each op's result shape and its innermost scope (``""``: none);
- ``idle_in``, ``scope_seconds`` and ``idle_gaps`` reduce them;
- the functions below them are per-layer numbers built on these, over the
  window's complete steps (``serve.step`` spans wholly inside it: the
  profiler drops a span that was open when it started or stopped).

On a trace without program spans or scopes, ``idle_gaps`` returns exactly
what ``xtrace.idle_gaps`` returns, and the per-layer numbers return None.
All times are in the trace's clock, in nanoseconds, on device 0.
"""
from __future__ import annotations

import bisect
import dataclasses
import re
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from benchlib import xtrace

Span = xtrace.Span
SCOPES = ("embed", "attention", "kv_write", "mlp", "head", "sample")
KERNEL = "paged_attention"
PHASES = ("serve.schedule", "serve.upload", "serve.dispatch", "serve.wait",
          "serve.commit")
ENGINE = ("serve.upload", "serve.dispatch", "serve.commit")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_INSTR = re.compile(r"\s*(?:ROOT )?%(\S+) = (\S+)")


@dataclasses.dataclass
class Trace(xtrace.Trace):
    # Per device, per op (aligned with ``ops``): result shape, scope.
    results: List[List[str]] = dataclasses.field(default_factory=list)
    scopes: List[List[str]] = dataclasses.field(default_factory=list)


def scope_of(path: str) -> str:
    """The innermost of ``SCOPES`` in an ``op_name`` path, else ``""``."""
    for part in reversed(path.split("/")):
        if part in SCOPES:
            return part
    return ""


def hlo_scopes(hlo: str) -> Dict[Tuple[str, str], str]:
    """(instruction, result shape) → innermost scope, for every instruction
    of a compiled module's HLO text.  An instruction XLA made without
    metadata (a sort that a scatter became) takes the scope of its
    computation where all of that computation's scoped instructions share
    one, as a branch of the sampler's ``cond`` does."""
    out, comp = {}, []
    for line in hlo.splitlines() + [""]:
        m = _INSTR.match(line)
        if m:
            path = _OP_NAME.search(line)
            comp.append(((m.group(1), m.group(2)),
                         scope_of(path.group(1) if path else "")))
        elif not line.startswith(" "):          # a computation ends
            named = {s for _, s in comp if s}
            fill = named.pop() if len(named) == 1 else ""
            out.update((k, s or fill) for k, s in comp)
            comp = []
    return out


def _key(event_name: str) -> Tuple[str, str]:
    m = _INSTR.match(event_name)
    return (m.group(1), m.group(2)) if m else (xtrace.op_name(event_name), "")


def _scopes(keys, starts, modules, tables) -> List[str]:
    """Each op's scope: the table of the module execution it starts in,
    chosen per module as the first table holding all of its ops."""
    out = [""] * len(keys)
    if not tables:
        return out
    members = defaultdict(list)             # module name → op indices
    mod_starts = [a for _, a, _ in modules]
    for i, s in enumerate(starts):
        j = bisect.bisect_right(mod_starts, s) - 1
        if j >= 0 and s < modules[j][2]:
            members[modules[j][0]].append(i)
    for ops in members.values():
        table = next((tb for tb in tables
                      if all(keys[i] in tb for i in ops)), None)
        if table is not None:
            for i in ops:
                out[i] = table[keys[i]]
    return out


def load(path: str, hlos: Sequence[str] = ()) -> Trace:
    """The trace at ``path``; ``hlos`` are the compiled HLO texts of the
    step shapes that ran in it (none: every op's scope is ``""``)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    tables = [hlo_scopes(h) for h in hlos]
    ops, results, scopes, host = [], [], [], defaultdict(list)
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            modules, keys, dev = [], [], []
            for line in plane.lines:
                if line.name == "XLA Modules":
                    modules = sorted(((e.name, e.start_ns, e.end_ns)
                                      for e in line.events),
                                     key=lambda m: m[1])
                elif line.name == "XLA Ops":
                    for e in line.events:
                        keys.append(_key(e.name))
                        dev.append((xtrace.op_name(e.name), e.start_ns,
                                    e.end_ns))
            if dev:
                ops.append(dev)
                results.append([r for _, r in keys])
                scopes.append(_scopes(keys, [a for _, a, _ in dev], modules,
                                      tables))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in xtrace.HOST_SPANS or \
                            e.name.startswith("serve."):
                        host[e.name].append((e.start_ns, e.end_ns))
    for v in host.values():
        v.sort()
    win = host.get("bench.window")
    return Trace(window=win[0] if win else None, ops=ops, host=dict(host),
                 results=results, scopes=scopes)


def _busy(t: xtrace.Trace) -> List[Span]:
    lo, hi = t.window
    return xtrace.union([(a, b) for _, a, b in xtrace.clip(t.ops[0], lo, hi)])


def _intersect(xs: List[Span], ys: List[Span]) -> List[Span]:
    """Intersection of two disjoint, sorted interval lists."""
    out, i, j = [], 0, 0
    while i < len(xs) and j < len(ys):
        a, b = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        if a < b:
            out.append((a, b))
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return out


def steps(t: xtrace.Trace) -> List[Span]:
    """The ``serve.step`` spans wholly inside the window."""
    if t.window is None:
        return []
    lo, hi = t.window
    return [(a, b) for a, b in t.host.get("serve.step", ())
            if lo <= a and b <= hi]


def idle_in(t: xtrace.Trace, names: Iterable[str],
            within: Optional[List[Span]] = None) -> Optional[float]:
    """Device idle time (ns) in the window inside any span named in
    ``names`` (and inside ``within``, disjoint and sorted, if given)."""
    if t.window is None or not t.ops:
        return None
    lo, hi = t.window
    spans = xtrace.union([(max(a, lo), min(b, hi)) for n in names
                          for a, b in t.host.get(n, ()) if b > lo and a < hi])
    if within is not None:
        spans = _intersect(spans, within)
    busy = _busy(t)
    return sum((b - a) - xtrace.overlap(busy, a, b) for a, b in spans)


def _leaf_ops(t: Trace):
    """Device 0's leaf ops in the window, clipped to it, as in
    ``xtrace.op_seconds``: (t0, t1, name, result shape, scope)."""
    lo, hi = t.window
    n = len(t.ops[0])
    res = t.results[0] if t.results else [""] * n
    sc = t.scopes[0] if t.scopes else [""] * n
    ev = sorted(((max(a, lo), min(b, hi), name, r, s) for (name, a, b), r, s
                 in zip(t.ops[0], res, sc) if b > lo and a < hi),
                key=lambda e: (e[0], -e[1]))
    for i, e in enumerate(ev):
        if i + 1 < len(ev) and ev[i + 1][0] < e[1] and ev[i + 1][1] <= e[1]:
            continue                              # encloses the next event
        yield e


def scope_seconds(t: Trace) -> Dict[str, float]:
    """Device seconds per innermost scope in the window (device 0, leaf
    ops); ``""`` collects ops with no scope."""
    if t.window is None or not t.ops:
        return {}
    out: Dict[str, float] = defaultdict(float)
    for a, b, _, _, s in _leaf_ops(t):
        out[s] += (b - a) * 1e-9
    return dict(out)


def idle_gaps(t: xtrace.Trace) -> List[Tuple[str, float]]:
    """Idle gaps on device 0 in the window, longest first, each named by
    the innermost span open at its midpoint, the program's ``serve.*``
    spans included; a gap under no span is ``outside engine.step``."""
    if t.window is None or not t.ops:
        return []
    lo, hi = t.window
    busy = _busy(t)
    edges = [lo] + [x for ab in busy for x in ab] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    spans = {n: (v, [a for a, _ in v]) for n, v in t.host.items()
             if n != "bench.window"}
    out = []
    for a, b in gaps:
        mid = (a + b) / 2
        name, start = "outside engine.step", None
        for n, (v, starts) in spans.items():
            i = bisect.bisect_right(starts, mid) - 1
            if i >= 0 and v[i][1] >= mid and (start is None
                                               or v[i][0] > start):
                name, start = n, v[i][0]
        out.append((name, (b - a) * 1e-9))
    return sorted(out, key=lambda g: -g[1])


def breakdown(t: Trace, n: int = 10) -> dict:
    """``xtrace.breakdown`` with gaps named by phase, and device seconds
    per scope."""
    ops = sorted(xtrace.op_seconds(t).items(), key=lambda kv: -kv[1])[:n]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in idle_gaps(t)[:n]],
            "scopes": [[k, v] for k, v in sorted(scope_seconds(t).items(),
                                                 key=lambda kv: -kv[1])]}


# ------------------------------------------------- per-layer numbers --

def _step_idle_ms(t: xtrace.Trace, names: Sequence[str]) -> Optional[float]:
    """Device idle inside ``names`` within the complete steps, per step."""
    done = steps(t)
    if not done or not t.ops or not any(t.host.get(n) for n in names):
        return None
    return idle_in(t, names, within=done) / len(done) * 1e-6


def sched_idle_ms(t: xtrace.Trace) -> Optional[float]:
    """Device idle inside ``serve.schedule``, per step (ms)."""
    return _step_idle_ms(t, ("serve.schedule",))


def engine_idle_ms(t: xtrace.Trace) -> Optional[float]:
    """Device idle inside ``serve.upload``, ``serve.dispatch`` and
    ``serve.commit``, per step (ms)."""
    return _step_idle_ms(t, ENGINE)


def loop_idle_ms(t: xtrace.Trace) -> Optional[float]:
    """Device idle between the window's first and last complete steps,
    outside every ``serve.step``, per step (ms)."""
    done = steps(t)
    if not done or not t.ops:
        return None
    between = [(done[0][0], done[-1][1])]
    outside = idle_in(t, ("bench.window",), within=between) - \
        idle_in(t, ("serve.step",), within=between)
    return outside / len(done) * 1e-6


def phase_idle_share(t: xtrace.Trace) -> Optional[float]:
    """Of the device idle time inside the complete steps, the share (%)
    inside one of the five phases."""
    done = steps(t)
    if not done or not t.ops or not any(t.host.get(n) for n in PHASES):
        return None
    inside = idle_in(t, ("serve.step",), within=done)
    return (100.0 * idle_in(t, PHASES, within=done) / inside
            if inside else None)


def _scoped(t: Trace) -> bool:
    return any(s for sc in t.scopes for s in sc)


def sampler_ms_per_step(t: Trace) -> Optional[float]:
    """Device time of ops under the ``sample`` scope in the window, per
    complete step (ms)."""
    done = steps(t)
    if not done or not _scoped(t):
        return None
    return scope_seconds(t).get("sample", 0.0) * 1e3 / len(done)


def _shares(t: Trace, pool: Tuple[int, ...]) -> Dict[str, float]:
    """Device seconds in the window: busy; under a scope; under
    ``kv_write``; in the kernel with no scope; in unscoped ops whose
    result is the KV pool, one layer's or the stacked layers' (``pool``:
    pages with the scratch page, kv heads, page size, head size)."""
    dims = ",".join(str(d) for d in pool)
    pool_shape = re.compile(rf"\(?[a-z0-9]+\[(\d+,)*{dims}\]")
    out = dict(busy=0.0, scoped=0.0, kv_write=0.0, kernel=0.0,
               pool_copies=0.0)
    for a, b, name, result, scope in _leaf_ops(t):
        d = (b - a) * 1e-9
        out["busy"] += d
        if scope:
            out["scoped"] += d
            if scope == "kv_write":
                out["kv_write"] += d
        elif KERNEL in name:
            out["kernel"] += d
        elif pool_shape.match(result):
            out["pool_copies"] += d
    return out


def pool_write_share(t: Trace, pool: Tuple[int, ...]) -> Optional[float]:
    """Share (%) of device busy time in ops that write or copy the KV pool:
    those under ``kv_write``, and the unscoped pool-shaped copies the layer
    scan puts around each layer."""
    if t.window is None or not t.ops or not _scoped(t):
        return None
    s = _shares(t, pool)
    return 100.0 * (s["kv_write"] + s["pool_copies"]) / s["busy"]


def scoped_share(t: Trace) -> Optional[float]:
    """Share (%) of device busy time in ops with a scope or in the
    kernel."""
    if t.window is None or not t.ops or not _scoped(t):
        return None
    s = _shares(t, ())
    return 100.0 * (s["scoped"] + s["kernel"]) / s["busy"]


def unattributed_share(t: Trace, pool: Tuple[int, ...]) -> Optional[float]:
    """Share (%) of device busy time in ops with no scope, outside the
    kernel and outside the pool copies."""
    if t.window is None or not t.ops or not _scoped(t):
        return None
    s = _shares(t, pool)
    return 100.0 * (s["busy"] - s["scoped"] - s["kernel"]
                    - s["pool_copies"]) / s["busy"]
