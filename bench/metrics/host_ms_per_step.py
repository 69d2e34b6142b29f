"""host_ms_per_step.<cell kind> (ms): the mean over the traced window's
engine steps of the step's span (the benchmark's ``engine.step``
annotation) minus the device busy time inside it: host work per step that
the device waits on."""
from benchlib import xtrace


def read(run):
    t = run.trace
    if t.window is None or not t.ops:
        return None
    lo, hi = t.window
    spans = [(a, b) for a, b in t.host.get("engine.step", ())
             if lo <= a and b <= hi]
    if not spans:
        return None
    busy = xtrace.union([(a, b) for _, a, b in t.ops[0]])
    host = [(b - a) - xtrace.overlap(busy, a, b) for a, b in spans]
    return sum(host) / len(host) * 1e-6
