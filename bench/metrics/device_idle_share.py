"""device_idle_share (%): the share of the traced window in which no
operation ran on the device, averaged over the chips (profiler trace)."""
from benchlib import xtrace


def read(run):
    busy = xtrace.busy_ns(run.trace)
    if busy is None:
        return None
    lo, hi = run.trace.window
    return 100.0 * (1.0 - busy / (hi - lo))
