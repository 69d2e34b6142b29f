"""admit_wait_p90_ms (ms): the 90th percentile, over requests due in the
window, of the time from the due time (a closed loop's send time) to the
``admitted`` event of the program's request span (``serving/tracing.py``);
a request not admitted by the close counts at the close."""
import numpy as np


def read(run):
    win = run.win
    waits = []
    for r in win.recs:
        if r.due is None or not (win.t_open <= r.due <= win.t_close):
            continue
        t = run.admitted.get(r.item.uid)
        t = win.t_close if t is None or t > win.t_close else t
        waits.append((t - r.due) * 1e3)
    return float(np.percentile(waits, 90)) if waits else None
