#!/usr/bin/env python3
"""Find an open-loop cell's knee on the chip: the highest offered rate
whose backlog does not grow over the window.

    python3 bench/sweep.py --workload <open-loop cell> --rates 0.12,0.16,0.2 \
        --seconds 80 --seed 8

One process builds the cell once, then serves one window per rate (the
traffic file's mix, pre-roll included, with its rate replaced).  The
backlog at time t is the number of requests due by t that have not
finished by t; its growth is the slope of a least-squares line through
it, sampled every 0.5 s over the window (which opens after the traffic's
pre-roll).  A rate is sustained when that slope is under 10% of the rate.
One JSON line per rate, then a line naming the knee.
"""
from __future__ import annotations

import argparse
import copy
import importlib.util
import json
import sys
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent
spec = importlib.util.spec_from_file_location("bench_run", HERE / "run.py")
run = importlib.util.module_from_spec(spec)
spec.loader.exec_module(run)


def backlog_slope(win) -> float:
    """Requests per second by which the backlog grows over the window."""
    t = np.arange(win.t_open, win.t_close, 0.5)
    due = np.array([r.due for r in win.recs])
    end = np.array([r.times[-1] if r.done and r.times else np.inf
                    for r in win.recs])
    backlog = [(np.sum(due <= x) - np.sum(end <= x)) for x in t]
    return float(np.polyfit(t - t[0], backlog, 1)[0]) if len(t) > 2 else 0.0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args(argv)
    cell = run.Cell(run.ROOT, args.workload)
    import jax
    if jax.devices()[0].platform != "tpu":
        run.log("sweep: needs a TPU")
        return 3
    from benchlib import endtoend, traffic
    setup = run.Setup(cell, args.seed)
    knee = None
    for rate in sorted(float(r) for r in args.rates.split(",")):
        cell.traffic = dict(copy.deepcopy(cell.traffic), rate=rate)
        items = traffic.build(cell.traffic, args.seed, args.seconds,
                              setup.a.vocab)
        win, state = run.measure(cell, setup, items, args.seconds)
        slope = backlog_slope(win)
        done = sum(1 for r in win.recs if r.done)
        row = {"rate": rate, "backlog_slope": slope,
               "sustained": slope < 0.1 * rate, "requests": len(items),
               "finished": done, "retraces": state["retraces"],
               "late_ms_max": win.late_ms_max}
        for m in ("output_tok_s", "ttft_p50_ms", "ttft_p90_ms",
                  "itl_p95_ms"):
            row[m] = endtoend.compute(m, win, 0.0)
        print(json.dumps(row), flush=True)
        if row["sustained"]:
            knee = rate
    print(json.dumps({"workload": args.workload, "knee": knee}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
