#!/usr/bin/env python3
"""Readings that set a cell's correctness limits, on the chip at the
cell's own size.  Not part of a benchmark run.

    python3 bench/control.py --workload ds7b.decode --seeds 21,22,23 \
        --seconds 51 [--arms sound,int8_kv]

Arms, one JSON line per seed and arm with the compared numbers:

- ``sound``: the program as configured; the same line carries ``w8a8``,
  the control read from the reference itself: at each position of the
  same prompts and served tokens, the gap of the token that the
  reference's int8-weight, int8-activation forward puts first;
- ``int8_kv``: the program with its own int8 KV pool switched on
  (``kv_quant``), the lower precision that the program offers;
- each fault of ``benchlib/faults.py`` (``state_unchanged``,
  ``token_altered``, ``top_p_ignored``, ``temperature_one``) planted in
  the program.
"""
from __future__ import annotations

import argparse
import contextlib
import importlib.util
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
spec = importlib.util.spec_from_file_location("bench_run", HERE / "run.py")
run = importlib.util.module_from_spec(spec)
spec.loader.exec_module(run)


def arm(cell, name: str, seed: int, seconds: float, require_chip=True):
    from benchlib import check, faults, traffic
    program = {"kv_quant": True} if name == "int8_kv" else None
    ctx = (faults.planted(name) if name in faults.KINDS
           else contextlib.nullcontext())
    t0 = time.perf_counter()
    cfg = cell.config
    with ctx:
        setup = run.Setup(cell, seed, require_chip, program=program)
        items = traffic.build(cell.traffic, seed, seconds, setup.a.vocab)
        win, state = run.measure(cell, setup, items, seconds)
    kv_lost = check.kv_bits_lost(setup.eng.kv.pool, cfg["torch_dtype"])
    setup.release()
    picked = check.pick(win.recs, seed, int(cfg.get("check_requests", 8)))
    row = {"arm": name, "seed": seed,
           "failed": sum(1 for r in win.recs if r.error is not None),
           "kv_bits_lost": kv_lost,
           **check.numbers(setup.a, setup.w, picked,
                           cfg["engine"]["max_len"])}
    if name == "sound":
        row["w8a8"] = check.numbers(setup.a, setup.w, picked,
                                    cfg["engine"]["max_len"], control=True)
    row["seconds"] = time.perf_counter() - t0
    return row


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--arms", default="sound,int8_kv")
    args = ap.parse_args(argv)
    import jax
    if jax.devices()[0].platform != "tpu":
        run.log("control: needs a TPU")
        return 3
    cell = run.Cell(run.ROOT, args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        for name in args.arms.split(","):
            print(json.dumps(arm(cell, name, seed, args.seconds)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
