#!/usr/bin/env python3
"""Run one benchmark cell once, traced, and reduce the trace by the
program's own spans and device scopes (``benchlib/phases.py``).

    python3 bench/phases.py --workload <cell> --seed <n> --seconds <s> \
        [--keep DIR]

Set-up and the window are ``bench/run.py``'s (``Setup``, ``measure``), so
the window is the one a ``--trace 1`` run profiles.  The last line of
stdout is one JSON object: the window's ``output_tok_s`` on the host
clock (traced), its steps, retraces and compiles, the per-layer numbers
of ``benchlib.phases``, the host seconds per phase from the program's
registry counters, and a breakdown with idle gaps named by phase and
device seconds per scope.  ``--keep`` copies the ``.xplane.pb``, and the
compiled step's HLO text for each stream width, into DIR.
No reference check is made.  With no TPU it exits 3 and prints nothing.
"""
from __future__ import annotations

import argparse
import json
import shutil
import sys
import tempfile
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import run as R  # noqa: E402  (bench/run.py: set-up and window)


def reduce(t, engine: dict, arch) -> dict:
    """The per-layer numbers of ``benchlib.phases`` for one trace."""
    from benchlib import phases
    # The pool holds one scratch page past ``num_pages``.
    pool = (engine["num_pages"] + 1, arch.hkv, engine["page_size"], arch.dh)
    return {
        "steps": len(phases.steps(t)),
        "sched_idle_ms": phases.sched_idle_ms(t),
        "engine_idle_ms": phases.engine_idle_ms(t),
        "loop_idle_ms": phases.loop_idle_ms(t),
        "phase_idle_share": phases.phase_idle_share(t),
        "sampler_ms_per_step": phases.sampler_ms_per_step(t),
        "pool_write_share": phases.pool_write_share(t, pool),
        "scoped_share": phases.scoped_share(t),
        "unattributed_share": phases.unattributed_share(t, pool),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--keep", default=None, metavar="DIR")
    args = ap.parse_args(argv)
    cell = R.Cell(R.ROOT, args.workload)
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < int(cell.workload["chips"]):
        R.log(f"needs {cell.workload['chips']} TPU chip(s)")
        return 3
    from benchlib import endtoend, phases, traffic, xtrace
    setup = R.Setup(cell, args.seed)
    items = traffic.build(cell.traffic, args.seed, args.seconds,
                          setup.a.vocab)
    with tempfile.TemporaryDirectory(prefix="bench-phases-") as tmp:
        win, state = R.measure(cell, setup, items, args.seconds, tmp)
        path = xtrace.find(tmp)
        # The compiled step of every stream width the window may have run
        # (loaded from the compile cache): the ops' scopes live there.
        widths = cell.config["engine"]["token_buckets"]
        hlos = [setup.eng.compiled_step_hlo(w) for w in widths]
        if args.keep:
            keep = Path(args.keep)
            keep.mkdir(parents=True, exist_ok=True)
            stem = f"{args.workload}.{args.seed}"
            shutil.copy(path, keep / f"{stem}.xplane.pb")
            for w, hlo in zip(widths, hlos):
                (keep / f"{stem}.step{w}.hlo.txt").write_text(hlo)
        t = phases.load(path, hlos)
    reg = state["reg_delta"]
    out = {
        "workload": args.workload, "seed": args.seed,
        "device": {"kind": devs[0].device_kind, "count": len(devs)},
        "output_tok_s": endtoend.compute("output_tok_s", win,
                                         win.t_open - R.T_START),
        "window_steps": state["steps"], "retraces": state["retraces"],
        "compiles_in_window": state["compiles_in_window"],
        "metrics": reduce(t, cell.config["engine"], setup.a),
        "host_seconds": {k: v for k, v in sorted(reg.items())
                         if k.endswith("seconds_total")
                         or k == "compile_cache_loads_total"},
        "breakdown": phases.breakdown(t),
    }
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
