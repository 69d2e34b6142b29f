#!/usr/bin/env python3
"""Run one benchmark cell once, on the chip it finds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``; its
configuration is ``bench/configs/<config>.json`` (published keys, the
program's arch, engine settings, correctness limits), its traffic
``bench/traffic/<traffic>.json``, and each per-layer metric a reader
``bench/metrics/<name>.py`` (or ``<name before the first dot>.py``).

Set-up draws the weights from the seed on the device, builds the
program's ``EngineCore``, compiles or loads every step shape the traffic
uses, and (closed loop) fills the lanes.  The window then serves the
traffic through ``AsyncLMServer`` for ``--seconds``.  With ``--trace 1``
the window is profiled and the per-layer metrics are reported instead of
the end-to-end ones.  Afterwards a sample of served tokens is compared
with the plain float32 reference (``benchlib/check.py``).

The last line of stdout is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` [, ``breakdown``], ``checks``.  With no
TPU, or fewer chips than the cell asks for, it exits 3 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Optional  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


class Cell:
    """A workload with its configuration, traffic and metric entries."""

    def __init__(self, root: Path, name: str):
        spec = json.loads((root / "BENCHMARK.json").read_text())
        wl = {w["name"]: w for w in spec["workloads"]}
        if name not in wl:
            raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
        self.root, self.bench = root, root / "bench"
        self.name, self.workload = name, wl[name]
        self.config = json.loads(
            (self.bench / "configs" / f"{self.workload['config']}.json")
            .read_text())
        self.traffic = json.loads(
            (self.bench / "traffic" / f"{self.workload['traffic']}.json")
            .read_text())

        def mine(m):
            return name in m.get("workloads", [name])

        self.end_to_end = [m for m in spec["end_to_end"] if mine(m)]
        self.per_layer = [m for m in spec["per_layer"] if mine(m)]

    def reader(self, metric: str):
        d = self.bench / "metrics"
        path = d / f"{metric}.py"
        if not path.exists():
            path = d / f"{metric.split('.')[0]}.py"
        spec = importlib.util.spec_from_file_location(
            f"bench_metric_{path.stem.replace('.', '_')}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read


class CompileClock:
    """Backend compiles and their seconds, from JAX's monitoring events."""

    def __init__(self):
        import jax
        self.count, self.seconds = 0, 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1
            self.seconds += secs


def program_config(cfg: dict, a):
    """The program's ModelConfig for the file, checked against its widths."""
    from repro.configs import get_config
    p = cfg["program"]
    pc = get_config(p["arch"]).replace(**p.get("replace", {}))
    want = dict(d_model=a.d, num_layers=a.layers, num_heads=a.hq,
                num_kv_heads=a.hkv, d_head=a.dh, d_ff=a.f, vocab_size=a.vocab,
                tie_embeddings=a.tied, attn_bias=a.bias, mlp_gated=a.gated,
                rope_theta=a.theta)
    got = {k: getattr(pc, k) for k in want}
    if got != want:
        raise SystemExit(f"program config {got} differs from the published "
                         f"widths {want}")
    return pc


class Run:
    """What a per-layer reader may read."""

    def __init__(self, **kw):
        self.__dict__.update(kw)


def peaks_for(bench: Path, kind: str) -> dict:
    table = json.loads((bench / "peaks.json").read_text())["devices"]
    if kind not in table:
        raise SystemExit(f"no peaks for device kind {kind!r} in peaks.json")
    return table[kind]


class Setup:
    """Everything set-up builds: weights, the engine and its step log."""

    def __init__(self, cell: Cell, seed: int, require_chip: bool = True,
                 program: Optional[dict] = None):
        import jax
        from benchlib import model, serve
        from repro.launch.compile_cache import enable_compile_cache
        from repro.models import build_model
        from repro.serving import EngineCore

        self.cache = enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        self.clock = CompileClock()
        self.devs = jax.devices()
        dev = self.devs[0]
        self.peaks = (peaks_for(cell.bench, dev.device_kind)
                      if require_chip else None)
        cfg, ecfg = cell.config, cell.config["engine"]
        self.a = a = model.arch_of(cfg)
        self.pcfg = program_config(cfg, a).replace(**(program or {}))
        self.w = jax.block_until_ready(model.make_weights(a, seed))
        params = model.program_params(a, self.w)
        model.check_layout(params, jax.eval_shape(
            build_model(self.pcfg).init, jax.random.PRNGKey(0)))
        self.eng = EngineCore(
            self.pcfg, params, lanes=ecfg["lanes"],
            page_size=ecfg["page_size"], num_pages=ecfg["num_pages"],
            chunk_size=ecfg["chunk_size"], max_len=ecfg["max_len"],
            token_buckets=tuple(ecfg["token_buckets"]),
            prefix_cache=ecfg.get("prefix_cache", False),
            speculative=ecfg.get("speculative", False))
        self.steplog = serve.StepLog(self.eng)
        self.warm_steps = serve.warm(self.eng, ecfg, a.vocab, seed,
                                     lone=cell.traffic["loop"] == "open")

    def release(self) -> None:
        """Drop the program's state (engine, pool, params tree)."""
        self.eng = self.steplog = None
        gc.collect()


def measure(cell: Cell, setup: Setup, items, seconds: float,
            trace: Optional[str] = None):
    """Serve one window → (Window, window state).  With ``trace`` (a
    directory) the window is profiled into it."""
    import jax
    from benchlib import serve
    eng, clock, steplog = setup.eng, setup.clock, setup.steplog
    state = {}

    def on_open():
        eng.obs.mark_warm()
        state["reg"] = eng.obs.registry.snapshot()
        state["compiles"] = clock.count
        state["nsteps"] = len(steplog.steps)
        if trace:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0      # annotations, no Python calls
            opts.host_tracer_level = 1
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(trace, profiler_options=opts)
            state["ann"] = jax.profiler.TraceAnnotation("bench.window")
            state["t_ann"] = time.perf_counter()
            state["ann"].__enter__()
        return time.perf_counter()

    def on_close():
        t = time.perf_counter()
        if trace:
            state["ann"].__exit__(None, None, None)
            jax.profiler.stop_trace()
        state["reg_delta"] = eng.obs.registry.delta(state["reg"])
        state["compiles_in_window"] = clock.count - state["compiles"]
        return t

    win = serve.serve_window(eng, items, cell.traffic, seconds, on_open,
                             on_close)
    state["admitted"] = {}
    for r in win.recs:
        span = eng.obs.tracer.span(r.item.uid)
        ev = span.first("admitted") if span is not None else None
        if ev is not None:
            state["admitted"][r.item.uid] = ev.t
    state["retraces"] = state["reg_delta"].get("step_retraces_total", 0)
    state["steps"] = len(steplog.steps) - state["nsteps"]
    return win, state


def run_cell(name: str, seed: int, seconds: float, trace: bool,
             root: Path = ROOT, require_chip: bool = True) -> int:
    cell = Cell(root, name)
    import jax
    devs = jax.devices()
    chips = int(cell.workload["chips"])
    if require_chip and (devs[0].platform != "tpu" or len(devs) < chips):
        log(f"needs {chips} TPU chip(s); JAX found {len(devs)} "
            f"{devs[0].platform} device(s)")
        return 3
    import numpy as np
    from benchlib import check, endtoend, traffic, xtrace

    cfg = cell.config
    log(f"{name}: {cfg['name']} x {cell.workload['traffic']}, seed {seed}, "
        f"{seconds}s, trace {int(trace)}, {devs[0].device_kind}")
    setup = Setup(cell, seed, require_chip)
    items = traffic.build(cell.traffic, seed, seconds, setup.a.vocab)
    log(f"set-up: {setup.warm_steps} warm-up steps, {setup.clock.count} "
        f"compiles ({setup.clock.seconds:.1f}s), cache {setup.cache}")
    tmp = tempfile.TemporaryDirectory(prefix="bench-trace-") if trace else None
    win, state = measure(cell, setup, items, seconds,
                         tmp.name if trace else None)
    setup_s = win.t_open - T_START
    log(f"window: {state['steps']} steps, {state['compiles_in_window']} "
        f"compiles and {state['retraces']} step retraces inside it; sender "
        f"late by at most {win.late_ms_max:.3f} ms")
    memory_peak = max(int(d.memory_stats()["peak_bytes_in_use"])
                      for d in devs[:chips]) if require_chip else 0

    metrics, breakdown = {}, None
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": memory_peak}
    if trace:
        t = xtrace.load(xtrace.find(tmp.name))
        tmp.cleanup()
        steps = setup.steplog.steps
        if t.window is not None:      # host clock → trace clock
            off = t.window[0] - state["t_ann"] * 1e9
            steps = [s for s in steps if t.window[0] <= s.t0 * 1e9 + off
                     and s.t1 * 1e9 + off <= t.window[1]]
        run = Run(cell=cell, arch=setup.a, pcfg=setup.pcfg,
                  engine=cfg["engine"], win=win, steps=steps,
                  reg=state["reg_delta"], admitted=state["admitted"],
                  trace=t, peaks=setup.peaks)
        for m in cell.per_layer:
            v = cell.reader(m["name"])(run)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        busy = xtrace.busy_ns(t)
        device["busy_s"] = (busy or 0.0) * 1e-9
        device["window_s"] = ((t.window[1] - t.window[0]) * 1e-9
                              if t.window else 0.0)
        breakdown = xtrace.breakdown(t)
    else:
        for m in cell.end_to_end:
            v = endtoend.compute(m["name"], win, setup_s)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}

    # The reference runs once the program's state is gone.
    attempted = len(win.recs)
    failed = sum(1 for r in win.recs if r.error is not None)
    for r in win.recs:
        if r.error is not None:
            log(f"request {r.item.uid} failed: {r.error}")
    kv_lost = check.kv_bits_lost(setup.eng.kv.pool, cfg["torch_dtype"])
    setup.release()
    t_ref = time.perf_counter()
    picked = check.pick(win.recs, seed, int(cfg.get("check_requests", 8)))
    nums = check.numbers(setup.a, setup.w, picked, cfg["engine"]["max_len"])
    nums.update(failed_requests=failed, kv_bits_lost=kv_lost)
    limits = dict(cfg.get("correct", {}), failed_requests=0, kv_bits_lost=0)
    correct = bool(cfg.get("correct")) and check.verdict(nums, limits)
    log(f"reference: {len(picked)} requests in "
        f"{time.perf_counter() - t_ref:.1f}s: {json.dumps(nums)}")
    checks = {k: {"value": nums[k] if np.isfinite(nums[k]) else None,
                  "limit": v} for k, v in limits.items()}
    for k, v in checks.items():
        log(f"check {k} {v['value']!r} limit {v['limit']!r}")
    out = {"correct": bool(correct), "attempted": attempted, "failed": failed,
           "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    print(json.dumps(out), flush=True)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a whole number >= 0")
    return run_cell(args.workload, args.seed, args.seconds, bool(args.trace))


if __name__ == "__main__":
    sys.exit(main())
