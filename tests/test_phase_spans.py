"""Phase spans inside the engine step and the serve loop.

- ``obs.span`` opens a profiler annotation, nests, and adds its seconds
  to the phase's counter; on a metrics-off bundle it only annotates;
- the five phases tile ``serve.step`` in order, and the step's latency
  histogram and ring take their duration from it;
- the GC hook and the compile listener count, and come off again;
- the program opens no span under the benchmark's names;
- the step's device regions are named scopes in the compiled HLO.
"""
import asyncio
import gc
import re
import time

import jax
import jax.numpy as jnp
import pytest

from repro.models import layers as L
from repro.serving import AsyncLMServer, EngineCore, Request, tracing
from repro.serving.tracing import PHASE_SPANS, ServingObservability
from tests.test_engine_core import build, prompts_for

BENCH_SPANS = {"bench.window", "engine.step", "scheduler.pack"}
PHASES = ("serve.schedule", "serve.upload", "serve.dispatch", "serve.wait",
          "serve.commit")


class Annotations:
    """Stands in for ``TraceAnnotation``: records each annotation's name,
    stats and host-clock interval, in the order they were opened."""

    def __init__(self):
        self.events = []            # [name, attrs, t_enter, t_exit]

    def __call__(self, name, **attrs):
        events = self.events

        class Ann:
            def __enter__(self):
                self.i = len(events)
                events.append([name, attrs, time.perf_counter(), None])
                return self

            def __exit__(self, *exc):
                events[self.i][3] = time.perf_counter()

        return Ann()

    def names(self):
        return [e[0] for e in self.events]


@pytest.fixture
def ann(monkeypatch):
    rec = Annotations()
    monkeypatch.setattr(tracing, "TraceAnnotation", rec)
    return rec


def _engine(**kw):
    cfg, params = build()
    eng = EngineCore(cfg, params, lanes=3, page_size=8, num_pages=24,
                     chunk_size=8, mode="ragged", **kw)
    reqs = [Request(uid=i, prompt=p, max_new=5)
            for i, p in enumerate(prompts_for(cfg, 3, (3, 9, 14, 6)))]
    return eng, reqs


def test_span_nests_and_counts(ann):
    obs = ServingObservability()
    with obs.span("serve.step", step=4) as outer:
        with obs.span("serve.wait") as inner:
            time.sleep(0.01)
    assert ann.names() == ["serve.step", "serve.wait"]
    (_, attrs, s0, s1), (_, _, w0, w1) = ann.events
    assert attrs == {"step": 4}
    assert s0 <= w0 <= w1 <= s1
    assert outer.seconds >= inner.seconds >= 0.01
    reg = obs.registry
    assert reg.value("wait_seconds_total") == pytest.approx(inner.seconds)
    assert reg.value("step_seconds_total") == pytest.approx(outer.seconds)
    with obs.span("serve.wait") as again:
        pass
    assert reg.value("wait_seconds_total") == pytest.approx(
        inner.seconds + again.seconds)


def test_disabled_span_only_annotates(ann):
    obs = ServingObservability(enabled=False)
    with obs.span("serve.schedule") as sp:
        time.sleep(0.001)
    assert ann.names() == ["serve.schedule"]
    assert sp.seconds > 0
    assert obs.registry.value("schedule_seconds_total") == 0


@pytest.mark.parametrize("metrics", [True, False])
def test_phase_spans_tile_the_engine_step(ann, metrics):
    eng, reqs = _engine(metrics=metrics)
    for r in reqs:
        eng.submit(r)
    steps = 0
    while eng.scheduler.has_work():
        eng.step()
        steps += 1
    ev = ann.events
    step_idx = [i for i, e in enumerate(ev) if e[0] == "serve.step"]
    assert len(step_idx) == steps
    # A metrics-off engine counts no steps, and numbers none.
    assert [ev[i][1].get("step") for i in step_idx] == (
        list(range(1, steps + 1)) if metrics else [None] * steps)
    gaps = total = 0.0
    for k, i in enumerate(step_idx):
        end = step_idx[k + 1] if k + 1 < len(step_idx) else len(ev)
        _, _, a, b = ev[i]
        inner = ev[i + 1:end]
        assert tuple(e[0] for e in inner) == PHASES
        t = a
        for _, _, c, d in inner:
            assert t <= c <= d <= b
            gaps += c - t
            t = d
        gaps += b - t
        total += b - a
    # Only the statements between the phases are left out of them.
    assert gaps < 0.01 * total, (gaps, total)
    if not metrics:
        return

    reg = eng.obs.registry
    ring = eng.obs.ring.records()
    assert reg.value("step_seconds_total") == pytest.approx(
        sum(r["dur_ms"] for r in ring) * 1e-3)
    assert eng.obs.h_step_ms.sum() == pytest.approx(
        sum(r["dur_ms"] for r in ring))
    assert reg.value("step_seconds_total") >= sum(
        reg.value(PHASE_SPANS[p][0]) for p in PHASES)
    for p in PHASES:
        assert reg.value(PHASE_SPANS[p][0]) > 0


def test_gc_hook_and_compile_listener_count(ann):
    obs = ServingObservability()
    n_callbacks = len(gc.callbacks)
    obs.install_hooks()
    obs.install_hooks()                       # idempotent
    try:
        assert len(gc.callbacks) == n_callbacks + 1
        gc.collect()
        jax.jit(lambda x: x * 3 + 1)(jnp.arange(7.0)).block_until_ready()
        jax.monitoring.record_event("/jax/compilation_cache/cache_hits")
    finally:
        obs.remove_hooks()
    reg = obs.registry
    assert "serve.gc" in ann.names()
    assert reg.value("gc_pause_seconds_total") > 0
    assert reg.value("jit_compile_seconds_total") > 0
    assert reg.value("compile_cache_loads_total") >= 1
    assert len(gc.callbacks) == n_callbacks
    before = reg.snapshot()
    gc.collect()
    jax.jit(lambda x: x * 5 - 2)(jnp.arange(3.0)).block_until_ready()
    assert reg.delta(before) == {k: 0 for k in reg.delta(before)}


def test_program_spans_avoid_the_benchmark_names(ann):
    assert not BENCH_SPANS & set(PHASE_SPANS)
    obs = ServingObservability()
    for name in BENCH_SPANS:
        with pytest.raises(KeyError):
            obs.span(name)

    eng, reqs = _engine()

    async def main():
        async with AsyncLMServer(eng) as server:
            async def one(req):
                return [t async for t in server.generate(req)]
            return await asyncio.gather(*(one(r) for r in reqs))

    outs = asyncio.run(main())
    assert all(len(o) == 5 for o in outs)
    names = set(ann.names())
    assert names <= set(PHASE_SPANS)
    assert {"serve.step", "serve.intake", "serve.flush", *PHASES} <= names


def test_step_scopes_name_the_device_regions():
    eng, _ = _engine()
    paths = [n.split("/") for n in
             re.findall(r'op_name="([^"]*)"', eng.compiled_step_hlo())]
    for scope in (L.SCOPE_EMBED, L.SCOPE_ATTENTION, L.SCOPE_KV_WRITE,
                  L.SCOPE_MLP, L.SCOPE_HEAD, L.SCOPE_SAMPLE):
        assert any(scope in p for p in paths), scope
    # The pool update is named inside its layer's attention.
    assert any(p.index(L.SCOPE_ATTENTION) < p.index(L.SCOPE_KV_WRITE)
               for p in paths if L.SCOPE_KV_WRITE in p)
