"""Request-lifecycle spans, step trace ring, and the retrace sentinel.

``ServingObservability`` is the one object threaded through the serving
stack (EngineCore, Scheduler, PagedKVCache, RadixPrefixCache, the
n-gram proposer, AsyncLMServer).  It owns

* a :class:`~repro.serving.metrics.MetricsRegistry` (the single source
  of truth for every counter/gauge/histogram the stack reports),
* a :class:`RequestTracer` recording one span per request
  (submitted → admitted → first_token → finished/aborted, with
  preemption/resume, prefix-hit, draft accept/reject, and CoW events
  attached),
* a :class:`StepTraceRing` of the scheduler's last N step decisions
  (bucket width, table width, live/padded rows, trimmed drafts, pool
  occupancy, cache reclaimable pages), and
* the **retrace sentinel**: the jitted step closures already bump a
  python-side counter *inside* the traced function body — a side effect
  that runs exactly when XLA traces, i.e. on every jit-cache miss.
  ``step_traced()`` mirrors that into ``step_traces_total`` always and
  into ``step_retraces_total`` only after :meth:`mark_warm` — so the
  PR 8 class of bug (a mid-traffic table-width shrink forcing a ~2 s
  XLA stall) is a metric, not an archaeology project.

It also opens the stack's **phase spans** (:meth:`ServingObservability.span`):
each is a ``jax.profiler.TraceAnnotation`` — on the device trace's own
clock, so a profiler window shows which phase of the step or the serve
loop the host was in during every device idle gap — and its host-clock
seconds accumulate in a ``<phase>_seconds_total`` counter.  Two more
hooks feed stall counters: Python GC pauses (span ``serve.gc``) and JAX
compiles and compile-cache loads (a ``jax.monitoring`` listener).

Every hook early-returns when ``enabled=False`` (metrics-off engines
for the overhead A/B), a span then only opens its annotation, and
everything stays host-side, off the jitted path.
"""
from __future__ import annotations

import gc
import threading
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import jax
from jax.profiler import TraceAnnotation

from .metrics import Counter, Histogram, MetricsRegistry

__all__ = [
    "PHASE_SPANS",
    "PhaseSpan",
    "SpanEvent",
    "RequestSpan",
    "RequestTracer",
    "StepTraceRing",
    "ServingObservability",
]


# ------------------------------------------------------- phase spans --

# The stack's profiler spans and the counter each one's host-clock seconds
# go to.  ``serve.step`` is one ``EngineCore.step``; the five phases after
# it tile it in order; ``serve.intake`` and ``serve.flush`` are the serve
# loop's work between steps; ``serve.gc`` is a Python GC pause.
PHASE_SPANS: Dict[str, Tuple[str, str]] = {
    "serve.step": ("step_seconds_total", "seconds in EngineCore.step()"),
    "serve.schedule": ("schedule_seconds_total",
                       "seconds admitting, planning and packing a step"),
    "serve.upload": ("upload_seconds_total",
                     "seconds building and uploading a step's inputs"),
    "serve.dispatch": ("dispatch_seconds_total",
                       "seconds enqueueing the step (traces and compiles "
                       "on a jit-cache miss)"),
    "serve.wait": ("wait_seconds_total",
                   "seconds the host waited for the step's tokens"),
    "serve.commit": ("commit_seconds_total",
                     "seconds committing tokens and retiring requests"),
    "serve.intake": ("intake_seconds_total",
                     "seconds the serve loop spent on intake and aborts"),
    "serve.flush": ("flush_seconds_total",
                    "seconds the serve loop spent streaming tokens out"),
    "serve.gc": ("gc_pause_seconds_total", "seconds in Python GC pauses"),
}

# jax.monitoring events: what JAX spends tracing, lowering and compiling
# (a compile-cache load is timed as a compile), and a cache load itself.
_COMPILE_EVENTS = frozenset((
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
))
_CACHE_LOAD_EVENT = "/jax/compilation_cache/cache_hits"


class PhaseSpan:
    """One host span: a profiler annotation around the block, and its
    host-clock duration added to ``counter`` (``None``: annotation only).
    ``seconds`` holds the duration once the block has exited."""

    __slots__ = ("name", "attrs", "counter", "seconds", "_t0", "_ann")

    def __init__(self, name: str, counter: Optional[Counter], **attrs):
        self.name = name
        self.attrs = attrs
        self.counter = counter
        self.seconds = 0.0

    def __enter__(self) -> "PhaseSpan":
        self._ann = TraceAnnotation(self.name, **self.attrs)
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        # The bookkeeping runs inside the annotation, so that consecutive
        # spans leave the least time between them.
        self.seconds = time.perf_counter() - self._t0
        if self.counter is not None:
            self.counter.inc(self.seconds)
        self._ann.__exit__(*exc)


# ------------------------------------------------------------- spans --

@dataclass
class SpanEvent:
    name: str
    t: float
    attrs: Dict[str, object] = field(default_factory=dict)


@dataclass
class RequestSpan:
    uid: int
    start_t: float
    events: List[SpanEvent] = field(default_factory=list)
    status: Optional[str] = None          # "finished" | "aborted" | ...
    end_t: Optional[float] = None

    @property
    def open(self) -> bool:
        return self.status is None

    def event_names(self) -> List[str]:
        return [e.name for e in self.events]

    def first(self, name: str) -> Optional[SpanEvent]:
        for e in self.events:
            if e.name == name:
                return e
        return None

    def duration_ms(self) -> float:
        end = self.end_t if self.end_t is not None else self.start_t
        return (end - self.start_t) * 1e3


class RequestTracer:
    """One span per request uid; bounded deque of closed spans."""

    def __init__(self, max_finished: int = 1024, clock=time.perf_counter):
        self.clock = clock
        self._open: Dict[int, RequestSpan] = {}
        self.finished: deque = deque(maxlen=max_finished)

    def begin(self, uid: int, **attrs) -> RequestSpan:
        stale = self._open.pop(uid, None)
        if stale is not None:            # uid reuse with a leaked span
            stale.status = "orphaned"
            stale.end_t = self.clock()
            self.finished.append(stale)
        now = self.clock()
        span = RequestSpan(uid=uid, start_t=now)
        span.events.append(SpanEvent("submitted", now, dict(attrs)))
        self._open[uid] = span
        return span

    def event(self, uid: int, name: str, **attrs) -> None:
        span = self._open.get(uid)
        if span is not None:             # unknown uid: deliberate no-op
            span.events.append(SpanEvent(name, self.clock(), dict(attrs)))

    def end(self, uid: int, status: str, **attrs) -> Optional[RequestSpan]:
        span = self._open.pop(uid, None)
        if span is None:
            return None
        now = self.clock()
        span.events.append(SpanEvent(status, now, dict(attrs)))
        span.status = status
        span.end_t = now
        self.finished.append(span)
        return span

    def open_spans(self) -> Dict[int, RequestSpan]:
        return dict(self._open)

    def span(self, uid: int) -> Optional[RequestSpan]:
        """The open span for uid, else the most recent closed one."""
        got = self._open.get(uid)
        if got is not None:
            return got
        for span in reversed(self.finished):
            if span.uid == uid:
                return span
        return None


class StepTraceRing:
    """Bounded ring of per-step scheduler-decision records (dicts)."""

    def __init__(self, capacity: int = 512):
        self.capacity = capacity
        self._ring: deque = deque(maxlen=capacity)

    def append(self, record: Dict[str, object]) -> None:
        self._ring.append(record)

    def records(self) -> List[Dict[str, object]]:
        return list(self._ring)

    def last(self) -> Optional[Dict[str, object]]:
        return self._ring[-1] if self._ring else None

    def __len__(self) -> int:
        return len(self._ring)


# ----------------------------------------------------- observability --

class ServingObservability:
    """The bundle threaded through the serving stack.

    All mutating hooks early-return when ``enabled`` is False; family
    handles are pre-bound in ``__init__`` so the hot hooks are attribute
    bumps, not dict lookups.
    """

    def __init__(self, enabled: bool = True,
                 registry: Optional[MetricsRegistry] = None,
                 ring_capacity: int = 512):
        self.enabled = enabled
        self.registry = registry if registry is not None else MetricsRegistry()
        self.tracer = RequestTracer()
        self.ring = StepTraceRing(ring_capacity)
        self.warm = False
        self._profiler: Optional[dict] = None
        self._hooked = False
        self._gc_span: Optional[PhaseSpan] = None
        self._compile_lock = threading.Lock()

        r = self.registry
        self._phase_counters = {
            name: r.counter(counter, help)
            for name, (counter, help) in PHASE_SPANS.items()}
        self.c_jit_compile_s = r.counter(
            "jit_compile_seconds_total",
            "seconds JAX spent tracing, lowering and compiling (or loading "
            "from the compile cache)")
        self.c_cache_loads = r.counter(
            "compile_cache_loads_total",
            "executables loaded from the persistent compile cache")
        # -- step/engine counters
        self.c_steps = r.counter(
            "steps_total", "engine steps executed")
        self.c_mixed_steps = r.counter(
            "mixed_steps_total", "steps co-batching prefill and decode")
        self.c_traces = r.counter(
            "step_traces_total", "jit traces of the step fn (lifetime)")
        self.c_retraces = r.counter(
            "step_retraces_total", "step fn traces after mark_warm()")
        self.c_prefill_toks = r.counter(
            "prefill_tokens_total", "prompt tokens processed")
        self.c_decode_toks = r.counter(
            "decode_tokens_total", "decode tokens processed")
        self.c_live_rows = r.counter(
            "live_rows_total", "live token rows packed into steps")
        self.c_padded_rows = r.counter(
            "padded_rows_total", "padded stream width summed over steps")
        self.c_tokens_out = r.counter(
            "tokens_generated_total", "tokens committed to requests")
        # Host mirror of the varlen attention kernel's grid, per layer call
        # (kernels/paged_attention/varlen.py:kv_block_counts).
        self.c_kv_blocks_walked = r.counter(
            "attn_kv_blocks_walked_total",
            "(q_block, page_block) pairs the attention grid walks")
        self.c_kv_blocks_live = r.counter(
            "attn_kv_blocks_live_total",
            "walked pairs holding a live KV row (copied and computed)")
        self.c_trim_prefill = r.counter(
            "trimmed_prefill_tokens_total",
            "prefill tokens deferred by bucket trimming")
        self.c_trim_drafts = r.counter(
            "spec_trimmed_draft_tokens_total",
            "draft tokens dropped by trim/degrade before packing")
        # -- request lifecycle
        self.c_submitted = r.counter(
            "requests_submitted_total", "requests entering the scheduler")
        self.c_admitted = r.counter(
            "requests_admitted_total", "waiting->running admissions")
        self.c_resumed = r.counter(
            "requests_resumed_total", "preempted->running resumptions")
        self.c_finished = r.counter(
            "requests_finished_total", "requests completed")
        self.c_aborted = r.counter(
            "requests_aborted_total", "requests aborted/cancelled")
        self.c_preempted = r.counter(
            "preemptions_total", "requests preempted by page pressure")
        # -- speculative decoding
        self.c_drafted = r.counter(
            "spec_drafted_tokens_total", "draft tokens entering verify")
        self.c_accepted = r.counter(
            "spec_accepted_tokens_total", "draft tokens accepted")
        self.c_spec_steps = r.counter(
            "spec_steps_total", "steps that verified at least one draft")
        self.c_proposals = r.counter(
            "spec_proposals_total", "proposer calls that drafted tokens")
        self.c_proposed = r.counter(
            "spec_proposed_tokens_total", "tokens drafted by the proposer")
        # -- prefix cache / pages
        self.c_prefix_lookups = r.counter(
            "prefix_lookups_total", "prefix-cache lookups at admission")
        self.c_prefix_lookup_toks = r.counter(
            "prefix_lookup_tokens_total", "prompt tokens offered for reuse")
        self.c_prefix_hits = r.counter(
            "prefix_hits_total", "lookups that matched cached pages")
        self.c_prefix_hit_toks = r.counter(
            "prefix_hit_tokens_total", "prompt tokens served from cache")
        self.c_prefix_shared = r.counter(
            "prefix_shared_page_grants_total", "cached pages granted shared")
        self.c_prefix_evicted = r.counter(
            "prefix_evicted_pages_total", "cached pages evicted")
        self.c_cow = r.counter(
            "cow_copies_total", "copy-on-write page copies")
        # -- streaming front door
        self.c_stream_requests = r.counter(
            "stream_requests_total", "streamed requests finished")
        self.c_stream_cancelled = r.counter(
            "stream_cancelled_total", "streamed requests cancelled")
        self.c_stream_tokens = r.counter(
            "stream_tokens_total", "tokens emitted to streams")
        # -- gauges
        self.g_pool_in_use = r.gauge(
            "pool_pages_in_use", "page-pool pages currently referenced")
        self.g_pool_free = r.gauge(
            "pool_pages_free", "page-pool pages on the free heap")
        self.g_pool_peak = r.gauge(
            "pool_pages_in_use_peak", "high-water pages in use")
        self.g_waiting = r.gauge(
            "scheduler_waiting", "requests queued for admission")
        self.g_running = r.gauge(
            "scheduler_running", "requests resident in lanes")
        self.g_table_pages = r.gauge(
            "step_table_pages", "page-table width of the last step")
        self.g_cached_pages = r.gauge(
            "prefix_cached_pages", "pages held by the prefix cache")
        self.g_reclaimable = r.gauge(
            "prefix_reclaimable_pages", "cache-only pages reclaimable")
        self.g_mesh = r.gauge(
            "mesh_devices", "tensor-parallel mesh size")
        self.g_coll_per_tok = r.gauge(
            "collective_bytes_per_token",
            "analytic per-device all-gather bytes per packed token")
        self.g_coll_per_step = r.gauge(
            "collective_bytes_per_step",
            "measured per-device collective bytes per step (from HLO)")
        # -- histograms
        self.h_step_ms = r.histogram(
            "step_latency_ms", "wall time of EngineCore.step()")
        self.h_ttft_ms = r.histogram(
            "request_ttft_ms", "submit to first committed token")
        self.h_tpot_ms = r.histogram(
            "request_tpot_ms", "mean inter-token time per finished request")
        self.h_stream_ttft_ms = r.histogram(
            "stream_ttft_ms", "server submit to first streamed token")
        self.h_stream_tpot_ms = r.histogram(
            "stream_tpot_ms", "server mean inter-token time per stream")

    # ----------------------------------------------------- phase spans --
    def span(self, name: str, **attrs) -> PhaseSpan:
        """``with obs.span("serve.schedule"):`` — a profiler annotation
        named ``name`` (one of :data:`PHASE_SPANS`; ``attrs`` become its
        trace stats), whose host seconds go to the phase's counter when
        enabled."""
        counter = self._phase_counters[name]
        return PhaseSpan(name, counter if self.enabled else None, **attrs)

    def install_hooks(self) -> None:
        """Start the stall hooks: a ``gc.callbacks`` entry that spans each
        GC pause as ``serve.gc``, and a ``jax.monitoring`` listener for
        ``jit_compile_seconds_total`` / ``compile_cache_loads_total``.
        Both are process-wide, so the owner removes them with
        :meth:`remove_hooks` (the serve loop does on exit)."""
        if self._hooked:
            return
        self._hooked = True
        gc.callbacks.append(self._on_gc)
        jax.monitoring.register_event_duration_secs_listener(self._on_compile)
        jax.monitoring.register_event_listener(self._on_event)

    def remove_hooks(self) -> None:
        if not self._hooked:
            return
        self._hooked = False
        gc.callbacks.remove(self._on_gc)
        jax.monitoring.unregister_event_duration_listener(self._on_compile)
        jax.monitoring.unregister_event_listener(self._on_event)
        if self._gc_span is not None:
            self._gc_span.__exit__(None, None, None)
            self._gc_span = None

    def _on_gc(self, phase: str, info: dict) -> None:
        # A collection starts and stops on one thread, under the GIL.
        if phase == "start":
            self._gc_span = self.span("serve.gc").__enter__()
        elif self._gc_span is not None:
            self._gc_span.__exit__(None, None, None)
            self._gc_span = None

    def _on_compile(self, event: str, secs: float, **_) -> None:
        if self.enabled and event in _COMPILE_EVENTS:
            with self._compile_lock:     # compiles may run on any thread
                self.c_jit_compile_s.inc(secs)

    def _on_event(self, event: str, **_) -> None:
        if self.enabled and event == _CACHE_LOAD_EVENT:
            with self._compile_lock:
                self.c_cache_loads.inc()

    # ------------------------------------------------- retrace sentinel --
    def step_traced(self) -> None:
        """Called from *inside* the jitted step closures: runs only when
        XLA traces (a jit-cache miss), i.e. once per new input shape."""
        if not self.enabled:
            return
        self.c_traces.inc()
        if self.warm:
            self.c_retraces.inc()

    def mark_warm(self) -> None:
        """Every trace after this counts as a retrace (a bug signal)."""
        self.warm = True

    # ---------------------------------------------------- request hooks --
    def request_submitted(self, uid: int, prompt_len: int = 0,
                          max_new: int = 0) -> None:
        if not self.enabled:
            return
        self.c_submitted.inc()
        self.tracer.begin(uid, prompt_len=prompt_len, max_new=max_new)

    def request_admitted(self, uid: int, hit_tokens: int = 0,
                         resumed: bool = False) -> None:
        if not self.enabled:
            return
        if resumed:
            self.c_resumed.inc()
            self.tracer.event(uid, "resumed")
        else:
            self.c_admitted.inc()
            attrs = {"prefix_hit_tokens": hit_tokens} if hit_tokens else {}
            self.tracer.event(uid, "admitted", **attrs)

    def request_preempted(self, uid: int) -> None:
        if not self.enabled:
            return
        self.c_preempted.inc()
        self.tracer.event(uid, "preempted")

    def request_finished(self, uid: int, aborted: bool = False,
                         generated: int = 0) -> None:
        if not self.enabled:
            return
        if aborted:
            self.c_aborted.inc()
        else:
            self.c_finished.inc()
        span = self.tracer.end(uid, "aborted" if aborted else "finished",
                               generated=generated)
        if span is not None and not aborted and generated > 1:
            first = span.first("first_token")
            if first is not None:
                self.h_tpot_ms.observe(
                    (span.end_t - first.t) * 1e3 / (generated - 1))

    def tokens_committed(self, uid: int, n: int, first: bool) -> None:
        if not self.enabled or n <= 0:
            return
        self.c_tokens_out.inc(n)
        if first:
            self.tracer.event(uid, "first_token")
            span = self.tracer.span(uid)
            if span is not None and span.open:
                self.h_ttft_ms.observe(
                    (span.events[-1].t - span.start_t) * 1e3)

    def spec_proposed(self, tokens: int) -> None:
        if not self.enabled:
            return
        self.c_proposals.inc()
        self.c_proposed.inc(tokens)

    def spec_verify(self, uid: int, drafted: int, accepted: int) -> None:
        if not self.enabled or drafted <= 0:
            return
        self.tracer.event(uid, "spec_verify",
                          drafted=drafted, accepted=accepted)

    def cow_copy(self) -> None:
        """Counter-only: PagedKVCache.cow() calls this for every copy."""
        if not self.enabled:
            return
        self.c_cow.inc()

    def request_cow(self, uid: int) -> None:
        """Span-only: the scheduler attributes a CoW to a request."""
        if not self.enabled:
            return
        self.tracer.event(uid, "cow_copy")

    # ------------------------------------------------ prefix-cache hooks --
    def prefix_lookup(self, tokens: int, hit_tokens: int,
                      shared_pages: int) -> None:
        if not self.enabled:
            return
        self.c_prefix_lookups.inc()
        self.c_prefix_lookup_toks.inc(tokens)
        if hit_tokens:
            self.c_prefix_hits.inc()
            self.c_prefix_hit_toks.inc(hit_tokens)
            self.c_prefix_shared.inc(shared_pages)

    def prefix_evicted(self, pages: int = 1) -> None:
        if not self.enabled:
            return
        self.c_prefix_evicted.inc(pages)

    def kv_blocks(self, walked: int, live: int) -> None:
        """One ragged step's attention walk: grid pairs and live pairs."""
        self.c_kv_blocks_walked.inc(walked)
        self.c_kv_blocks_live.inc(live)

    # ------------------------------------------------------- step hook --
    def record_step(self, out, *, dur_ms: float, sched, kv,
                    cache=None, table_pages: int = 0,
                    trimmed_prefill: int = 0, trimmed_drafts: int = 0,
                    width: int = 0) -> None:
        """Called once per EngineCore.step() with the StepOutput."""
        if self._profiler is not None:
            self._profiler_tick()
        if not self.enabled:
            return
        self.c_steps.inc()
        if out.prefill_tokens and out.decode_tokens:
            self.c_mixed_steps.inc()
        self.c_prefill_toks.inc(out.prefill_tokens)
        self.c_decode_toks.inc(out.decode_tokens)
        self.c_live_rows.inc(out.live_rows)
        self.c_padded_rows.inc(out.padded_rows)
        if out.drafted_tokens:
            self.c_drafted.inc(out.drafted_tokens)
            self.c_accepted.inc(out.accepted_tokens)
            self.c_spec_steps.inc()
        if trimmed_prefill:
            self.c_trim_prefill.inc(trimmed_prefill)
        if trimmed_drafts:
            self.c_trim_drafts.inc(trimmed_drafts)
        self.h_step_ms.observe(dur_ms)

        in_use = kv.num_pages - len(kv.free)
        self.g_pool_in_use.set(in_use)
        self.g_pool_free.set(len(kv.free))
        self.g_pool_peak.set_max(in_use)
        self.g_waiting.set(len(sched.waiting))
        self.g_running.set(len(sched.running))
        self.g_table_pages.set(table_pages)
        reclaimable = 0
        if cache is not None:
            self.g_cached_pages.set(cache.cached_pages)
            reclaimable = cache.reclaimable_pages
            self.g_reclaimable.set(reclaimable)
        self.ring.append({
            "step": int(self.c_steps.value()),
            "width": width,
            "table_pages": table_pages,
            "live_rows": out.live_rows,
            "padded_rows": out.padded_rows,
            "prefill_tokens": out.prefill_tokens,
            "decode_tokens": out.decode_tokens,
            "drafted_tokens": out.drafted_tokens,
            "accepted_tokens": out.accepted_tokens,
            "trimmed_prefill_tokens": trimmed_prefill,
            "trimmed_draft_tokens": trimmed_drafts,
            "pool_pages_in_use": in_use,
            "cache_reclaimable_pages": reclaimable,
            "dur_ms": dur_ms,
        })

    def reset_peaks(self) -> None:
        """Re-anchor high-water gauges (bench passes call this)."""
        self.g_pool_peak.set(self.g_pool_in_use.value())

    # ---------------------------------------------------- jax profiler --
    def arm_profiler(self, steps: int, logdir: str) -> None:
        """Opt-in: capture a ``jax.profiler`` trace window around the
        next ``steps`` engine steps, written to ``logdir``."""
        self._profiler = {"left": int(steps), "dir": logdir, "on": False}

    def _profiler_tick(self) -> None:
        p = self._profiler
        if p is None:
            return
        if not p["on"]:
            try:
                import jax
                jax.profiler.start_trace(p["dir"])
                p["on"] = True
            except Exception:
                self._profiler = None
                return
        p["left"] -= 1
        if p["left"] <= 0:
            try:
                import jax
                jax.profiler.stop_trace()
            except Exception:
                pass
            self._profiler = None

    # ------------------------------------------------- summary windows --
    def server_window(self) -> Dict[str, float]:
        """Anchor for a per-server-instance summary window."""
        return {"requests": self.c_stream_requests.value(),
                "tokens": self.c_stream_tokens.value(),
                "ttft_n": self.h_stream_ttft_ms.count(),
                "tpot_n": self.h_stream_tpot_ms.count()}

    def stream_finished(self, submitted_t: float, first_t: Optional[float],
                        end_t: float, emitted: int) -> None:
        """Server-side terminal accounting for one finished stream."""
        if not self.enabled or first_t is None:
            return
        self.c_stream_requests.inc()
        self.c_stream_tokens.inc(emitted)
        self.h_stream_ttft_ms.observe((first_t - submitted_t) * 1e3)
        if emitted > 1:
            self.h_stream_tpot_ms.observe(
                (end_t - first_t) * 1e3 / (emitted - 1))

    def stream_cancelled(self) -> None:
        if not self.enabled:
            return
        self.c_stream_cancelled.inc()

    def server_summary(self, window: Optional[Dict[str, float]],
                       *, steps: int, cancelled: int,
                       span: Tuple[Optional[float], Optional[float]],
                       ) -> Dict[str, float]:
        """The registry view behind ``AsyncLMServer.summary()``."""
        w = window or {"requests": 0, "tokens": 0, "ttft_n": 0, "tpot_n": 0}
        n = int(self.c_stream_requests.value() - w["requests"])
        if n == 0:
            return {"requests": 0, "cancelled": cancelled, "steps": steps}
        t0, t1 = span
        elapsed = (t1 - t0) if (t0 is not None and t1 is not None) else 0.0
        return {
            "requests": n,
            "cancelled": cancelled,
            "steps": steps,
            "req_s": n / elapsed if elapsed > 0 else float("inf"),
            "ttft_ms_p50": self.h_stream_ttft_ms.percentile(
                0.50, skip=int(w["ttft_n"])),
            "ttft_ms_p99": self.h_stream_ttft_ms.percentile(
                0.99, skip=int(w["ttft_n"])),
            "tpot_ms": self.h_stream_tpot_ms.mean(skip=int(w["tpot_n"])),
            "tokens": int(self.c_stream_tokens.value() - w["tokens"]),
        }
