"""Serving benchmarks: engines, decode A/B, prefill TTFT, prefix reuse.

Eight families, all emitted as CSV rows (``benchmarks.run``) *and* as a
machine-readable ``BENCH_serving.json`` so the perf trajectory is tracked
across PRs.  Every EngineCore aggregate — step latency percentiles,
mixed-step counts, prefix hit rates, speculative acceptance, engine and
server TTFT/TPOT — is read back from the engine's own metrics registry
(``repro.serving.metrics``) via snapshot/delta windows; the bench
re-derives nothing the serving stack already counts.

1. **Engine throughput** — slot-contiguous vs the request-level
   ``EngineCore`` in BOTH packings (the PR-3 padded ``(lanes, C)`` block
   and the token-level ragged stream) at the SAME resident-KV budget under
   mixed traffic (a couple of long prompts among many short ones).  The
   slot engine sizes every lane for the longest request; the paged engines
   spend rows page-by-page, so the same budget sustains more concurrent
   lanes — and the ragged arm additionally never pays the mixed-batch
   padding tax (a decode lane costs 1 token-row, not a chunk-wide one).
   Per-step decode latency (p50/p95), peak resident cache rows, mixed
   chunked-prefill+decode step counts and ``padding_efficiency`` (live
   token rows / computed token rows) are recorded; each arm carries its
   ``prefill_mode`` ("contiguous" / "chunked") and ``packing``.

2. **Step breakdown** — the PR-1 gather path vs the in-place paged path at
   equal row budget, one attention layer, same pool/table/occupancy:

   - legacy: gather the contiguous (B, Hkv, W·ps, D) view from the page
     table, attend over it per lane, write the active page back — the
     per-step O(B·H·L·D) copy the in-place kernel deleted;
   - in-place: write each lane's one new KV row at its (page, offset) and
     attend through the table (``kernels/paged_attention``) — no copy.

3. **Prefill TTFT** — time-to-first-token on long prompts, chunked paged
   prefill (``EngineCore``: fixed-shape chunks streamed straight into
   pages) vs the PR-2 *scatter* path (b=1 contiguous prefill jitted per
   prompt length, then scattered into pages — reconstructed here inline as
   the baseline), at equal page budget.  Measured over a stream of
   *distinct* prompt lengths — the serving-realistic case, where the
   scatter path pays a fresh XLA compile per length while chunking's
   static shapes stay warm — and once more at a repeated (warm) length.
   Each arm is tagged ``prefill_mode: chunked|scatter``.

4. **Speculative decoding** — draft-then-verify A/B, spec engine (n-gram
   proposer, k=4) vs an identical non-spec engine, three arms.
   *Repetitive*: N identical greedy requests — once the first stream
   finishes, the proposer's history replays it and the verify accepts
   nearly every draft, so each drafting step commits several tokens
   (nightly CI asserts ``accepted_per_spec_step > 1.5``).
   *Adversarial*: lookup-hostile traffic — the proposer issues no drafts
   and speculation must not cost throughput (CI asserts the spec/non-spec
   tok/s ratio ≥ 0.8).  *Rejection*: a maximally wrong proposer — every
   draft verified and rolled back, the worst-case cost bound (recorded,
   no floor).  Acceptance rate, accepted-tokens-per-drafting-step and
   tok/s are recorded per arm; every arm drains until a pass compiles
   nothing new, so the reported numbers are a warm server's.

5. **Prefix reuse** — the shared-system-prompt workload: N requests open
   with the same page-aligned prefix and differ only in their tails.  The
   first request prefills cold and publishes its full pages into the radix
   prefix cache; every later admission is granted those resident pages and
   streams only its tail.  Measured at equal memory on one engine: cold
   TTFT (the first shared-prefix request, compile-warm) vs warm TTFT (the
   rest), with exact `prefix_hit_rate` (hit tokens / known tokens over the
   warm phase — deterministic, not a timing), `pages_shared` grants and
   CoW-copy counts from the cache's own telemetry.  The nightly CI job
   asserts `prefix_hit_rate ≥ 0.9` and warm-over-cold TTFT speedup > 1.

6. **Serve loop** — the async front door (PR 8) vs the batch driver on
   the SAME warm engine.  The batch arm submits everything at t=0 and
   steps to drain — its TTFT tail is the admission queue.  The stream arm
   replays the same traffic through :class:`AsyncLMServer` under Poisson
   arrivals whose rate is *self-calibrated* to 70% of what the batch arm
   just sustained (the classic sustained-utilization point — offering
   100% is a knife edge where backlog, not the server, sets TTFT),
   measuring per-client TTFT/TPOT from each request's own arrival.  Nightly CI asserts the
   streaming TTFT p50 ≤ the batch driver's (spreading arrivals over the
   window the engine needs anyway must not cost first-token latency).

7. **Observability overhead** — metrics-on vs metrics-off engines on
   identical mixed traffic.  The registry/tracing layer is host-side
   python on the step boundary, so it must cost ~nothing next to a
   jitted step; nightly CI asserts the on/off tok/s ratio ≥ 0.98.  The
   serve-loop family additionally arms the **retrace sentinel**
   (``mark_warm`` + one measured pass per arm) and records
   ``retraces_after_warm`` — nightly CI pins it at 0, so a mid-traffic
   jit recompile (the PR 8 table-width-shrink class of bug) fails the
   build instead of silently costing a ~2 s stall.

8. **Sharded serving** — the tensor-parallel engine (PR 9): identical
   mixed traffic served at mesh 1 vs mesh 2, tok/s plus the analytic
   per-token / per-step all-gather bytes at each width.  The backend pins
   its device count at first jax init (1 on CPU), so this arm runs in a
   subprocess with two forced host devices, exactly like
   ``tests/_multidevice.py``.  The tok/s ratio is recorded with **no CPU
   floor**: two placeholder devices share the same cores, so the CPU
   number measures shard_map + collective overhead, not the speedup a
   real 2-chip mesh sees (the collective-bytes column is the
   device-independent signal).

CPU numbers are relative A/B signals, not TPU claims (docs/benchmarks.md).
"""
from __future__ import annotations

import argparse
import json
import os
import time
from typing import Any, Dict, Iterator, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Row = Tuple[str, float, str]

_JSON_DEFAULT = os.environ.get("BENCH_SERVING_JSON", "BENCH_serving.json")


# --------------------------------------------------------------- utilities --

def _time_ms(fn, *args, iters: int = 10) -> float:
    """Best-of-N wall-clock ms of ``fn(*args)`` after a compile warm-up
    (min, not median: these shapes run multi-threaded and the best sample
    is the least contended one)."""
    jax.block_until_ready(fn(*args))
    samples = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        samples.append((time.perf_counter() - t0) * 1e3)
    return float(np.min(samples))


def _time_state_ms(fn, state, iters: int = 10) -> Tuple[float, Any]:
    """Best-of-N ms of a donating state → state step, chained like a real
    decode loop (donation keeps pool updates in place where the backend
    supports aliasing; XLA:CPU copies regardless — both write paths pay
    that copy equally, see the JSON note)."""
    state = fn(*state)                      # compile + warm
    jax.block_until_ready(state)
    samples = []
    for _ in range(iters):
        t0 = time.perf_counter()
        state = fn(*state)
        jax.block_until_ready(state)
        samples.append((time.perf_counter() - t0) * 1e3)
    return float(np.min(samples)), state


def _pct(xs: List[float], q: float) -> float:
    return float(np.percentile(np.asarray(xs), q)) if xs else 0.0


# ------------------------------------------------------- engine throughput --

def _mixed_requests(vocab: int, tiny: bool, seed: int = 7):
    """Many short requests + two long-prompt ones.

    The long prompts (not long generations) force the slot engine's
    ``max_len`` up — every lane reserves the worst case so such requests can
    land anywhere — while the paged engine spends only the pages the long
    sequence actually needs, only while it is resident.
    """
    from repro.serving import Request
    rng = np.random.default_rng(seed)
    if tiny:
        prompts: List[int] = [4 + (i % 3) * 2 for i in range(10)] + [48]
    else:
        prompts = [4 + (i % 3) * 2 for i in range(48)] + [384, 384]
    return [Request(uid=i, prompt=rng.integers(0, vocab, lp
                                               ).astype(np.int32), max_new=8)
            for i, lp in enumerate(prompts)]


def _instrumented_drain(engine, requests, rows_in_use,
                        core: bool = False) -> Dict[str, Any]:
    """Drain traffic and report per-pass aggregates.

    ``core=True``: the engine is an EngineCore and every aggregate —
    step-latency percentiles, mixed-step counts, live/padded rows, peak
    pool pages — is read back from the engine's own metrics registry
    (``snapshot()``/``delta()`` windows over the lifetime counters plus a
    count-offset window over the ``step_latency_ms`` histogram), not
    recomputed bench-side.  ``rows_in_use`` is only sampled for the slot
    engine, which carries no registry."""
    for r in requests:
        engine.submit(r)
    if core:
        obs = engine.obs
        snap = obs.registry.snapshot()
        step_n0 = obs.h_step_ms.count()
        obs.reset_peaks()
    lat: List[float] = []
    peak_rows = 0
    steps = 0

    def busy():
        if core:
            return engine.scheduler.has_work()
        return engine.queue or any(a is not None for a in engine.active)

    t0 = time.perf_counter()
    while busy():
        if core:
            engine.step()
        else:
            s0 = time.perf_counter()
            engine.step()
            lat.append((time.perf_counter() - s0) * 1e3)
            peak_rows = max(peak_rows, rows_in_use(engine))
        steps += 1
        if steps > 10_000:
            raise RuntimeError("serving did not drain")
    dt = time.perf_counter() - t0
    toks = sum(len(r.tokens) for r in engine.finished)
    engine.finished.clear()             # engine is reused across passes
    if not core:
        return {"tok_s": toks / dt, "tokens": toks, "steps": steps,
                "step_ms_p50": _pct(lat, 50), "step_ms_p95": _pct(lat, 95),
                "peak_cache_rows": int(peak_rows)}
    d = obs.registry.delta(snap)
    live, padded = int(d["live_rows_total"]), int(d["padded_rows_total"])
    return {"tok_s": toks / dt, "tokens": toks,
            "steps": int(d["steps_total"]),
            "step_ms_p50": obs.h_step_ms.percentile(0.50, skip=step_n0),
            "step_ms_p95": obs.h_step_ms.percentile(0.95, skip=step_n0),
            "peak_cache_rows":
                int(obs.g_pool_peak.value() * engine.kv.page_size),
            "mixed_steps": int(d["mixed_steps_total"]),
            "prefill_tokens": int(d["prefill_tokens_total"]),
            "decode_tokens": int(d["decode_tokens_total"]),
            "live_rows": live, "padded_rows": padded,
            "padding_efficiency": live / max(padded, 1)}


def _engine_results(tiny: bool) -> Dict[str, Any]:
    from repro.configs import get_config
    from repro.models import build_model
    from repro.serving import EngineCore, ServingEngine

    page = 8 if tiny else 16
    max_len = 128 if tiny else 1024          # serving SLA: longest request
    budget_rows = (2 if tiny else 4) * max_len    # resident-KV budget
    cfg = get_config("deepseek-7b-smoke")
    params = build_model(cfg).init(jax.random.PRNGKey(0))

    slot_lanes = budget_rows // max_len
    paged_lanes = 4 if tiny else 16          # page pool spreads wider
    num_pages = budget_rows // page

    # Engines are REUSED across passes: early passes warm the jit caches
    # (per-width step buckets — and, for the slot engine, per-length prefill
    # buckets), the last pass is the steady state a long-running server
    # actually sees.
    slot_eng = ServingEngine(cfg, params, slots=slot_lanes, max_len=max_len)
    pad_eng = EngineCore(cfg, params, lanes=paged_lanes, page_size=page,
                         num_pages=num_pages, max_len=max_len,
                         chunk_size=2 * page, mode="padded")
    rag_eng = EngineCore(cfg, params, lanes=paged_lanes, page_size=page,
                         num_pages=num_pages, max_len=max_len,
                         chunk_size=2 * page, mode="ragged")
    for _ in range(2 if tiny else 3):
        slot = _instrumented_drain(
            slot_eng, _mixed_requests(cfg.vocab_size, tiny),
            lambda e: e.slots * e.max_len)
        padded = _instrumented_drain(
            pad_eng, _mixed_requests(cfg.vocab_size, tiny),
            lambda e: e.pages_in_use * e.kv.page_size, core=True)
        ragged = _instrumented_drain(
            rag_eng, _mixed_requests(cfg.vocab_size, tiny),
            lambda e: e.pages_in_use * e.kv.page_size, core=True)

    slot["lanes"] = slot_lanes
    padded["lanes"] = ragged["lanes"] = paged_lanes
    slot["prefill_mode"] = "contiguous"
    padded["prefill_mode"] = ragged["prefill_mode"] = "chunked"
    slot["packing"], padded["packing"] = "slots", "padded"
    ragged["packing"] = "ragged"
    # Resolved varlen-kernel block shapes (block_q / block_pages / source:
    # tuned|default) — recorded so a bench regression is attributable to
    # the kernel config that produced the number, not just the packing.
    ragged["kernel_config"] = rag_eng.kernel_config.describe()
    return {"budget_rows": budget_rows, "page_size": page,
            "num_pages": num_pages, "max_len": max_len,
            "token_buckets": list(rag_eng.scheduler.token_buckets),
            "slot": slot, "padded": padded, "ragged": ragged,
            "speedup": ragged["tok_s"] / slot["tok_s"],
            "speedup_padded": padded["tok_s"] / slot["tok_s"],
            "speedup_ragged_vs_padded": ragged["tok_s"] / padded["tok_s"]}


# --------------------------------------------------------- step breakdown --

def _breakdown_results(tiny: bool) -> Dict[str, Any]:
    """Gather-path vs in-place decode step at equal row budget (1 layer)."""
    from repro.core.streaming_attention import naive_attention
    from repro.kernels.paged_attention import paged_attention

    if tiny:
        b, hq, hkv, d, ps, w = 2, 4, 2, 32, 8, 4
    else:
        # Memory-bound regime (the serving-relevant one): the gathered
        # (B, Hkv, W·ps, D) views are ~17 MB per pool — far beyond cache —
        # so the legacy copy costs real bandwidth every step.
        b, hq, hkv, d, ps, w = 32, 8, 2, 128, 16, 64
    n = b * w + 1                            # every lane fully grown
    rng = np.random.default_rng(0)
    kp = jnp.asarray(rng.normal(size=(n, hkv, ps, d)), jnp.bfloat16)
    vp = jnp.asarray(rng.normal(size=(n, hkv, ps, d)), jnp.bfloat16)
    q = jnp.asarray(rng.normal(size=(b, hq, 1, d)).astype(np.float32))
    newk = jnp.asarray(rng.normal(size=(b, hkv, d)), jnp.bfloat16)
    newv = jnp.asarray(rng.normal(size=(b, hkv, d)), jnp.bfloat16)
    tbl = jnp.asarray(
        np.stack([rng.permutation(n - 1)[:w] for _ in range(b)]), jnp.int32)
    idxs = jnp.asarray(rng.integers(ps * (w - 1), ps * w, size=b), jnp.int32)

    def gather(pool):
        out = jnp.moveaxis(jnp.take(pool, tbl, axis=0), 1, 2)
        s = out.shape
        return out.reshape(s[0], s[1], s[2] * s[3], *s[4:])

    def writeback_page(pool, view):          # one whole page per lane
        page_no = idxs // ps
        page_ids = jnp.take_along_axis(tbl, page_no[:, None], 1)[:, 0]
        rows = page_no[:, None] * ps + jnp.arange(ps)[None, :]
        page = jnp.take_along_axis(
            view, rows[:, None, :, None], axis=2).astype(pool.dtype)
        return pool.at[page_ids].set(jnp.moveaxis(page, 1, 2)
                                     .reshape(b, ps, hkv, d)
                                     .transpose(0, 2, 1, 3))

    def write_row(kp, vp):                   # one row per lane
        page_ids = jnp.take_along_axis(tbl, (idxs // ps)[:, None], 1)[:, 0]
        off = idxs % ps
        return (kp.at[page_ids, :, off].set(newk.astype(kp.dtype)),
                vp.at[page_ids, :, off].set(newv.astype(vp.dtype)))

    def attend_view(kg, vg):                 # per-lane view attention (PR 1)
        return jax.vmap(
            lambda qb, kb, vb, i: naive_attention(
                qb[None], kb[None], vb[None], causal=True,
                q_offset=i, kv_len=i + 1)[0])(q, kg, vg, idxs)

    # Attention paths, each jitted whole so XLA fuses what it can — the
    # legacy arm is PR 1's real dataflow (gather feeding the view attend).
    legacy_gather = jax.jit(lambda kp, vp: (gather(kp), gather(vp)))
    legacy_attend_path = jax.jit(
        lambda kp, vp: attend_view(gather(kp), gather(vp)))
    inplace_attend_path = jax.jit(
        lambda kp, vp: paged_attention(q, kp, vp, tbl, idxs + 1))

    # Pool writers: donated + chained like the engine's decode loop.  The
    # legacy arm writes BOTH pools' active page (PR 1's scatter_active_page
    # covered every cache leaf), matching the in-place arm's k+v row writes.
    j_writeback = jax.jit(
        lambda kp, vp, kg, vg: (writeback_page(kp, kg),
                                writeback_page(vp, vg)),
        donate_argnums=(0, 1))
    j_write_row = jax.jit(write_row, donate_argnums=(0, 1))

    kg, vg = legacy_gather(kp, vp)
    iters = 5 if tiny else 30
    out = {
        "shape": {"lanes": b, "heads_q": hq, "heads_kv": hkv, "d_head": d,
                  "page_size": ps, "pages_per_lane": w,
                  "rows_per_lane": ps * w},
        "note": "write paths both pay a full pool copy on XLA:CPU (no "
                "scatter aliasing there even under donation); on TPU the "
                "row write is strictly less traffic than the page "
                "write-back.  The attend path is the PR's hot-path delta.",
        # pure reads first — the donating chain below consumes the pools
        "legacy_gather_ms": _time_ms(legacy_gather, kp, vp, iters=iters),
        "legacy_attend_path_ms": _time_ms(legacy_attend_path, kp, vp,
                                          iters=iters),
        "attend_in_place_ms": _time_ms(inplace_attend_path, kp, vp,
                                       iters=iters),
    }
    wb_ms, (kp, vp) = _time_state_ms(
        lambda kp_, vp_: j_writeback(kp_, vp_, kg, vg), (kp, vp),
        iters=iters)
    row_ms, _ = _time_state_ms(j_write_row, (kp, vp), iters=iters)
    out.update(
        legacy_writeback_page_ms=wb_ms, write_row_ms=row_ms,
        attend_speedup=out["legacy_attend_path_ms"]
        / out["attend_in_place_ms"],
        step_speedup=(out["legacy_attend_path_ms"] + wb_ms)
        / (out["attend_in_place_ms"] + row_ms))
    return out


# ------------------------------------------------------------ prefill TTFT --

def _scatter_prefill_arm(cfg, params, lens, num_pages, page) -> List[float]:
    """The PR-2 prefill dataflow, reconstructed as the baseline: b=1
    contiguous prefill (jitted per prompt length) then a scatter of the
    contiguous cache into pages — the ``write_prefill`` copy the chunked
    path deleted.  → TTFT ms per prompt."""
    from repro.models import build_model
    from repro.serving.core import greedy_token
    from repro.serving.paged import PagedKVCache

    model = build_model(cfg)
    kv = PagedKVCache(model, num_pages, page)

    def write(pool, caches1, ids):
        n = ids.shape[0]

        def wr(pl, one, ax, lax):
            s = one.shape
            one = one.reshape(s[:lax] + (n, page) + s[lax + 1:])
            one = jnp.squeeze(one, ax)
            one = jnp.moveaxis(one, lax - 1, ax)
            return pl.at[(slice(None),) * ax + (ids,)].set(
                one.astype(pl.dtype))

        return jax.tree.map(wr, pool, caches1, kv.axes, kv.laxes)

    scatter = jax.jit(write, donate_argnums=(0,))
    prefill = jax.jit(
        lambda p, t, c: model.prefill(p, {"tokens": t}, c))

    rng = np.random.default_rng(0)
    ttft = []
    for lp in lens:
        prompt = rng.integers(0, cfg.vocab_size, lp).astype(np.int32)
        n0 = kv.pages_needed(lp)
        pages = jnp.arange(n0, dtype=jnp.int32)
        t0 = time.perf_counter()
        fresh = model.init_cache(1, n0 * page)
        logits, c1 = prefill(params, jnp.asarray(prompt)[None], fresh)
        kv.pool = scatter(kv.pool, c1, pages)
        tok = greedy_token(logits[0])
        jax.block_until_ready(kv.pool)
        del tok
        ttft.append((time.perf_counter() - t0) * 1e3)
    return ttft


def _chunked_prefill_arm(cfg, params, lens, num_pages, page,
                         chunk) -> List[float]:
    """Chunked paged prefill through EngineCore at the same page budget:
    submit → step until the first token lands.  → TTFT ms per prompt."""
    from repro.serving import EngineCore, Request

    eng = EngineCore(cfg, params, lanes=1, page_size=page,
                     num_pages=num_pages, chunk_size=chunk,
                     max_len=num_pages * page)
    rng = np.random.default_rng(0)
    ttft = []
    for i, lp in enumerate(lens):
        prompt = rng.integers(0, cfg.vocab_size, lp).astype(np.int32)
        t0 = time.perf_counter()
        eng.submit(Request(uid=i, prompt=prompt, max_new=1))
        while eng.scheduler.has_work():
            out = eng.step()
            if out.tokens:
                break
        ttft.append((time.perf_counter() - t0) * 1e3)
        eng.run()                         # drain the tail, free the pages
        eng.finished.clear()
    return ttft


def _prefill_results(tiny: bool) -> Dict[str, Any]:
    """TTFT on long prompts: chunked vs scatter at equal page budget.

    ``distinct``: a stream of all-different prompt lengths — the scatter
    path re-jits its b=1 prefill for every length, the chunked path reuses
    its small static bucket set.  Both arms first serve a *warm-up* stream
    of lengths disjoint from the measured ones: that covers the chunked
    arm's one-time (bucket × table-width) compile keys — a bounded set a
    long-running server crosses once — while leaving the scatter arm's
    pathology untouched (its compiles are per *length*, and the warm-up
    lengths are all different from the measured ones).  ``warm``: the same
    length twice, keeping only the second (steady-state compute, compile
    excluded).
    """
    from repro.configs import get_config
    from repro.models import build_model

    page = 8 if tiny else 16
    chunk = 4 * page          # prefill-only lanes: bigger chunks, no padding
    if tiny:
        lens = [40, 44, 52, 60]
    else:
        lens = [384, 400, 432, 464, 496]
    cfg = get_config("deepseek-7b-smoke")
    params = build_model(cfg).init(jax.random.PRNGKey(0))
    num_pages = -(-max(lens) // page) + 2     # equal budget for both arms
    # One chunk shorter than each measured length (same final-chunk
    # remainder — the ragged bucket key — never the same length) plus one
    # near-max length that reaches the widest table bucket.  At bench
    # scale this covers the chunked arm's compile keys exactly; at --tiny
    # the short warm prompts cannot reach every (bucket × width) combo, so
    # tiny distinct medians retain some compile cost (tiny CI is
    # crash-only; cross-PR TTFT comparisons should use the full run).
    warm_lens = sorted({w for w in
                        [lp - chunk for lp in lens] + [max(lens) - 1]
                        if w >= 1 and w not in set(lens)})

    arms = {}
    for mode, fn in (("scatter", lambda ls: _scatter_prefill_arm(
                          cfg, params, ls, num_pages, page)),
                     ("chunked", lambda ls: _chunked_prefill_arm(
                          cfg, params, ls, num_pages, page, chunk))):
        distinct = fn(warm_lens + lens)[len(warm_lens):]
        warm = min(fn([lens[0]] * 4)[1:])     # best-of-3 after compile
        arms[mode] = {"prefill_mode": mode,
                      "warmup_lens": warm_lens,
                      "ttft_ms_distinct": distinct,
                      "ttft_ms_distinct_median": _pct(distinct, 50),
                      "ttft_ms_warm": warm}
    return {"page_size": page, "chunk_size": chunk, "num_pages": num_pages,
            "prompt_lens": lens,
            "scatter": arms["scatter"], "chunked": arms["chunked"],
            "ttft_speedup_distinct":
                arms["scatter"]["ttft_ms_distinct_median"]
                / arms["chunked"]["ttft_ms_distinct_median"],
            "ttft_speedup_warm": arms["scatter"]["ttft_ms_warm"]
                / arms["chunked"]["ttft_ms_warm"]}


# ------------------------------------------------------ speculative decode --

def _spec_traffic(vocab: int, tiny: bool, repetitive: bool, seed: int = 5):
    """Traffic for the draft-then-verify A/B.

    ``repetitive``: N *identical* greedy requests.  Greedy decoding is
    deterministic, so every request regenerates the same stream; after the
    first finishes, the n-gram proposer's history ring replays it and the
    verify accepts nearly every draft — the lookup-friendly best case
    (agentic retries, self-consistency sampling, templated output).

    ``repetitive=False``: all-distinct random prompts, used by the
    adversarial/rejection arms below.
    """
    from repro.serving import Request
    rng = np.random.default_rng(seed)
    n = 6 if tiny else 16
    lp = 12 if tiny else 48
    max_new = 16 if tiny else 32
    if repetitive:
        base = rng.integers(0, vocab, lp).astype(np.int32)
        prompts = [base.copy() for _ in range(n)]
    else:
        prompts = [rng.integers(0, vocab, lp).astype(np.int32)
                   for _ in range(n)]
    return [Request(uid=i, prompt=p, max_new=max_new)
            for i, p in enumerate(prompts)]


def _no_drafts(stream, k):
    """The n-gram proposer's behaviour on genuinely lookup-hostile traffic:
    no trailing n-gram ever recurs, so it returns no drafts.  Modelled
    explicitly because the *random-weight* smoke model's greedy streams
    settle into short token loops, which would make any real n-gram
    matcher fire on any traffic — a real tokenizer+model stays quiet here.
    """
    return []


class _JunkProposer:
    """Rejection worst case: always drafts k uniform-random tokens, so
    acceptance is ~1/vocab per draft — the engine pays the full 1+k verify
    stream and commits ~1 token.  Bounds the cost of a maximally wrong
    proposer (recorded for trajectory; no CI floor — CPU steps are
    compute-bound, so extra verify rows cost linearly here, unlike the
    bandwidth-bound accelerator regime the feature targets)."""

    def __init__(self, vocab: int, seed: int = 9):
        self.vocab = vocab
        self.rng = np.random.default_rng(seed)

    def __call__(self, stream, k):
        return [int(t) for t in self.rng.integers(0, self.vocab, k)]


def _spec_drain(eng, requests) -> Dict[str, Any]:
    """Drain one pass and attach the pass's speculative deltas — a
    registry window (``snapshot``/``delta``), not bench-side diffing of
    engine attributes."""
    since = eng.obs.registry.snapshot()
    res = _instrumented_drain(
        eng, requests, lambda e: e.pages_in_use * e.kv.page_size, core=True)
    d = eng.obs.registry.delta(since)
    drafted = d.get("spec_drafted_tokens_total", 0)
    accepted = d.get("spec_accepted_tokens_total", 0)
    spec_steps = d.get("spec_steps_total", 0)
    res.update(drafted_tokens=int(drafted), accepted_tokens=int(accepted),
               spec_steps=int(spec_steps),
               acceptance=accepted / drafted if drafted else 0.0,
               accepted_per_spec_step=(accepted / spec_steps
                                       if spec_steps else 0.0))
    return res


def _speculative_results(tiny: bool) -> Dict[str, Any]:
    """Spec vs non-spec engine at equal lanes/pages, three arms:

    - ``repetitive`` — identical requests through the n-gram proposer with
      history: near-total acceptance, several tokens per drafting step
      (CI floor ``accepted_per_spec_step > 1.5``);
    - ``adversarial`` — lookup-hostile traffic, proposer never matches so
      no drafts are issued: speculation must cost ~nothing
      (CI floor tok/s ratio ≥ 0.8);
    - ``rejection`` — a maximally wrong proposer, every draft verified and
      thrown away: the worst-case cost bound (recorded, no floor).

    Engines are reused across passes — early passes warm the jit caches
    and (repetitive arm) seed the proposer's history with the finished
    streams — and each arm keeps draining until a pass compiles nothing
    new (``trace_count`` stable), so the reported pass is a warm server,
    never an XLA-compile measurement.  The non-spec baseline serves the
    *same* traffic, so the tok/s ratio isolates the draft/verify
    machinery itself.
    """
    from repro.configs import get_config
    from repro.models import build_model
    from repro.serving import EngineCore, NGramProposer

    page = 8 if tiny else 16
    lanes = 2 if tiny else 4
    spec_k = 4
    chunk = 2 * page
    cfg = get_config("deepseek-7b-smoke")
    params = build_model(cfg).init(jax.random.PRNGKey(0))
    lp, max_new = (12, 16) if tiny else (48, 32)
    req_rows = lp + max_new + spec_k
    num_pages = lanes * -(-req_rows // page) + 4

    def engine(proposer: Any) -> Any:
        kw = {} if proposer is None else dict(
            speculative=True, spec_k=spec_k, proposer=proposer)
        return EngineCore(cfg, params, lanes=lanes, page_size=page,
                          num_pages=num_pages, chunk_size=chunk,
                          max_len=num_pages * page, mode="ragged", **kw)

    arm_defs = (
        ("repetitive", True,
         lambda: NGramProposer(max_ngram=3, history=8)),
        ("adversarial", False, lambda: _no_drafts),
        ("rejection", False, lambda: _JunkProposer(cfg.vocab_size)),
    )
    arms: Dict[str, Any] = {}
    for name, repetitive, mk in arm_defs:
        eng_s, eng_b = engine(mk()), engine(None)
        for _ in range(6):
            t0, b0 = eng_s.trace_count, eng_b.trace_count
            spec = _spec_drain(eng_s, _spec_traffic(cfg.vocab_size, tiny,
                                                    repetitive))
            base = _spec_drain(eng_b, _spec_traffic(cfg.vocab_size, tiny,
                                                    repetitive))
            if eng_s.trace_count == t0 and eng_b.trace_count == b0:
                break
        arms[name] = {"spec": spec, "baseline": base,
                      "tok_s_ratio": spec["tok_s"] / base["tok_s"],
                      "accepted_per_spec_step":
                          spec["accepted_per_spec_step"],
                      "acceptance": spec["acceptance"]}
    return {"page_size": page, "lanes": lanes, "spec_k": spec_k,
            "num_pages": num_pages, "max_new": max_new,
            "proposer": "ngram(max_ngram=3, history=8)",
            # All engines in this section resolve the same per-(model,
            # platform) kernel config; recorded once for attributability.
            "kernel_config": eng_s.kernel_config.describe(),
            "repetitive": arms["repetitive"],
            "adversarial": arms["adversarial"],
            "rejection": arms["rejection"]}


# ------------------------------------------------------------ prefix reuse --

def _prefix_reuse_results(tiny: bool) -> Dict[str, Any]:
    """Shared-system-prompt TTFT: cold prefill vs radix-cache hits.

    One engine, equal memory, the production-redundant stream: every
    request opens with the same S-token page-aligned prefix (S multiple of
    page_size, so hits are whole shared pages and no CoW lands on this
    path) plus a short distinct tail.  A disjoint-prefix warm-up request
    retires the one-time step compiles first, so the cold arm measures
    compute, not XLA; the cache is on throughout, making cold-vs-warm a
    pure reuse delta.  ``prefix_hit_rate`` is the *deterministic* fraction
    of warm-phase known tokens served from resident pages (S / (S+tail) by
    construction) — CI asserts it ≥ 0.9; the TTFT speedup is the wall-clock
    claim (> 1: a warm request streams ~tail tokens instead of S+tail).
    """
    from repro.configs import get_config
    from repro.models import build_model
    from repro.serving import EngineCore, Request

    page = 8 if tiny else 16
    shared_len = (6 if tiny else 16) * page       # 48 / 256 tokens
    tail_len = 4 if tiny else 16                  # hit_rate 0.923 / 0.941
    n_warm = 5 if tiny else 10
    chunk = 2 * page
    max_new = 4
    cfg = get_config("deepseek-7b-smoke")
    params = build_model(cfg).init(jax.random.PRNGKey(0))
    need = shared_len + tail_len + max_new
    num_pages = 2 * -(-need // page) + 4          # requests + resident cache

    eng = EngineCore(cfg, params, lanes=2, page_size=page,
                     num_pages=num_pages, chunk_size=chunk,
                     max_len=num_pages * page, prefix_cache=True)
    rng = np.random.default_rng(3)
    shared = rng.integers(0, cfg.vocab_size, shared_len).astype(np.int32)

    def ttft(uid, prompt):
        t0 = time.perf_counter()
        eng.submit(Request(uid=uid, prompt=prompt, max_new=max_new))
        while eng.scheduler.has_work():
            if eng.step().tokens:
                break
        ms = (time.perf_counter() - t0) * 1e3
        eng.run()                                 # drain tail, publish pages
        eng.finished.clear()
        return ms

    def prompt_for(uid):                          # distinct first tail token
        tail = rng.integers(0, cfg.vocab_size, tail_len).astype(np.int32)
        tail[0] = uid % cfg.vocab_size
        return np.concatenate([shared, tail])

    # compile warm-up on a *disjoint* prefix: same lengths, zero reuse
    ttft(10_000, rng.integers(0, cfg.vocab_size,
                              shared_len + tail_len).astype(np.int32))
    cold_ms = ttft(0, prompt_for(0))              # first sharer: cache miss
    r = eng.obs.registry
    snap = r.snapshot()                           # warm-phase window anchor
    warm_ms = [ttft(uid, prompt_for(uid)) for uid in range(1, 1 + n_warm)]
    # Every reuse aggregate comes straight from the metrics registry: the
    # hit rate is a counter ratio over the warm-phase window, the page
    # telemetry the lifetime counters/gauges the cache itself maintains.
    hit_rate = r.ratio("prefix_hit_tokens_total",
                       "prefix_lookup_tokens_total", since=snap)
    hit_toks = r.delta(snap)["prefix_hit_tokens_total"]

    return {"page_size": page, "chunk_size": chunk, "num_pages": num_pages,
            "kernel_config": eng.kernel_config.describe(),
            "shared_prefix_tokens": int(shared_len),
            "tail_tokens": int(tail_len), "warm_requests": n_warm,
            "cold_ttft_ms": cold_ms, "warm_ttft_ms": warm_ms,
            "warm_ttft_ms_median": _pct(warm_ms, 50),
            "ttft_speedup_warm_vs_cold": cold_ms / _pct(warm_ms, 50),
            "prefix_hit_rate": hit_rate,
            "prefix_hit_tokens": int(hit_toks),
            "pages_shared": int(r.value("prefix_shared_page_grants_total")),
            "cached_pages": int(r.value("prefix_cached_pages")),
            "cow_copies": int(r.value("cow_copies_total")),
            "evicted_pages": int(r.value("prefix_evicted_pages_total"))}


# --------------------------------------------------------------- serve loop --

def _serve_traffic(vocab: int, n: int, max_new: int, seed: int):
    from repro.serving import Request
    rng = np.random.default_rng(seed)
    return [Request(uid=i, max_new=max_new,
                    prompt=rng.integers(0, vocab, int(rng.integers(4, 24))
                                        ).astype(np.int32))
            for i in range(n)]


def _serve_loop_results(tiny: bool) -> Dict[str, Any]:
    """Async streaming front door vs the batch driver, one warm engine.

    Arm 1 (``batch``) is today's driver: submit all N requests at t=0,
    step until drained, record each request's first-token time — late
    admissions pay the whole queue in their TTFT.  Arm 2 (``stream``)
    serves the identical traffic through :class:`AsyncLMServer` with
    Poisson inter-arrivals at 70% of N / batch_elapsed — the throughput
    the engine just proved on this traffic, derated to the classic
    sustained-utilization point so the stream arm is offered a load it
    can actually absorb (at 100% any serving overhead compounds into an
    unbounded backlog and TTFT measures the queue, not the server).
    Both arms run after a full warm-up drain (compile keys retired); the
    deltas are serving policy, not XLA.
    """
    import asyncio

    from repro.configs import get_config
    from repro.models import build_model
    from repro.serving import AsyncLMServer, EngineCore

    # n >> lanes and long-ish generations: the batch arm's *median* request
    # must actually sit in the admission queue, else both arms just measure
    # prefill and the comparison is noise.
    page = 8 if tiny else 16
    lanes = 2 if tiny else 4
    n = 12 if tiny else 32
    max_new = 16 if tiny else 32
    cfg = get_config("deepseek-7b-smoke")
    params = build_model(cfg).init(jax.random.PRNGKey(0))
    need = 24 + max_new
    num_pages = lanes * -(-need // page) + 4
    eng = EngineCore(cfg, params, lanes=lanes, page_size=page,
                     num_pages=num_pages, chunk_size=2 * page,
                     max_len=num_pages * page, mode="ragged")

    def drain(requests):
        for r in requests:
            eng.submit(r)
        while eng.scheduler.has_work():
            eng.step()
        eng.finished.clear()

    async def client(server, req, delay):
        await asyncio.sleep(delay)
        async for _ in server.generate(req):
            pass

    def stream_pass(seed: int, rate: float) -> Dict[str, Any]:
        arrivals = np.cumsum(
            np.random.default_rng(seed + 1).exponential(1.0 / rate, n))

        async def serve():
            async with AsyncLMServer(eng, max_waiting=n) as server:
                await asyncio.gather(*[
                    client(server, r, d) for r, d in
                    zip(_serve_traffic(cfg.vocab_size, n, max_new, seed),
                        arrivals)])
            return server.summary()

        summary = asyncio.run(serve())
        eng.finished.clear()
        return summary

    def batch_pass(seed: int) -> Tuple[Dict[str, Any], float]:
        """Submit-all-then-drain; TTFT/TPOT are the engine-side
        ``request_ttft_ms`` / ``request_tpot_ms`` histograms (windowed by
        observation count), not re-derived from per-step polling."""
        reqs = _serve_traffic(cfg.vocab_size, n, max_new, seed)
        ttft, tpot = eng.obs.h_ttft_ms, eng.obs.h_tpot_ms
        ttft_n, tpot_n = ttft.count(), tpot.count()
        t0 = time.perf_counter()
        for r in reqs:
            eng.submit(r)
        steps = 0
        while eng.scheduler.has_work():
            eng.step()
            steps += 1
        elapsed = time.perf_counter() - t0
        eng.finished.clear()
        res = {"req_s": n / elapsed, "steps": steps,
               "ttft_ms_p50": ttft.percentile(0.50, skip=ttft_n),
               "ttft_ms_p99": ttft.percentile(0.99, skip=ttft_n),
               "tpot_ms": tpot.mean(skip=tpot_n)}
        return res, elapsed

    drain(_serve_traffic(cfg.vocab_size, n, max_new, seed=0))   # warm jits

    # Both arms repeat until a pass compiles nothing new (the speculative
    # family's convention): seed-1 prompt lengths and staggered arrivals
    # each reach ragged bucket widths the warm drain never does, and an
    # XLA stall in either arm would corrupt the TTFT comparison.
    for _ in range(6):
        c0 = eng.trace_count
        batch, elapsed = batch_pass(seed=1)
        if eng.trace_count == c0:
            break

    # --- stream arm: same engine, Poisson arrivals at 70% of the proven
    # drain rate.  Offering exactly 100% is a knife edge — any per-step
    # serving overhead makes the queue grow without bound over the trace
    # and every client's TTFT becomes the backlog, not the server.  0.7
    # is the classic "sustained utilization" operating point.
    rate = 0.7 * n / elapsed
    for _ in range(6):
        c0 = eng.trace_count
        stream = stream_pass(seed=1, rate=rate)
        if eng.trace_count == c0:
            break

    # --- retrace sentinel: both arms just proved trace-stable, so arm the
    # registry's retrace counter and run one final *measured* pass per
    # arm.  Any jit trace from here is a shape-stability regression (the
    # PR 8 table-width-shrink class of bug); nightly CI pins this at 0.
    eng.obs.mark_warm()
    batch, _ = batch_pass(seed=1)
    stream = stream_pass(seed=1, rate=rate)
    retraces = int(eng.obs.registry.value("step_retraces_total"))
    return {"page_size": page, "lanes": lanes, "requests": n,
            "max_new": max_new, "num_pages": num_pages,
            "poisson_rate_req_s": rate,
            "batch": batch, "stream": stream,
            "retraces_after_warm": retraces,
            "ttft_p50_ratio_stream_vs_batch":
                stream["ttft_ms_p50"] / max(batch["ttft_ms_p50"], 1e-9)}


# ------------------------------------------------------------ observability --

def _observability_results(tiny: bool) -> Dict[str, Any]:
    """Metrics-on vs metrics-off engines on identical mixed traffic.

    The observability layer is host-side python on the step boundary —
    counter bumps, a ring append, gauge writes — so it must be invisible
    next to a jitted model step.  Two otherwise-identical ragged engines
    (one ``metrics=True``, one ``metrics=False``) serve the same traffic;
    both repeat until a pass compiles nothing new, then best-of-3 tok/s
    each, the passes interleaved so machine drift hits both arms alike.
    ``overhead_ratio`` = on/off; the nightly job asserts ≥ 0.98 (≤ 2%
    overhead) at full scale.  At tiny scale a step is sub-millisecond,
    which magnifies the fixed ~tens-of-µs host-side bookkeeping far
    beyond its share at any real step time, so tiny gets the
    noise-tolerant 0.8 floor instead (same stance as the
    adversarial-spec ratio).
    """
    from repro.configs import get_config
    from repro.models import build_model
    from repro.serving import EngineCore

    page = 8 if tiny else 16
    lanes = 4 if tiny else 16
    max_len = 128 if tiny else 1024
    num_pages = (2 if tiny else 4) * max_len // page
    cfg = get_config("deepseek-7b-smoke")
    params = build_model(cfg).init(jax.random.PRNGKey(0))

    def engine(metrics: bool):
        return EngineCore(cfg, params, lanes=lanes, page_size=page,
                          num_pages=num_pages, max_len=max_len,
                          chunk_size=2 * page, mode="ragged",
                          metrics=metrics)

    eng_on, eng_off = engine(True), engine(False)

    def drain(eng, seed: int) -> float:
        for r in _mixed_requests(cfg.vocab_size, tiny, seed=seed):
            eng.submit(r)
        t0 = time.perf_counter()
        while eng.scheduler.has_work():
            eng.step()
        dt = time.perf_counter() - t0
        toks = sum(len(r.tokens) for r in eng.finished)
        eng.finished.clear()
        return toks / dt

    for _ in range(2 if tiny else 3):            # retire the compile keys
        a0, b0 = eng_on.trace_count, eng_off.trace_count
        drain(eng_on, seed=7)
        drain(eng_off, seed=7)
        if eng_on.trace_count == a0 and eng_off.trace_count == b0:
            break
    ons, offs = [], []
    for _ in range(3):                           # interleave the arms
        ons.append(drain(eng_on, seed=7))
        offs.append(drain(eng_off, seed=7))
    on, off = max(ons), max(offs)
    return {"tiny": tiny,
            "page_size": page, "lanes": lanes, "num_pages": num_pages,
            "metrics_on_tok_s": on, "metrics_off_tok_s": off,
            "overhead_ratio": on / off,
            "registry_families": len(eng_on.obs.registry.names()),
            "ring_len": len(eng_on.obs.ring)}


# ----------------------------------------------------------------- driver --

# --------------------------------------------------------- sharded engine --

_SHARDED_SNIPPET = """
import json, time
import numpy as np
import jax
from repro.configs import get_config
from repro.models import build_model
from repro.serving import EngineCore, Request

tiny = {tiny}
page, lanes = 8, 4
num_pages = 32 if tiny else 64
cfg = get_config("deepseek-7b-smoke")
params = build_model(cfg).init(jax.random.PRNGKey(0))

def traffic(seed=7):
    rng = np.random.default_rng(seed)
    n = 6 if tiny else 12
    return [Request(uid=i,
                    prompt=rng.integers(0, cfg.vocab_size,
                                        int(rng.integers(4, 40)))
                    .astype(np.int32),
                    max_new=int(rng.integers(4, 12)))
            for i in range(n)]

def arm(mesh):
    eng = EngineCore(cfg, params, lanes=lanes, page_size=page,
                     num_pages=num_pages, chunk_size=2 * page, mesh=mesh)
    for r in traffic():                 # warm pass: compile every bucket
        eng.submit(r)
    eng.run()
    reqs = traffic(seed=8)
    for r in reqs:
        eng.submit(r)
    steps = rows = 0
    t0 = time.perf_counter()
    while eng.scheduler.has_work():
        out = eng.step()
        steps += 1
        rows += out.live_rows
    dt = time.perf_counter() - t0
    toks = sum(len(r.tokens) for r in reqs)
    per_tok = eng.collective_bytes_per_token
    # Measured (not analytic) per-step collective bytes: an AOT
    # lower+compile of the sharded step at the widest bucket, walked by
    # launch/hlo_analysis.  0 at mesh 1 (no collectives to count).
    measured = eng.measure_collective_bytes()
    return {{"mesh": eng.mesh_size, "tok_s": toks / dt, "steps": steps,
             "tokens": toks, "live_rows": rows,
             "collective_bytes_per_token": per_tok,
             "collective_bytes_per_step": per_tok * rows // max(steps, 1),
             "collective_bytes_per_step_measured": measured,
             "traces": eng.trace_count}}

out = {{"mesh1": arm(None), "mesh2": arm(2)}}
out["tok_s_ratio_mesh2_vs_mesh1"] = (out["mesh2"]["tok_s"]
                                     / out["mesh1"]["tok_s"])
print("RESULT " + json.dumps(out))
"""


def _sharded_results(tiny: bool) -> Dict[str, Any]:
    """Mesh 1 vs mesh 2 on identical traffic, in a 2-device subprocess.

    The child measures two placeholder CPU devices, so it pins itself to
    the CPU: on a chip host it must not contend for the chip the parent
    holds."""
    import subprocess
    import sys

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=2").strip()
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, "-c", _SHARDED_SNIPPET.format(tiny=tiny)],
        capture_output=True, text=True, env=env, timeout=1200)
    if proc.returncode != 0:
        raise RuntimeError(f"sharded arm failed:\n{proc.stderr[-4000:]}")
    line = [ln for ln in proc.stdout.splitlines()
            if ln.startswith("RESULT ")][-1]
    return json.loads(line[len("RESULT "):])


def run_serving(tiny: bool = False) -> Dict[str, Any]:
    return {"meta": {"platform": jax.default_backend(), "tiny": tiny,
                     "config": "deepseek-7b-smoke"},
            "engines": _engine_results(tiny),
            "step_breakdown": _breakdown_results(tiny),
            "prefill_ttft": _prefill_results(tiny),
            "speculative": _speculative_results(tiny),
            "prefix_reuse": _prefix_reuse_results(tiny),
            "serve_loop": _serve_loop_results(tiny),
            "observability": _observability_results(tiny),
            "sharded": _sharded_results(tiny)}


def write_json(results: Dict[str, Any], path: str = _JSON_DEFAULT) -> None:
    with open(path, "w") as f:
        json.dump(results, f, indent=2, sort_keys=True)
        f.write("\n")


def rows_from(results: Dict[str, Any]) -> Iterator[Row]:
    e, bd = results["engines"], results["step_breakdown"]
    pf = results["prefill_ttft"]
    sp = results["speculative"]
    px = results["prefix_reuse"]
    yield ("serving/slot_contiguous_tok_s", e["slot"]["tok_s"],
           f"{e['slot']['tokens']} toks; {e['slot']['lanes']} lanes x "
           f"{e['max_len']} rows = budget")
    yield ("serving/padded_tok_s", e["padded"]["tok_s"],
           f"same budget as {e['num_pages']} x {e['page_size']}-row pages; "
           f"{e['padded']['lanes']} lanes, padded (lanes, C) steps")
    yield ("serving/ragged_tok_s", e["ragged"]["tok_s"],
           f"same budget/lanes, token-level ragged steps "
           f"(buckets {e['token_buckets']})")
    yield ("serving/ragged_speedup", e["speedup"],
           "ragged EngineCore vs slot engine, equal-memory mixed traffic")
    yield ("serving/padded_speedup", e["speedup_padded"],
           "PR-3 padded EngineCore vs slot engine (the padding-tax arm)")
    yield ("serving/ragged_vs_padded_speedup", e["speedup_ragged_vs_padded"],
           "the padding tax itself: same engine, ragged vs padded packing")
    yield ("serving/padding_efficiency_ragged",
           e["ragged"]["padding_efficiency"],
           f"live rows / computed rows ({e['ragged']['live_rows']} / "
           f"{e['ragged']['padded_rows']})")
    yield ("serving/padding_efficiency_padded",
           e["padded"]["padding_efficiency"],
           f"live rows / computed rows ({e['padded']['live_rows']} / "
           f"{e['padded']['padded_rows']})")
    yield ("serving/ragged_step_ms_p50", e["ragged"]["step_ms_p50"],
           "EngineCore ragged step latency (packed prefill+decode stream)")
    yield ("serving/ragged_peak_cache_rows",
           float(e["ragged"]["peak_cache_rows"]),
           f"resident rows at peak (slot engine: "
           f"{e['slot']['peak_cache_rows']} always)")
    yield ("serving/mixed_prefill_decode_steps",
           float(e["ragged"]["mixed_steps"]),
           f"ragged steps batching prefill chunks with decodes "
           f"({e['ragged']['prefill_tokens']} chunk toks streamed)")
    yield ("serving/step_legacy_gather_ms", bd["legacy_gather_ms"],
           "the per-step copy the in-place kernel deleted")
    yield ("serving/step_attend_in_place_ms", bd["attend_in_place_ms"],
           "paged attention through the table (live step, dominant)")
    yield ("serving/step_write_row_ms", bd["write_row_ms"],
           "single-row pool write (live step)")
    yield ("serving/attend_speedup_vs_gather_path", bd["attend_speedup"],
           f"legacy gather+attend {bd['legacy_attend_path_ms']:.3g} ms -> "
           f"in-place {bd['attend_in_place_ms']:.3g} ms at "
           f"{bd['shape']['rows_per_lane']} rows/lane")
    yield ("serving/step_speedup_vs_gather_path", bd["step_speedup"],
           "attend+write vs PR 1 gather+attend+page-writeback")
    yield ("serving/ttft_chunked_ms", pf["chunked"]["ttft_ms_distinct_median"],
           f"median over distinct prompt lens {pf['prompt_lens']}; "
           f"prefill_mode=chunked (c={pf['chunk_size']})")
    yield ("serving/ttft_scatter_ms", pf["scatter"]["ttft_ms_distinct_median"],
           "same stream through the PR-2 contiguous-then-scatter path; "
           "prefill_mode=scatter (re-jits per length)")
    yield ("serving/ttft_speedup_distinct", pf["ttft_speedup_distinct"],
           "chunked vs scatter on all-distinct prompt lengths")
    yield ("serving/ttft_speedup_warm", pf["ttft_speedup_warm"],
           "chunked vs scatter at a repeated (pre-compiled) length")
    rep, adv = sp["repetitive"], sp["adversarial"]
    yield ("serving/spec_accepted_per_step", rep["accepted_per_spec_step"],
           f"extra tokens committed per drafting step, repetitive stream "
           f"(k={sp['spec_k']}, {sp['proposer']}; CI floor 1.5)")
    yield ("serving/spec_acceptance_repetitive", rep["acceptance"],
           f"{rep['spec']['accepted_tokens']} / "
           f"{rep['spec']['drafted_tokens']} drafts accepted over "
           f"{rep['spec']['spec_steps']} drafting steps")
    yield ("serving/spec_tok_s_repetitive", rep["spec"]["tok_s"],
           f"spec engine, {sp['lanes']} lanes; non-spec baseline "
           f"{rep['baseline']['tok_s']:.4g} tok/s on the same stream")
    yield ("serving/spec_speedup_repetitive", rep["tok_s_ratio"],
           f"spec vs non-spec tok/s, lookup-friendly traffic "
           f"({rep['spec']['steps']} vs {rep['baseline']['steps']} steps)")
    yield ("serving/spec_tok_s_ratio_adversarial", adv["tok_s_ratio"],
           f"spec vs non-spec tok/s on lookup-hostile traffic "
           f"({adv['spec']['drafted_tokens']} drafts issued; CI floor 0.8)")
    rej = sp["rejection"]
    yield ("serving/spec_tok_s_ratio_rejection", rej["tok_s_ratio"],
           f"worst case: every draft verified and rolled back "
           f"(acceptance {rej['acceptance']:.3g} over "
           f"{rej['spec']['drafted_tokens']} junk drafts; CPU is "
           f"compute-bound so verify rows cost linearly here)")
    yield ("serving/prefix_cold_ttft_ms", px["cold_ttft_ms"],
           f"first shared-prefix request ({px['shared_prefix_tokens']}+"
           f"{px['tail_tokens']} tokens), compile-warm, cache miss")
    yield ("serving/prefix_warm_ttft_ms", px["warm_ttft_ms_median"],
           f"median of {px['warm_requests']} cache-hit requests "
           f"(stream only the {px['tail_tokens']}-token tail)")
    yield ("serving/prefix_ttft_speedup", px["ttft_speedup_warm_vs_cold"],
           "warm vs cold TTFT on the shared-prefix workload, same engine")
    yield ("serving/prefix_hit_rate", px["prefix_hit_rate"],
           f"warm-phase known tokens served from resident pages "
           f"({px['prefix_hit_tokens']} hit; deterministic)")
    yield ("serving/prefix_pages_shared", float(px["pages_shared"]),
           f"shared-page grants across admissions "
           f"({px['cached_pages']} pages resident in the radix cache, "
           f"{px['cow_copies']} CoW copies)")
    sl = results["serve_loop"]
    yield ("serving/serve_loop_stream_req_s", sl["stream"]["req_s"],
           f"AsyncLMServer, Poisson arrivals at the self-calibrated "
           f"{sl['poisson_rate_req_s']:.3g} req/s over {sl['requests']} "
           f"requests, {sl['lanes']} lanes")
    yield ("serving/serve_loop_stream_ttft_ms_p50",
           sl["stream"]["ttft_ms_p50"],
           "submit -> first streamed token, per-client arrival clock")
    yield ("serving/serve_loop_stream_ttft_ms_p99",
           sl["stream"]["ttft_ms_p99"],
           "streaming TTFT tail under Poisson arrivals")
    yield ("serving/serve_loop_stream_tpot_ms", sl["stream"]["tpot_ms"],
           "mean inter-token time after the first, streaming clients")
    yield ("serving/serve_loop_batch_ttft_ms_p50", sl["batch"]["ttft_ms_p50"],
           f"batch driver (submit-all at t=0): median request pays the "
           f"admission queue in its TTFT ({sl['batch']['steps']} steps)")
    yield ("serving/serve_loop_ttft_p50_ratio",
           sl["ttft_p50_ratio_stream_vs_batch"],
           "streaming vs batch TTFT p50, same warm engine + traffic "
           "(CI floor: <= 1)")
    yield ("serving/serve_loop_retraces_after_warm",
           float(sl["retraces_after_warm"]),
           "jit traces during the measured post-warm batch+stream passes "
           "(retrace sentinel; nightly CI pins this at 0)")
    ob = results["observability"]
    yield ("serving/obs_overhead_ratio", ob["overhead_ratio"],
           f"metrics-on / metrics-off tok/s on identical mixed traffic "
           f"({ob['metrics_on_tok_s']:.4g} vs {ob['metrics_off_tok_s']:.4g}"
           f"; nightly CI floor 0.98 full / 0.8 tiny — sub-ms tiny steps "
           f"magnify the fixed host-side cost)")
    sh = results["sharded"]
    yield ("serving/sharded_tok_s_mesh1", sh["mesh1"]["tok_s"],
           f"single-device ragged engine in the 2-placeholder-CPU-device "
           f"subprocess "
           f"({sh['mesh1']['tokens']} toks over {sh['mesh1']['steps']} steps)")
    yield ("serving/sharded_tok_s_mesh2", sh["mesh2"]["tok_s"],
           "same traffic, KV-head-sharded pool + shard_map step at mesh 2")
    yield ("serving/sharded_tok_s_ratio", sh["tok_s_ratio_mesh2_vs_mesh1"],
           "mesh 2 vs mesh 1 tok/s — recorded, NO CPU floor (placeholder "
           "devices share the same cores; overhead signal only)")
    yield ("serving/sharded_collective_bytes_per_token",
           float(sh["mesh2"]["collective_bytes_per_token"]),
           f"analytic all-gather bytes received per device per token row "
           f"at mesh 2 (per step: {sh['mesh2']['collective_bytes_per_step']}"
           f" B; mesh 1: {sh['mesh1']['collective_bytes_per_token']} B)")
    yield ("serving/sharded_collective_bytes_per_step_measured",
           float(sh["mesh2"]["collective_bytes_per_step_measured"]),
           "per-device collective bytes per widest-bucket step, counted "
           "from the compiled HLO (launch/hlo_analysis walk; nightly CI "
           "asserts > 0 at mesh 2)")


def bench_paged_serving() -> Iterator[Row]:
    results = run_serving()
    write_json(results)                 # benchmarks.run refreshes the JSON
    yield from rows_from(results)


ALL_SERVING = (bench_paged_serving,)


def main() -> None:
    ap = argparse.ArgumentParser(
        description="serving benchmarks -> CSV rows + BENCH_serving.json")
    ap.add_argument("--json", default=_JSON_DEFAULT,
                    help="output path for the JSON results")
    ap.add_argument("--tiny", action="store_true",
                    help="CI scale: small pools/traffic, crash-test numbers")
    args = ap.parse_args()
    results = run_serving(tiny=args.tiny)
    write_json(results, args.json)
    print("name,value,derived")
    for name, value, note in rows_from(results):
        print(f"{name},{value:.6g},{note}")
    print(f"wrote {args.json}")


if __name__ == "__main__":
    main()
