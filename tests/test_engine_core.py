"""EngineCore + Scheduler: the request-level serving API, both packings.

Covers the serving contracts: mixed chunked-prefill + decode batches are
token-identical to the PR-2 engines (float and int8) in BOTH step packings
— the PR-3 right-aligned (lanes, C) block and the token-level ragged
stream, which is additionally proven token-identical to the padded step on
the same traces; a stream of distinct prompt lengths compiles O(1) step
functions in either mode (never keyed by prompt length); the ragged step
graph contains no (lanes, C)-padded intermediate (jaxpr walk); ragged
packing never exceeds the token budget, keeps cu_seqlens/lane ids
consistent, and preserves decode-first fairness and token-identical
preemption-resume; chunked paged prefill matches the contiguous prefill
oracle over ragged lengths, chunk sizes {1, ps, 3·ps}, GQA and int8 pools;
sliding-window configs page when page_size ≤ window."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # CI image without hypothesis: seeded fallback
    from tests._hypothesis_stub import given, settings, st

from repro.configs import get_config
from repro.models import build_model
from repro.serving import (EngineCore, Request, RequestState, ServingEngine,
                           StepOutput)


def build(name="deepseek-7b-smoke", **replace):
    cfg = get_config(name)
    if replace:
        cfg = cfg.replace(**replace)
    params = build_model(cfg).init(jax.random.PRNGKey(0))
    return cfg, params


def prompts_for(cfg, seed, lens):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, lp).astype(np.int32)
            for lp in lens]


def by_uid(done):
    return {r.uid: r.tokens for r in done}


# --------------------------------------------------- mixed-batch identity --

@pytest.mark.parametrize("mode", ["padded", "ragged"])
@pytest.mark.parametrize("kv_quant", [False, True])
def test_step_token_identical_to_pr2_engines(kv_quant, mode):
    """EngineCore.step() with mixed chunked-prefill + decode lanes emits the
    same greedy token streams as the slot-contiguous engine on the same
    request trace (lowest-index tie-break), float and int8, in both the
    padded-block and ragged-stream packings.  Prompt lengths straddle chunk
    and page boundaries so early requests are decoding while later ones
    still stream prefill chunks — the mixed batch is exercised, not just
    reachable."""
    cfg, params = build(kv_quant=kv_quant)
    lens = (3, 21, 9, 14, 6)
    news = (7, 5, 9, 4, 6)

    def submit_all(eng):
        for i, p in enumerate(prompts_for(cfg, 13, lens)):
            eng.submit(Request(uid=i, prompt=p, max_new=news[i]))

    slot = ServingEngine(cfg, params, slots=3, max_len=64)
    submit_all(slot)
    want = by_uid(slot.run())

    core = EngineCore(cfg, params, lanes=3, page_size=8, num_pages=24,
                      chunk_size=8, mode=mode)
    submit_all(core)
    outs = []
    while core.scheduler.has_work():
        outs.append(core.step())
    assert by_uid(core.finished) == want
    assert any(o.mixed for o in outs), "no step mixed prefill with decode"


@pytest.mark.parametrize("kv_quant", [False, True])
def test_ragged_step_token_identical_to_padded_step(kv_quant):
    """The ragged packed-stream step vs the PR-3 padded step as oracle, on
    the same mixed prefill+decode traces (float and int8): identical token
    streams, and the ragged run's padding efficiency (live rows / computed
    rows) strictly dominates the padded run's."""
    cfg, params = build(kv_quant=kv_quant)
    lens = (5, 27, 11, 18, 8, 3)
    news = (6, 4, 8, 3, 7, 5)

    def run(mode):
        eng = EngineCore(cfg, params, lanes=3, page_size=8, num_pages=24,
                         chunk_size=8, mode=mode)
        for i, p in enumerate(prompts_for(cfg, 31, lens)):
            eng.submit(Request(uid=i, prompt=p, max_new=news[i]))
        outs = []
        while eng.scheduler.has_work():
            outs.append(eng.step())
        return by_uid(eng.finished), outs

    want, outs_p = run("padded")
    got, outs_r = run("ragged")
    assert got == want, "ragged step diverged from the padded oracle"
    assert any(o.mixed for o in outs_r), "no ragged step mixed the phases"

    def eff(outs):
        return (sum(o.live_rows for o in outs)
                / max(sum(o.padded_rows for o in outs), 1))

    assert eff(outs_r) > eff(outs_p), (eff(outs_r), eff(outs_p))
    assert eff(outs_r) >= 0.9, f"ragged packing wasted rows: {eff(outs_r)}"


# ------------------------------------------------------- compile counting --

@pytest.mark.parametrize("mode", ["padded", "ragged"])
def test_distinct_prompt_lengths_compile_O1_step_functions(mode):
    """The recompile fallout of the per-prompt-length b=1 prefill is gone in
    both packings: step shapes are keyed by (width bucket × power-of-two
    table width, held at its high-water mark) — the padded step's widths
    are {1, C}, the ragged step's the scheduler's token-bucket set — never
    by prompt length.  A first stream warms every reachable combo; a
    second stream of *new* distinct lengths then traces nothing at all
    (the PR-2 engines compiled one prefill per length)."""
    cfg, params = build()
    eng = EngineCore(cfg, params, lanes=1, page_size=8, num_pages=64,
                     chunk_size=8, mode=mode)

    def serve(lens, seed):
        for i, p in enumerate(prompts_for(cfg, seed, lens)):
            eng.submit(Request(uid=seed * 100 + i, prompt=p, max_new=2))
        eng.run()
        eng.finished.clear()

    # Warm every reachable (width bucket × table width) combo: lengths
    # 2..22 cover all chunk remainders at table widths 1/2/4, and 24/27/29
    # add the full-chunk and remainder cases at width 4.
    serve(tuple(range(2, 23)) + (24, 27, 29), seed=1)
    traced = eng.trace_count
    # O(1) across the bucket set: bounded by width buckets × table buckets
    # ({1, 2, 4} for this pool), and never by the number of prompt lengths.
    widths = 2 if mode == "padded" else len(eng.scheduler.token_buckets)
    assert traced <= 3 * widths, (traced, widths)
    serve((23, 25, 26, 28, 30), seed=2)        # 5 new distinct lengths
    assert eng.trace_count == traced, (
        f"new prompt lengths retraced the step: {traced} → "
        f"{eng.trace_count}")


def test_page_table_width_never_shrinks_across_steps():
    """pack() holds the page-table P axis at its high-water mark: after a
    long resident has grown the table, a later short-only step packs at
    the same width — same trace key — instead of shrinking back.  Without
    the mark, every time the resident mix turned short (fresh arrivals
    mid-serve) the step recompiled at (stream width × smaller table
    width): a multi-second XLA stall in the middle of live traffic for a
    shape the engine had already paid for."""
    cfg, params = build()
    eng = EngineCore(cfg, params, lanes=2, page_size=8, num_pages=32,
                     chunk_size=8, mode="ragged")
    widths = []
    inner = eng._ragged

    def spy(p, pool, table, *rest):
        widths.append(int(table.shape[1]))
        return inner(p, pool, table, *rest)

    eng._ragged = spy
    eng.submit(Request(uid=0, prompt=prompts_for(cfg, 3, (20,))[0],
                       max_new=8))             # 28 rows → 4 pages resident
    eng.run()
    eng.finished.clear()
    hwm = max(widths)
    assert hwm >= 4, widths
    widths.clear()
    eng.submit(Request(uid=1, prompt=prompts_for(cfg, 4, (4,))[0],
                       max_new=4))             # 1-page request, solo
    eng.run()
    assert widths and set(widths) == {hwm}, (widths, hwm)


# ------------------------------------------------------------ preemption --

@pytest.mark.parametrize("mode", ["padded", "ragged"])
def test_preempted_request_resumes_token_identical(mode):
    """Fill the pool with a long-running request, admit a longer prompt;
    the pool exhausts mid-flight, the youngest resident is evicted
    (recompute preemption) and later resumes — and every request's token
    stream is identical to an uncontended (solo, full-pool) run.  Holds in
    both packings: ragged trim/packing changes step shapes, never the
    replayed stream."""
    cfg, params = build()
    specs = [(4, 26), (12, 14)]            # (prompt_len, max_new)
    prompts = prompts_for(cfg, 21, [lp for lp, _ in specs])

    solo = {}
    for uid, (lp, mn) in enumerate(specs):
        eng = EngineCore(cfg, params, lanes=2, page_size=4, num_pages=16,
                         chunk_size=4, mode=mode)
        eng.submit(Request(uid=uid, prompt=prompts[uid], max_new=mn))
        solo[uid] = eng.run()[0].tokens

    # contended: 8 pages cannot hold both peaks (8 + 7 pages)
    eng = EngineCore(cfg, params, lanes=2, page_size=4, num_pages=8,
                     chunk_size=4, mode=mode)
    preempted_seen = []
    for uid, (lp, mn) in enumerate(specs):
        eng.submit(Request(uid=uid, prompt=prompts[uid], max_new=mn))
    while eng.scheduler.has_work():
        out = eng.step()
        preempted_seen.extend(out.preempted)
    assert preempted_seen, "pool contention never triggered an eviction"
    got = by_uid(eng.finished)
    assert got == solo, "preempted request did not resume token-identically"
    assert eng.pages_in_use == 0
    # the evicted request went through the PREEMPTED state and finished
    evicted = eng.finished[-1] if eng.finished[-1].uid in preempted_seen \
        else eng.finished[0]
    assert evicted.state is RequestState.FINISHED


def test_oldest_resident_is_never_evicted():
    """Eviction picks strictly younger residents, so the oldest request
    always runs to completion — the progress guarantee behind
    preemption-by-eviction."""
    cfg, params = build()
    eng = EngineCore(cfg, params, lanes=3, page_size=4, num_pages=8,
                     chunk_size=4)
    for i, p in enumerate(prompts_for(cfg, 3, (6, 6, 6))):
        eng.submit(Request(uid=i, prompt=p, max_new=20))
    first_done = None
    while eng.scheduler.has_work():
        out = eng.step()
        assert 0 not in out.preempted, "oldest request was evicted"
        if first_done is None and out.finished:
            first_done = out.finished[0]
    assert first_done == 0      # FCFS: the oldest finishes first here


# ------------------------------------------- chunked-prefill equivalence --

def _drive_chunked_prefill(model, params, core, prompts, chunk):
    """Manually stream ragged prompts through the unified chunk step (the
    exact EngineCore dataflow) and return each lane's final-row logits."""
    kv = core.kv
    lanes = len(prompts)
    pages = [[] for _ in prompts]
    rows = [0] * lanes
    final = [None] * lanes
    while any(rows[i] < len(prompts[i]) for i in range(lanes)):
        q_len = np.zeros((lanes,), np.int32)
        kv_len = np.zeros((lanes,), np.int32)
        toks = np.zeros((lanes, chunk), np.int32)
        for i, p in enumerate(prompts):
            c = min(chunk, len(p) - rows[i])
            if c <= 0:
                continue
            while len(pages[i]) < kv.pages_needed(rows[i] + c):
                pages[i].append(kv.alloc())
            toks[i, chunk - c:] = p[rows[i]:rows[i] + c]
            q_len[i] = c
            kv_len[i] = rows[i] + c
            rows[i] += c
        width = 1 << max(max(len(pg) for pg in pages) - 1, 0).bit_length()
        tbl = np.full((lanes, width), kv.scratch, np.int32)
        for i, pg in enumerate(pages):
            tbl[i, :len(pg)] = pg
        logits, kv.pool = core._step(
            core.params, kv.pool, jnp.asarray(tbl), jnp.asarray(toks),
            jnp.asarray(kv_len), jnp.asarray(q_len))
        for i in range(lanes):
            if q_len[i] and rows[i] == len(prompts[i]):
                final[i] = np.asarray(logits[i])
    return final


@pytest.mark.parametrize("kv_quant", [False, True])
@pytest.mark.parametrize("chunk_factor", ["1", "ps", "3ps"])
def test_chunked_prefill_matches_contiguous_oracle(chunk_factor, kv_quant):
    """Chunked paged prefill == the contiguous ``prefill`` oracle on the
    final-position logits, over ragged prompt lengths, chunk sizes
    {1, ps, 3·ps}, GQA heads (the smoke config is 4 query / 2 KV) and int8
    pools.  Greedy argmax must agree exactly; logits to float tolerance."""
    cfg, params = build(kv_quant=kv_quant)
    ps = 8
    chunk = {"1": 1, "ps": ps, "3ps": 3 * ps}[chunk_factor]
    m = build_model(cfg)
    lens = (19, 7, 25)                       # ragged, page-straddling
    prompts = prompts_for(cfg, 5, lens)

    core = EngineCore(cfg, params, lanes=len(prompts), page_size=ps,
                      num_pages=16, chunk_size=chunk)
    got = _drive_chunked_prefill(m, params, core, prompts, chunk)

    for i, p in enumerate(prompts):
        caches = m.init_cache(1, len(p))
        want, _ = m.prefill(params, {"tokens": jnp.asarray(p)[None]}, caches)
        want = np.asarray(want[0])
        np.testing.assert_allclose(got[i], want, atol=2e-4, rtol=2e-4,
                                   err_msg=f"lane {i} (len {len(p)})")
        assert int(np.argmax(got[i])) == int(np.argmax(want))


# ------------------------------------------------------------- fairness --

@pytest.mark.parametrize("mode", ["padded", "ragged"])
def test_token_budget_keeps_decode_ahead_of_prefill(mode):
    """With a step token budget, resident decode lanes always get their one
    token before prefill chunks spend the rest — a long prompt streams
    through spare capacity instead of starving decodes.  Ragged trim only
    ever shrinks prefill chunks, so the guarantee survives packing."""
    cfg, params = build()
    eng = EngineCore(cfg, params, lanes=2, page_size=8, num_pages=16,
                     chunk_size=8, step_tokens=5, mode=mode)
    eng.submit(Request(uid=0, prompt=prompts_for(cfg, 1, (4,))[0],
                       max_new=12))
    eng.step()                              # uid 0 resident, decoding
    eng.submit(Request(uid=1, prompt=prompts_for(cfg, 2, (30,))[0],
                       max_new=2))
    saw_budgeted_mix = False
    while eng.scheduler.has_work():
        out = eng.step()
        assert out.prefill_tokens + out.decode_tokens <= 5
        if out.mixed:
            assert out.decode_tokens >= 1
            assert out.prefill_tokens <= 4  # budget minus the decode lane
            saw_budgeted_mix = True
    assert saw_budgeted_mix
    assert len(by_uid(eng.finished)[0]) == 12


# ------------------------------------------------- sliding-window paging --

@pytest.mark.parametrize("page_size", [4, 8])
def test_sliding_window_config_pages_when_window_fits(page_size):
    """gemma2-style local+global stacks serve through EngineCore when
    page_size ≤ window (no ring buffer materialises inside a page — the
    pageability probe must not look past page_size, so the window == page
    boundary works too) and stay token-identical to the slot engine,
    window masking included."""
    cfg, params = build("gemma2-9b-smoke")
    assert cfg.window == 8

    def submit_all(eng):
        for i, p in enumerate(prompts_for(cfg, 5, (4, 14, 9))):
            eng.submit(Request(uid=i, prompt=p, max_new=(6, 4, 8)[i]))

    slot = ServingEngine(cfg, params, slots=2, max_len=64)
    submit_all(slot)
    want = by_uid(slot.run())
    core = EngineCore(cfg, params, lanes=2, page_size=page_size,
                      num_pages=96 // page_size, chunk_size=8)
    submit_all(core)
    assert by_uid(core.run()) == want


# ------------------------------------------------------------ rejection --

def test_empty_prompt_rejected_at_submit():
    """A zero-token prompt can never be scheduled (known() == 0 plans
    q_len = 0 forever) — it must be rejected at submit, not wedge a lane."""
    cfg, params = build()
    eng = EngineCore(cfg, params, lanes=1, page_size=8, num_pages=8)
    with pytest.raises(ValueError, match="empty prompt"):
        eng.submit(Request(uid=0, prompt=np.array([], np.int32), max_new=4))
    assert not eng.scheduler.has_work()


# ----------------------------------------------- ragged graph guarantees --

def _sampling_args(lanes):
    """All-greedy in-step sampling arrays for tracing the ragged step."""
    return (jnp.zeros((lanes,), jnp.float32), jnp.zeros((lanes,), jnp.int32),
            jnp.ones((lanes,), jnp.float32), jnp.zeros((lanes,), jnp.uint32),
            jnp.zeros((lanes,), jnp.int32))


def test_ragged_graph_has_no_padded_intermediate():
    """The ragged step graph must never materialise a (lanes, C)-padded
    block: every intermediate of the traced step is checked for an
    adjacent (lanes, chunk) dim pair.  lanes=3 × chunk=24 shares no
    adjacent pair with any smoke-config dimension or the T=48 stream, so a
    hit can only be the padded block.  The padded step itself is the
    sanity check that the detector fires."""
    from tests._jaxpr import jaxpr_shapes

    cfg, params = build()
    lanes, chunk, ps = 3, 24, 8
    eng = EngineCore(cfg, params, lanes=lanes, page_size=ps, num_pages=32,
                     chunk_size=chunk)
    t, pw = 48, 4                       # 3 decodes + a 45-token chunk share
    cu = jnp.asarray([0, 1, 2, 48, 48], jnp.int32)      # (lanes + 2,)
    jaxpr = jax.make_jaxpr(eng._ragged)(
        eng.params, eng.kv.pool,
        jnp.full((t, pw), eng.kv.scratch, jnp.int32),
        jnp.zeros((t,), jnp.int32), jnp.zeros((t,), jnp.int32),
        jnp.zeros((lanes,), jnp.int32), cu, *_sampling_args(lanes))

    def padded_pairs(shapes):
        return [s for s in shapes
                if any(s[i] == lanes and s[i + 1] == chunk
                       for i in range(len(s) - 1))]

    bad = padded_pairs(jaxpr_shapes(jaxpr.jaxpr))
    assert not bad, f"(lanes, C)-padded intermediate in ragged graph: {bad}"

    # sanity: the detector does catch the padded step's block
    padded = jax.make_jaxpr(eng._step)(
        eng.params, eng.kv.pool,
        jnp.full((lanes, pw), eng.kv.scratch, jnp.int32),
        jnp.zeros((lanes, chunk), jnp.int32),
        jnp.zeros((lanes,), jnp.int32), jnp.zeros((lanes,), jnp.int32))
    assert padded_pairs(jaxpr_shapes(padded.jaxpr))


# ------------------------------------------------ scheduler pack properties --

def _sim_engine(sched, batch):
    """Advance scheduler state the way EngineCore._finish would, without
    running any jax compute (greedy tokens faked as 0)."""
    for p in batch.plans:
        run = p.run
        sample = p.sample
        run.rows += p.q_len
        if not sample:
            continue
        run.req.tokens.append(0)
        if len(run.req.tokens) >= run.req.max_new:
            sched.finish(run)


def _make_scheduler(num_pages=64, lanes=3, chunk=8, step_tokens=None):
    from repro.serving import PagedKVCache, Scheduler
    cfg = get_config("deepseek-7b-smoke")
    kv = PagedKVCache(build_model(cfg), num_pages, 8)
    return Scheduler(kv, lanes=lanes, chunk_size=chunk,
                     step_tokens=step_tokens), cfg


@settings(max_examples=15, deadline=None)
@given(st.integers(0, 10_000))
def test_ragged_packing_properties(seed):
    """Every schedule_ragged() batch, across a random request stream:
    packing never exceeds the token budget; the width is the tightest
    bucket; cu_seqlens is monotone and consistent with lane ids, positions,
    tokens and per-token table rows; decode lanes are never trimmed."""
    rng = np.random.default_rng(seed)
    sched, cfg = _make_scheduler()
    for uid in range(int(rng.integers(2, 7))):
        sched.submit(Request(
            uid=uid,
            prompt=rng.integers(0, cfg.vocab_size,
                                int(rng.integers(1, 30))).astype(np.int32),
            max_new=int(rng.integers(1, 8))))
    steps = 0
    while sched.has_work():
        steps += 1
        assert steps < 500, "scheduler did not drain"
        decode_runs = [r for r in sched.running if r.remaining() == 1]
        rows_before = {r.ticket: r.rows for r in sched.running}
        batch, _ = sched.schedule_ragged()
        plans, cu = batch.plans, batch.cu_seqlens

        # budget + bucket tightness
        assert batch.live == sum(p.q_len for p in plans) == int(cu[-1])
        assert batch.live <= sched.step_tokens
        assert batch.width in sched.token_buckets
        assert batch.width >= max(batch.live, 1)
        tighter = [w for w in sched.token_buckets
                   if max(batch.live, 1) <= w < batch.width]
        assert not tighter, f"width {batch.width} not tightest: {tighter}"

        # cu_seqlens ↔ lane_id ↔ pos ↔ tokens ↔ table consistency
        assert cu[0] == 0 and np.all(np.diff(cu) >= 1)
        for i, p in enumerate(plans):
            lo, hi = int(cu[i]), int(cu[i + 1])
            assert hi - lo == p.q_len
            assert np.all(batch.lane_id[lo:hi] == i)
            start = rows_before.get(p.run.ticket, 0)  # 0: admitted this step
            np.testing.assert_array_equal(
                batch.pos[lo:hi], start + np.arange(p.q_len))
            np.testing.assert_array_equal(
                batch.tokens[lo:hi], p.run.next_tokens(p.q_len))
            npg = len(p.run.pages)
            assert npg >= sched.kv.pages_needed(start + p.q_len)
            np.testing.assert_array_equal(
                batch.table[lo:hi, :npg],
                np.tile(np.asarray(p.run.pages, np.int32), (p.q_len, 1)))
            assert np.all(batch.table[lo:hi, npg:] == sched.kv.scratch)
        assert np.all(batch.lane_id[batch.live:] == -1)
        assert np.all(batch.table[batch.live:] == sched.kv.scratch)

        # decode-first, trim-exempt: every resident decode lane runs intact
        for r in decode_runs:
            if r in sched.running:       # not evicted while planning
                mine = [p for p in plans if p.run is r]
                assert mine and mine[0].q_len == 1, \
                    "decode lane trimmed or starved by ragged packing"
        _sim_engine(sched, batch)
    assert sched.kv.free_pages == sched.kv.num_pages


def test_trim_never_starves_a_prefill_lane():
    """Regression: 8 decode lanes exactly fill a bucket (floor = 8) while a
    2-token prefill tail wants the other 2 tokens.  A trim that zeroed the
    tail would see the identical plan every step and starve it for the
    decodes' whole lifetime; the progress guarantee (every planned lane
    keeps ≥ 1 token, else pad up) must finish it promptly."""
    rng = np.random.default_rng(0)
    sched, cfg = _make_scheduler(num_pages=64, lanes=9, chunk=16)
    for uid in range(8):
        sched.submit(Request(uid=uid, prompt=np.array([1], np.int32),
                             max_new=40))
    sched.submit(Request(
        uid=8, prompt=rng.integers(0, cfg.vocab_size, 2).astype(np.int32),
        max_new=1))
    for _ in range(4):          # uid 8 needs ≤ 2 planned steps to finish
        batch, _ = sched.schedule_ragged()
        assert batch.live <= sched.step_tokens
        _sim_engine(sched, batch)
        if not any(r.req.uid == 8 for r in sched.running):
            break
    assert not any(r.req.uid == 8 for r in sched.running), \
        "prefill lane starved by trim-to-bucket"


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 10_000))
def test_ragged_packing_under_preemption(seed):
    """A pool far too small for the offered load: schedule_ragged must keep
    its packing invariants while evicting — evicted requests rewind to row
    0 and hold no pages, and the stream drains completely."""
    rng = np.random.default_rng(seed)
    sched, cfg = _make_scheduler(num_pages=8, lanes=3, chunk=4)
    for uid in range(4):
        sched.submit(Request(
            uid=uid,
            prompt=rng.integers(0, cfg.vocab_size,
                                int(rng.integers(4, 16))).astype(np.int32),
            max_new=int(rng.integers(4, 12))))
    evictions = 0
    steps = 0
    while sched.has_work():
        steps += 1
        assert steps < 2000, "scheduler did not drain under preemption"
        batch, preempted = sched.schedule_ragged()
        evictions += len(preempted)
        assert batch.live <= sched.step_tokens
        assert batch.width in sched.token_buckets
        for r in sched.waiting:
            assert r.rows == 0 and r.pages == [], \
                "evicted request kept pages or cursor state"
        _sim_engine(sched, batch)
    assert sched.kv.free_pages == sched.kv.num_pages


# ------------------------------------------------------------ StepOutput --

def test_step_output_accounting():
    """StepOutput's lane/token accounting adds up against the request
    bookkeeping."""
    cfg, params = build()
    eng = EngineCore(cfg, params, lanes=2, page_size=8, num_pages=16,
                     chunk_size=8)
    eng.submit(Request(uid=0, prompt=prompts_for(cfg, 9, (11,))[0],
                       max_new=3))
    out = eng.step()
    assert isinstance(out, StepOutput)
    assert out.lanes == 1 and out.prefill_tokens == 8  # first chunk of 11
    assert out.tokens == {} and not out.finished
    out = eng.step()                        # final 3 prompt rows → sample
    assert out.prefill_tokens == 3 and len(out.tokens) == 1
    eng.run()
    assert len(eng.finished[0].tokens) == 3

    # Phase accounting is by remaining-known, not q_len: a chunk_size=1
    # engine still reports its prompt streaming as prefill tokens.
    eng1 = EngineCore(cfg, params, lanes=1, page_size=8, num_pages=16,
                      chunk_size=1)
    eng1.submit(Request(uid=0, prompt=prompts_for(cfg, 9, (5,))[0],
                        max_new=2))
    outs = []
    while eng1.scheduler.has_work():
        outs.append(eng1.step())
    assert sum(o.prefill_tokens for o in outs) == 4   # rows 0..3 of 5
    assert sum(o.decode_tokens for o in outs) == 2    # the 2 sampling steps
