"""Speculative decoding: the draft/verify/rollback gate suite.

The contract under test is exactness, not speed: greedy speculative decode
must be **token-identical** to non-speculative decode — the verify rule is
argmax equality against the engine's own greedy pick, so a drafted token is
committed iff sequential decode would have emitted it — and a rejected
draft must leave **no trace in the pool**: refcounts, free heap, page
tables and cursors identical to never having drafted.  Covered here:

- equivalence cross: speculative ragged decode vs non-speculative
  ragged *and* padded baselines, float and int8, k ∈ {1, 2, 4}, prefix
  cache on and off, under a proposer that mixes full accepts, partial
  accepts and full rejects;
- forced best case (oracle proposer replaying the true continuation: every
  draft accepted, strictly fewer steps) and forced worst case (adversarial
  proposer off-by-one everywhere: every draft rejected, stream unchanged);
- acceptance-rule property: each drafting step commits exactly the longest
  drafted prefix matching the true continuation, plus the bonus token;
- pool-state twin: stepping a drafting engine whose every draft is
  rejected leaves refcounts / free heap / tables / cursors equal to a
  never-drafting twin after *every* step;
- scheduler properties with 1+k decode chunks: packing invariants (budget,
  tightest bucket, cu_seqlens/pos/stream consistency) hold with drafts in
  the stream and under preemption; a budget-starved step sheds drafts —
  never mandatory tokens, never residents; page pressure degrades drafts
  without evicting anyone;
- compile-level gates: the verify step's graph is the same one-varlen-
  attend graph as the plain ragged step (no per-draft loop, no gathered
  (lanes, k) KV), and k is a static shape — draft counts varying 0..k
  retrace nothing.
"""
from collections import Counter

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # CI image without hypothesis: seeded fallback
    from tests._hypothesis_stub import given, settings, st

from repro.serving import (EngineCore, NGramProposer, Request, Scheduler,
                           StepOutput)
from tests.test_engine_core import build, by_uid, prompts_for

LANES, PS, PAGES, CHUNK, MAX_NEW = 2, 8, 32, 8, 8


def _prompts(cfg, n=4, shared=2 * PS, tail=4, seed=11):
    """n equal-length prompts sharing a page-aligned prefix (so the prefix
    cache has something to hit) with distinct tails (so the scripted
    proposers can tell the streams apart)."""
    rng = np.random.default_rng(seed)
    prefix = rng.integers(0, cfg.vocab_size, shared).astype(np.int32)
    return [np.concatenate([prefix,
                            rng.integers(0, cfg.vocab_size,
                                         tail).astype(np.int32)])
            for _ in range(n)]


def _serve(eng, prompts, max_new=MAX_NEW):
    """Submit one request per prompt and drain → (uid → tokens, n_steps)."""
    for i, p in enumerate(prompts):
        eng.submit(Request(uid=i, prompt=p, max_new=max_new))
    steps = 0
    while eng.scheduler.has_work():
        eng.step()
        steps += 1
        assert steps < 10_000
    return by_uid(eng.finished), steps


class ScriptedProposer:
    """Drafts by replaying a known ground-truth generation.

    ``truth`` maps each prompt (as a tuple) to its greedy continuation.
    ``corrupt(call_index, k)`` returns the draft position to corrupt
    (off-by-one the token) or None — so tests can force full acceptance
    (never corrupt), full rejection (always position 0) or exact partial
    acceptance.  Streams are matched on the full prompt (all prompts are
    equal length), so shared prefixes never alias.
    """

    def __init__(self, truth, vocab, corrupt=lambda i, k: None):
        self.truth = {tuple(p): list(t) for p, t in truth.items()}
        self.vocab = vocab
        self.corrupt = corrupt
        self.calls = 0
        self.log = []                       # (drafts, true continuation)

    def __call__(self, stream, k):
        s = [int(t) for t in stream]
        for prompt, toks in self.truth.items():
            lp = len(prompt)
            if tuple(s[:lp]) == prompt and s[lp:] == toks[:len(s) - lp]:
                got = len(s) - lp
                cont = toks[got:got + k]
                drafts = list(cont)
                m = self.corrupt(self.calls, len(drafts))
                if m is not None and m < len(drafts):
                    drafts[m] = (drafts[m] + 1) % self.vocab
                self.calls += 1
                if drafts:
                    self.log.append((drafts, cont))
                return drafts
        return []


_BASE = {}       # (kv_quant, mode) → (cfg, params, uid → tokens, steps)


def _baseline(kv_quant, mode):
    if (kv_quant, mode) not in _BASE:
        cfg, params = build(kv_quant=kv_quant)
        eng = EngineCore(cfg, params, lanes=LANES, page_size=PS,
                         num_pages=PAGES, chunk_size=CHUNK, mode=mode)
        done, steps = _serve(eng, _prompts(cfg))
        assert eng.pages_in_use == 0
        _BASE[(kv_quant, mode)] = (cfg, params, done, steps)
    return _BASE[(kv_quant, mode)]


def _truth(cfg, done):
    return {tuple(int(t) for t in p): done[i]
            for i, p in enumerate(_prompts(cfg))}


def _spec_engine(cfg, params, proposer, k, prefix_cache=False, lanes=LANES,
                 num_pages=PAGES, **kw):
    return EngineCore(cfg, params, lanes=lanes, page_size=PS,
                      num_pages=num_pages, chunk_size=CHUNK, mode="ragged",
                      speculative=True, spec_k=k, proposer=proposer,
                      prefix_cache=prefix_cache, **kw)


# ------------------------------------------------------ equivalence cross --

_SPEC = {}       # (kv_quant, k, prefix_cache) → (uid → tokens, stats)


def _spec_run(kv_quant, k, prefix_cache):
    """Memoized speculative run under the mixed-corruption proposer: the
    corrupt position cycles ∅, 0, 1, … so full accepts, full rejects and
    partial accepts (rollback) all happen in every configuration."""
    key = (kv_quant, k, prefix_cache)
    if key not in _SPEC:
        cfg, params, want, _ = _baseline(kv_quant, "ragged")
        prop = ScriptedProposer(
            _truth(cfg, want), cfg.vocab_size,
            corrupt=lambda i, d: None if i % (k + 1) == 0
            else i % (k + 1) - 1)
        eng = _spec_engine(cfg, params, prop, k, prefix_cache)
        done, _ = _serve(eng, _prompts(cfg))
        # with the cache on, published prefix pages deliberately stay
        # resident after finish; everything else must be back in the heap
        cached = eng.prefix_stats.get("cached_pages", 0) if prefix_cache else 0
        assert eng.pages_in_use == cached
        assert eng.drafted_total > 0, "proposer never drafted"
        _SPEC[key] = (done, eng.spec_stats)
    return _SPEC[key]


@pytest.mark.parametrize("prefix_cache", [False, True])
@pytest.mark.parametrize("k", [1, 2, 4])
@pytest.mark.parametrize("base_mode", ["ragged", "padded"])
@pytest.mark.parametrize("kv_quant", [False, True])
def test_spec_greedy_token_identical(kv_quant, base_mode, k, prefix_cache):
    """Speculative greedy decode emits byte-identical token streams to the
    non-speculative engine in BOTH baseline packings, float and int8,
    k ∈ {1,2,4}, prefix cache on and off — under a proposer that mixes
    full accepts, partial accepts and full rejects."""
    _, _, want, _ = _baseline(kv_quant, base_mode)
    done, stats = _spec_run(kv_quant, k, prefix_cache)
    assert done == want, (
        f"speculative (k={k}, cache={prefix_cache}) diverged from "
        f"{base_mode} baseline: {stats}")


def test_spec_partial_acceptance_actually_happened():
    """The cross above must have exercised rollback, not just all-or-
    nothing: at k=4 the corruption cycle yields partial accepts (0 <
    acceptance < 1)."""
    _, stats = _spec_run(False, 4, False)
    assert 0.0 < stats["acceptance"] < 1.0, stats


# --------------------------------------------------- forced best and worst --

def test_spec_best_case_all_accepted_fewer_steps():
    """Oracle proposer replays the true continuation: every draft accepted
    (acceptance = 1), the stream is identical, and the engine takes
    strictly fewer steps than sequential decode."""
    cfg, params, want, base_steps = _baseline(False, "ragged")
    prop = ScriptedProposer(_truth(cfg, want), cfg.vocab_size)
    eng = _spec_engine(cfg, params, prop, k=4)
    done, steps = _serve(eng, _prompts(cfg))
    assert done == want
    s = eng.spec_stats
    assert s["acceptance"] == 1.0 and s["drafted_tokens"] > 0, s
    assert steps < base_steps, (steps, base_steps)
    assert eng.pages_in_use == 0


def test_spec_worst_case_all_rejected_stream_unchanged():
    """Adversarial proposer corrupts draft position 0 every call: every
    draft is rejected, yet the stream is identical and the pool drains
    clean — speculation can waste work but never corrupt state."""
    cfg, params, want, _ = _baseline(False, "ragged")
    prop = ScriptedProposer(_truth(cfg, want), cfg.vocab_size,
                            corrupt=lambda i, d: 0)
    eng = _spec_engine(cfg, params, prop, k=4)
    done, _ = _serve(eng, _prompts(cfg))
    assert done == want
    s = eng.spec_stats
    assert s["drafted_tokens"] > 0 and s["accepted_tokens"] == 0, s
    assert eng.pages_in_use == 0


def test_ngram_proposer_end_to_end():
    """The default n-gram proposer (no scripting, no ground truth) is also
    token-identical — lookup drafts are just another proposer under the
    same verify rule."""
    cfg, params, want, _ = _baseline(False, "ragged")
    eng = _spec_engine(cfg, params, NGramProposer(max_ngram=3, history=8),
                       k=4)
    done, _ = _serve(eng, _prompts(cfg))
    assert done == want
    assert eng.pages_in_use == 0


# ------------------------------------------------ acceptance-rule property --

@settings(max_examples=5, deadline=None)
@given(st.integers(0, 10_000))
def test_acceptance_commits_exactly_longest_matching_prefix(seed):
    """Single lane, per-call random corruption position: every drafting
    step must commit exactly ``longest matching prefix + 1`` tokens —
    checked against the proposer's own log of (drafts, true continuation)
    using the step's drafted/accepted accounting."""
    rng = np.random.default_rng(seed)
    cfg, params, want, _ = _baseline(False, "ragged")
    prompts = _prompts(cfg)[:1]
    prop = ScriptedProposer(
        _truth(cfg, want), cfg.vocab_size,
        corrupt=lambda i, d: int(v) if (v := rng.integers(0, d + 1)) < d
        else None)
    eng = _spec_engine(cfg, params, prop, k=4, lanes=1)
    eng.submit(Request(uid=0, prompt=prompts[0], max_new=MAX_NEW))
    li = 0
    while eng.scheduler.has_work():
        out = eng.step()
        if not out.drafted_tokens:
            continue
        drafts, cont = prop.log[li]
        li += 1
        # the scheduler may have trimmed the proposal (budget/bucket):
        # the plan kept the oldest prefix of it
        drafts = drafts[:out.drafted_tokens]
        exp = 0
        while exp < len(drafts) and drafts[exp] == cont[exp]:
            exp += 1
        assert out.accepted_tokens == exp, (drafts, cont, out)
    assert li == len(prop.log), "drafting steps and proposer log diverged"
    assert by_uid(eng.finished)[0] == want[0]


# ----------------------------------------------------- pool-state rollback --

def test_rejected_drafts_leave_pool_identical_to_never_drafting():
    """Twin engines in lockstep — one drafting (every draft rejected), one
    plain.  After EVERY step: identical refcounts, identical free heap
    (as a multiset: pop-min allocation makes it identical in order too),
    identical page tables and cursors.  Rollback is provably 'as if the
    drafts never happened', not just 'eventually cleaned up'.

    Single lane on purpose: with lanes sharing a step, drafts legitimately
    change *other* lanes' pacing — bucket trim cuts drafts before prefill
    tails, so a co-scheduled prefill can keep rows the plain engine's trim
    would shave (a throughput win, covered by the packing tests) — and two
    lanes allocating in one step can pop heap pages in a different order.
    Neither is rollback; one lane pins both, making the claim exact."""
    cfg, params, want, _ = _baseline(False, "ragged")
    prompts = _prompts(cfg)
    prop = ScriptedProposer(_truth(cfg, want), cfg.vocab_size,
                            corrupt=lambda i, d: 0)
    plain = EngineCore(cfg, params, lanes=1, page_size=PS,
                       num_pages=PAGES, chunk_size=CHUNK, mode="ragged")
    spec = _spec_engine(cfg, params, prop, k=4, lanes=1)
    for i, p in enumerate(prompts):
        plain.submit(Request(uid=i, prompt=p, max_new=MAX_NEW))
        spec.submit(Request(uid=i, prompt=p, max_new=MAX_NEW))
    drafted = 0
    while plain.scheduler.has_work() or spec.scheduler.has_work():
        plain.step()
        out = spec.step()
        drafted += out.drafted_tokens
        assert out.accepted_tokens == 0
        assert spec.kv.ref == plain.kv.ref
        assert sorted(spec.kv.free) == sorted(plain.kv.free)
        assert ([(r.req.uid, r.rows, r.pages)
                 for r in spec.scheduler.running]
                == [(r.req.uid, r.rows, r.pages)
                    for r in plain.scheduler.running])
    assert drafted > 0, "twin test never drafted"
    assert by_uid(spec.finished) == by_uid(plain.finished) == want
    assert spec.pages_in_use == plain.pages_in_use == 0


def test_abort_after_drafting_leaves_pool_identical_to_never_drafting():
    """Abort arm of the twin test: cancel the resident request right after
    a drafting step — the instant a lane's page table may still cover the
    speculative worst case (cursor + 1 + draft rows).  ``abort`` must
    route the surplus through ``uncommit`` before publish/release, so the
    refcounts and free heap stay identical to the never-drafted twin
    *through* the abort, the survivors drain token-identically, and the
    pool empties.  Same single-lane lockstep discipline as above."""
    cfg, params, want, _ = _baseline(False, "ragged")
    prompts = _prompts(cfg)
    prop = ScriptedProposer(_truth(cfg, want), cfg.vocab_size,
                            corrupt=lambda i, d: 0)
    plain = EngineCore(cfg, params, lanes=1, page_size=PS,
                       num_pages=PAGES, chunk_size=CHUNK, mode="ragged")
    spec = _spec_engine(cfg, params, prop, k=4, lanes=1)
    for i, p in enumerate(prompts):
        plain.submit(Request(uid=i, prompt=p, max_new=MAX_NEW))
        spec.submit(Request(uid=i, prompt=p, max_new=MAX_NEW))
    aborted = None
    while plain.scheduler.has_work() or spec.scheduler.has_work():
        plain.step()
        out = spec.step()
        if aborted is None and out.drafted_tokens:
            aborted = spec.scheduler.running[0].req.uid
            assert spec.abort(aborted) and plain.abort(aborted)
        assert spec.kv.ref == plain.kv.ref
        assert sorted(spec.kv.free) == sorted(plain.kv.free)
        assert ([(r.req.uid, r.rows, r.pages)
                 for r in spec.scheduler.running]
                == [(r.req.uid, r.rows, r.pages)
                    for r in plain.scheduler.running])
    assert aborted is not None, "abort arm never drafted"
    survivors = {u: t for u, t in want.items() if u != aborted}
    assert by_uid(spec.finished) == by_uid(plain.finished) == survivors
    assert spec.pages_in_use == plain.pages_in_use == 0


# ------------------------------------------- scheduler chunk-aware packing --

def _rng_proposer(rng, vocab):
    """Deterministic fake proposer for jax-free scheduler tests: draft
    length and tokens keyed off the rng stream."""
    def prop(stream, k):
        d = int(rng.integers(0, k + 1))
        return [int(t) for t in rng.integers(0, vocab, d)]
    return prop


def _make_spec_scheduler(num_pages=64, lanes=3, chunk=8, step_tokens=None,
                         spec_k=4, proposer=None, page_size=8,
                         token_buckets=None):
    from repro.models import build_model
    from repro.serving import PagedKVCache
    from repro.configs import get_config
    cfg = get_config("deepseek-7b-smoke")
    kv = PagedKVCache(build_model(cfg), num_pages, page_size)
    return Scheduler(kv, lanes=lanes, chunk_size=chunk,
                     step_tokens=step_tokens, spec_k=spec_k,
                     proposer=proposer, token_buckets=token_buckets), cfg


def _sim_spec_engine(sched, batch, rng):
    """Advance scheduler state the way EngineCore._finish would for a
    drafting step — commit a random prefix of each lane's drafts plus the
    bonus token, uncommit the surplus pages — without any jax compute."""
    for p in batch.plans:
        run, req = p.run, p.run.req
        if not p.sample:
            run.rows += p.q_len
            continue
        d = len(p.drafts)
        acc = int(rng.integers(0, d + 1)) if d else 0
        n, done = 0, False
        for _ in range(acc + 1):
            req.tokens.append(0)
            n += 1
            if len(req.tokens) >= req.max_new:
                done = True
                break
        run.rows += (p.q_len - d) + n - 1
        if d:
            run.pages = sched.kv.uncommit(run.pages, run.rows)
        if done:
            sched.finish(run)


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 10_000))
def test_spec_packing_properties(seed):
    """Packing invariants survive 1+k decode chunks: budget respected by
    the whole stream, width is the tightest bucket, cu_seqlens ↔ pos ↔
    stream-token consistency (drafts ride the stream at cursor-relative
    positions), drafts only ever extend greedy decode lanes whose
    mandatory token is intact, and pages cover the drafted worst case.
    Random accept fractions drain the pool back to empty."""
    rng = np.random.default_rng(seed)
    sched, cfg = _make_spec_scheduler(
        proposer=_rng_proposer(np.random.default_rng(seed + 1),
                               cfg_vocab := 512))
    for uid in range(int(rng.integers(2, 7))):
        sched.submit(Request(
            uid=uid,
            prompt=rng.integers(0, cfg_vocab,
                                int(rng.integers(1, 30))).astype(np.int32),
            max_new=int(rng.integers(1, 12))))
    steps = drafted = 0
    while sched.has_work():
        steps += 1
        assert steps < 1000, "scheduler did not drain"
        rows_before = {r.ticket: r.rows for r in sched.running}
        batch, _ = sched.schedule_ragged()
        plans, cu = batch.plans, batch.cu_seqlens
        assert batch.live == sum(p.q_len for p in plans) == int(cu[-1])
        assert batch.live <= sched.step_tokens
        assert batch.width in sched.token_buckets
        tighter = [w for w in sched.token_buckets
                   if max(batch.live, 1) <= w < batch.width]
        assert not tighter
        for i, p in enumerate(plans):
            lo, hi = int(cu[i]), int(cu[i + 1])
            d = len(p.drafts)
            drafted += d
            assert hi - lo == p.q_len
            start = rows_before.get(p.run.ticket, 0)
            np.testing.assert_array_equal(
                batch.pos[lo:hi], start + np.arange(p.q_len))
            np.testing.assert_array_equal(batch.tokens[lo:hi],
                                          p.stream_tokens())
            if d:
                # drafts extend a decode lane: mandatory token intact,
                # drafts past the known stream, pages cover the worst case
                assert p.q_len - d == 1 and p.run.remaining() == 1
                assert p.sample
                np.testing.assert_array_equal(
                    batch.tokens[lo + 1:hi], np.asarray(p.drafts, np.int32))
            assert len(p.run.pages) >= sched.kv.pages_needed(
                start + p.q_len)
        _sim_spec_engine(sched, batch, rng)
        for r in sched.running:     # post-commit: no speculative surplus
            assert len(r.pages) == sched.kv.pages_needed(r.rows), (
                "pages beyond the committed cursor survived the step")
    assert sched.kv.free_pages == sched.kv.num_pages
    assert all(r == 0 for r in sched.kv.ref)


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 10_000))
def test_spec_packing_under_preemption(seed):
    """A pool far too small for the offered load, with drafting on: the
    packing invariants hold while evicting, evicted requests rewind clean
    (no pages, cursor 0), draft grants never leak pages, and the stream
    drains with the pool fully restored."""
    rng = np.random.default_rng(seed)
    sched, _ = _make_spec_scheduler(
        num_pages=8, lanes=3, chunk=4, page_size=8,
        proposer=_rng_proposer(np.random.default_rng(seed + 1), 512))
    for uid in range(4):
        sched.submit(Request(
            uid=uid,
            prompt=rng.integers(0, 512,
                                int(rng.integers(4, 16))).astype(np.int32),
            max_new=int(rng.integers(4, 12))))
    steps = 0
    while sched.has_work():
        steps += 1
        assert steps < 3000, "did not drain under preemption + drafting"
        batch, _ = sched.schedule_ragged()
        assert batch.live <= sched.step_tokens
        assert batch.width in sched.token_buckets
        for r in sched.waiting:
            assert r.rows == 0 and r.pages == []
        _sim_spec_engine(sched, batch, rng)
    assert sched.kv.free_pages == sched.kv.num_pages
    assert all(r == 0 for r in sched.kv.ref)


def test_budget_starved_step_degrades_k_not_residents():
    """The chunk-aware fairness fix: mandatory decode tokens (1/lane) are
    funded first, drafts only from leftovers.  step_tokens = lanes leaves
    zero leftover → no drafts, every lane still planned; step_tokens =
    lanes + 2 funds exactly 2 draft tokens, oldest lane first; nobody is
    evicted in either case."""
    greedy4 = lambda s, k: [0] * k
    sched, _ = _make_spec_scheduler(lanes=3, step_tokens=3, spec_k=4,
                                    proposer=greedy4,
                                    token_buckets=(1, 2, 3, 4, 5, 8))
    for uid in range(3):
        sched.submit(Request(uid=uid, prompt=np.array([1 + uid], np.int32),
                             max_new=20))
    batch, preempted = sched.schedule_ragged()
    assert not preempted and sched.preempted_count == 0
    assert len(batch.plans) == 3
    assert all(p.q_len == 1 and p.drafts == () for p in batch.plans)

    sched2, _ = _make_spec_scheduler(lanes=3, step_tokens=5, spec_k=4,
                                     proposer=greedy4,
                                     token_buckets=(1, 2, 3, 4, 5, 8))
    for uid in range(3):
        sched2.submit(Request(uid=uid, prompt=np.array([1 + uid], np.int32),
                              max_new=20))
    batch2, preempted2 = sched2.schedule_ragged()
    assert not preempted2 and sched2.preempted_count == 0
    by_ticket = sorted(batch2.plans, key=lambda p: p.run.ticket)
    assert [len(p.drafts) for p in by_ticket] == [2, 0, 0]
    assert [p.q_len for p in by_ticket] == [3, 1, 1]
    assert batch2.live == 5 <= sched2.step_tokens


def test_page_pressure_degrades_drafts_not_residents():
    """Draft rows are never worth an eviction: with one free page left,
    the oldest decode lane keeps its full draft (it fits free) and the
    younger lane sheds ALL drafts rather than preempting anyone — both
    lanes still run their mandatory token."""
    sched, _ = _make_spec_scheduler(num_pages=3, lanes=2, chunk=8,
                                    page_size=4, spec_k=4, proposer=None)
    for uid in range(2):
        sched.submit(Request(
            uid=uid, prompt=np.arange(1, 4, dtype=np.int32), max_new=8))
    rng = np.random.default_rng(0)
    # stream the 3-token prompts through (samples once: both lanes decode)
    batch, _ = sched.schedule_ragged()
    _sim_spec_engine(sched, batch, rng)
    assert all(r.remaining() == 1 for r in sched.running)
    sched.proposer = lambda s, k: [0] * k          # now start drafting
    batch, preempted = sched.schedule_ragged()
    assert not preempted and sched.preempted_count == 0
    by_ticket = sorted(batch.plans, key=lambda p: p.run.ticket)
    assert len(by_ticket) == 2
    # lane 0: rows 3 → 8 needs one extra page; exactly one is free
    assert len(by_ticket[0].drafts) == 4 and by_ticket[0].q_len == 5
    # lane 1: nothing free without eviction → mandatory token only
    assert len(by_ticket[1].drafts) == 0 and by_ticket[1].q_len == 1


# -------------------------------------------------- compile-level gates --

def test_verify_graph_is_one_varlen_attend():
    """The verify step is the SAME graph as the plain ragged step — the
    drafted rows ride the packed stream through one varlen attend.  The
    spec trace (2-D last_idx) must match the plain trace (1-D last_idx)
    primitive-for-primitive on everything that could hide a per-draft
    loop or a re-attend (dot_general / scan / while counts), contain no
    (lanes, C)-padded intermediate, and no rank ≥ 4 (lanes, 1+k)-leading
    gathered-KV tensor."""
    from tests.test_engine_core import _sampling_args
    from tests._jaxpr import iter_eqns, jaxpr_shapes

    cfg, params = build()
    lanes, k, ps = 3, 4, 8
    eng = _spec_engine(cfg, params, proposer=lambda s, n: [], k=k,
                       lanes=lanes)
    t, pw = 16, 4           # 3 decode lanes with 1+4 rows each, bucketed
    args = (eng.params, eng.kv.pool,
            jnp.full((t, pw), eng.kv.scratch, jnp.int32),
            jnp.zeros((t,), jnp.int32), jnp.zeros((t,), jnp.int32))
    # lane boundaries in the serving (lanes + 2,) convention: 3 lanes with
    # 1 + k rows each, then the trailing pseudo-segment ending at T
    cu = jnp.asarray([0, 5, 10, 15, t, t], jnp.int32)
    spec_jaxpr = jax.make_jaxpr(eng._ragged)(
        *args, jnp.zeros((lanes, k + 1), jnp.int32), cu,
        *_sampling_args(lanes))
    plain_jaxpr = jax.make_jaxpr(eng._ragged)(
        *args, jnp.zeros((lanes,), jnp.int32), cu, *_sampling_args(lanes))

    spec_c, plain_c = (Counter(e.primitive.name for e in iter_eqns(j.jaxpr))
                       for j in (spec_jaxpr, plain_jaxpr))
    for prim in ("dot_general", "scan", "while"):
        assert spec_c.get(prim, 0) == plain_c.get(prim, 0), (
            f"{prim}: {spec_c.get(prim, 0)} vs {plain_c.get(prim, 0)} — "
            f"the verify step added compute beyond the logit gather")
    assert spec_c.get("dot_general", 0) > 0      # sanity: detector sees ops

    shapes = list(jaxpr_shapes(spec_jaxpr.jaxpr))
    bad = [s for s in shapes
           if len(s) >= 4 and s[0] == lanes and s[1] == k + 1]
    assert not bad, f"(lanes, 1+k)-gathered KV intermediate: {bad}"
    chunk = eng.chunk_size
    padded = [s for s in shapes
              if any(s[i] == lanes and s[i + 1] == chunk
                     for i in range(len(s) - 1))]
    assert not padded, f"(lanes, C)-padded intermediate: {padded}"


def test_spec_k_is_static_O1_compiles():
    """k is a shape constant, draft count is data: a proposer whose draft
    length varies 0..k step to step — across a warm-up stream of many
    distinct prompt lengths — compiles the same O(bucket set) step
    functions as ever, and a second stream of new lengths (and new draft
    counts) traces nothing at all."""
    cfg, params = build()
    vary = lambda s, k: [int(s[-1])] * (len(s) % (k + 1))
    eng = _spec_engine(cfg, params, proposer=vary, k=4, lanes=1,
                       num_pages=64)

    def serve(lens, seed):
        for i, p in enumerate(prompts_for(cfg, seed, lens)):
            eng.submit(Request(uid=seed * 100 + i, prompt=p, max_new=4))
        while eng.scheduler.has_work():
            eng.step()
        eng.finished.clear()

    # two warm-up streams cover every reachable (width bucket × table
    # width) combo the draft-length cycle can produce — including drafted
    # widths past the 4-page table boundary (prompts > 32 rows)
    serve(tuple(range(2, 23)) + (24, 27, 29), seed=1)
    serve((23, 25, 26, 28, 30, 31, 33, 34, 36, 38, 40), seed=2)
    traced = eng.trace_count
    widths = len(eng.scheduler.token_buckets) + 2    # + padded-block widths
    assert traced <= 4 * widths, (traced, widths)
    assert eng.drafted_total > 0, "draft-count variety never exercised"
    serve((32, 35, 37, 39, 41), seed=3)              # 5 new distinct lengths
    assert eng.trace_count == traced, (
        f"varying draft counts retraced the step: {traced} → "
        f"{eng.trace_count}")


# ----------------------------------------------------------- constructor --

def test_speculative_requires_ragged_mode():
    cfg, params = build()
    with pytest.raises(ValueError, match="ragged"):
        EngineCore(cfg, params, mode="padded", speculative=True)
    with pytest.raises(ValueError, match="spec_k"):
        EngineCore(cfg, params, mode="ragged", speculative=True, spec_k=0)


def test_step_output_spec_accounting_defaults_zero():
    """Non-speculative engines report zero drafted/accepted — the fields
    exist on every StepOutput so bench/telemetry code never branches."""
    cfg, params = build()
    eng = EngineCore(cfg, params, lanes=1, page_size=8, num_pages=16)
    eng.submit(Request(uid=0, prompt=prompts_for(cfg, 9, (5,))[0],
                       max_new=2))
    out = eng.step()
    assert isinstance(out, StepOutput)
    assert out.drafted_tokens == 0 and out.accepted_tokens == 0
