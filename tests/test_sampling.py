"""In-step per-request sampling: semantics, batch invariance, trace shape.

The PR-8 contracts:

- validation — unservable params (max_new ≤ 0, negative temperature with a
  seed, top-k ≤ 0, stop tokens outside the vocab, …) raise
  ``InvalidRequest`` at construction/submit, never mid-serve;
- greedy identity — temperature 0 through the in-step sampler is
  bit-identical to the host lowest-index tie-break, so the full
  cross-engine equivalence matrix (float + int8 × spec × prefix-cache)
  is unchanged;
- batch invariance — a request's sampled stream is a pure function of
  (seed, params, prompt): identical whether served alone, co-batched with
  other traffic, or preempted and replayed;
- stop sequences — truncation lands at exactly the completing token, even
  mid-way through a multi-token speculative commit, and never leaks the
  match into the output;
- trace stability — all sampling params are data: serving new
  temperatures/seeds/top-k/top-p retraces nothing (O(1) compiles);
- graph shape — sampling runs INSIDE the jitted ragged step: the traced
  step outputs int32 tokens (no (lanes, V) float output, no host
  round-trip between logits and token) and its one sampling region
  operates on last-idx-gathered rows, never on the full (T, V) stream.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.serving import (EngineCore, InvalidRequest, Request,
                           SamplingParams, ServingEngine)
from repro.serving.sampling import greedy_rows, sample_rows, stop_holdback
from tests.test_engine_core import _sampling_args, build, by_uid, prompts_for


def engine(cfg, params, **kw):
    kw.setdefault("lanes", 4)
    kw.setdefault("page_size", 4)
    kw.setdefault("num_pages", 64)
    kw.setdefault("chunk_size", 8)
    return EngineCore(cfg, params, **kw)


def serve(eng, reqs):
    for r in reqs:
        eng.submit(r)
    return by_uid(eng.run())


# ------------------------------------------------------------- validation --

def test_invalid_params_rejected_at_construction():
    with pytest.raises(InvalidRequest, match="temperature"):
        SamplingParams(temperature=-0.5, seed=3)
    with pytest.raises(InvalidRequest, match="top_k"):
        SamplingParams(top_k=0)
    with pytest.raises(InvalidRequest, match="top_p"):
        SamplingParams(top_p=0.0)
    with pytest.raises(InvalidRequest, match="top_p"):
        SamplingParams(top_p=1.5)
    with pytest.raises(InvalidRequest, match="seed"):
        SamplingParams(seed=2 ** 32)
    with pytest.raises(InvalidRequest, match="max_tokens"):
        SamplingParams(max_tokens=0)
    with pytest.raises(InvalidRequest, match="stop"):
        SamplingParams(stop=((),))          # empty stop sequence
    with pytest.raises(InvalidRequest, match="stop"):
        SamplingParams(stop=((-3,),))       # negative token id
    # negative temperature WITHOUT a seed is just greedy — servable
    assert SamplingParams(temperature=-1.0).greedy


def test_invalid_requests_rejected_at_submit():
    cfg, params = build()
    eng = engine(cfg, params)
    p = prompts_for(cfg, 0, (8,))[0]
    with pytest.raises(InvalidRequest, match="max_new"):
        Request(uid=0, prompt=p, max_new=0)
    with pytest.raises(InvalidRequest, match="max_tokens"):
        Request(uid=0, prompt=p, max_new=4,
                sampling=SamplingParams(max_tokens=-1))
    # stop tokens outside the vocab: only the engine knows the vocab
    bad = Request(uid=1, prompt=p, max_new=4,
                  sampling=SamplingParams(stop=((cfg.vocab_size,),)))
    with pytest.raises(InvalidRequest, match="vocab"):
        eng.submit(bad)
    assert not eng.scheduler.has_work()     # nothing half-admitted
    # the slot engine rejects the same way
    slot = ServingEngine(cfg, params, slots=1, max_len=48)
    with pytest.raises(InvalidRequest, match="vocab"):
        slot.submit(bad)


def test_max_tokens_folds_into_max_new():
    cfg, params = build()
    p = prompts_for(cfg, 0, (8,))[0]
    r = Request(uid=0, prompt=p, max_new=16,
                sampling=SamplingParams(max_tokens=3))
    assert r.max_new == 3
    assert serve(engine(cfg, params), [r])[0] == r.tokens
    assert len(r.tokens) == 3


# -------------------------------------------------------- greedy identity --

def test_in_step_greedy_matches_host_tie_break():
    """Crafted exact ties: the in-step greedy pick is the host
    lowest-index rule, row for row."""
    from repro.serving.core import greedy_tokens
    rng = np.random.default_rng(0)
    lg = rng.normal(size=(6, 33)).astype(np.float32)
    lg[0, 4] = lg[0, 19] = lg[0].max() + 1.0        # two joint maxima
    lg[1, :] = 0.0                                  # all tied → index 0
    lg[2, 32] = lg[2].max() + 1.0                   # winner at the edge
    z = np.zeros((6,), np.int32)
    picks = np.asarray(sample_rows(
        lg, np.zeros((6,), np.float32), z, np.ones((6,), np.float32),
        z.astype(np.uint32), z))
    assert (picks == greedy_tokens(lg)).all()
    assert picks[0] == 4 and picks[1] == 0 and picks[2] == 32
    assert (np.asarray(greedy_rows(lg)) == picks).all()


@pytest.mark.parametrize("bad", [np.nan, np.inf])
def test_nonfinite_logit_row_picks_sentinel(bad):
    """A row whose max logit is NaN or +inf picks NONFINITE_PICK (never a
    vocab id); finite rows, -inf masks included, sample as before."""
    from repro.serving.sampling import NONFINITE_PICK, sample_in_step
    rng = np.random.default_rng(0)
    lg = rng.normal(size=(3, 33)).astype(np.float32)
    lg[1, 7] = bad
    lg[2, :5] = -np.inf
    z = np.zeros((3,), np.int32)
    kw = dict(temperature=np.zeros((3,), np.float32), top_k=z,
              top_p=np.ones((3,), np.float32), seed=z.astype(np.uint32),
              counter=z)
    picks = np.asarray(sample_in_step(jnp.asarray(lg), **kw))
    want = np.asarray(greedy_rows(lg))
    assert picks[1] == NONFINITE_PICK
    assert picks[0] == want[0] and picks[2] == want[2]
    spec = np.asarray(sample_in_step(jnp.asarray(np.stack([lg, lg], 1)),
                                     **kw))
    assert (spec[1] == NONFINITE_PICK).all() and (spec[0] == want[0]).all()


def test_engine_refuses_a_step_with_nonfinite_logits():
    """NaN weights reach the logits: the step raises instead of streaming
    a garbage token."""
    cfg, params = build()
    params["final_norm"] = jax.tree.map(lambda a: a * jnp.nan,
                                        params["final_norm"])
    eng = EngineCore(cfg, params, lanes=2, page_size=8, num_pages=16,
                     chunk_size=8)
    eng.submit(Request(uid=3, prompt=prompts_for(cfg, 0, [5])[0],
                       max_new=2))
    with pytest.raises(FloatingPointError, match=r"\[3\]"):
        eng.run()


@pytest.mark.parametrize("kv_quant", [False, True])
@pytest.mark.parametrize("feature", ["plain", "spec", "prefix"])
def test_temperature_zero_identity_across_matrix(kv_quant, feature):
    """temperature=0 through the in-step sampler reproduces the padded
    oracle's host-greedy streams across float + int8 × speculative ×
    prefix-cache — the pre-existing equivalence matrix survives the
    sampler moving into the graph."""
    cfg, params = build(kv_quant=kv_quant)
    lens, news = (3, 21, 9, 14), (7, 5, 9, 4)
    kw = {"speculative": feature == "spec",
          "prefix_cache": feature == "prefix"}

    def reqs():
        return [Request(uid=i, prompt=p, max_new=news[i])
                for i, p in enumerate(prompts_for(cfg, 13, lens))]

    ragged = serve(engine(cfg, params, **kw), reqs())
    oracle = serve(engine(cfg, params, mode="padded"), reqs())
    assert ragged == oracle


# -------------------------------------------------------- batch invariance --

def _solo_stream(cfg, params, req_fn, **kw):
    eng = engine(cfg, params, **kw)
    return serve(eng, [req_fn()])[100]


@pytest.mark.parametrize("kv_quant", [False, True])
@pytest.mark.parametrize("prefix_cache", [False, True])
def test_sampled_stream_batch_invariant(kv_quant, prefix_cache):
    """Same (seed, prompt, params) → the same token stream whether the
    request runs alone or shares its steps with co-batched traffic that
    lands it on a different lane."""
    cfg, params = build(kv_quant=kv_quant)
    others = prompts_for(cfg, 7, (13, 7, 21))
    mine = prompts_for(cfg, 8, (5,))[0]

    def req():
        return Request(uid=100, prompt=mine, max_new=6,
                       sampling=SamplingParams(temperature=0.8, top_k=50,
                                               top_p=0.95, seed=42))

    alone = _solo_stream(cfg, params, req, prefix_cache=prefix_cache)
    eng = engine(cfg, params, prefix_cache=prefix_cache)
    crowd = [Request(uid=i, prompt=p, max_new=6)
             for i, p in enumerate(others)]
    shared = serve(eng, crowd + [req()])
    assert shared[100] == alone
    for i in range(3):                      # greedy neighbours unperturbed
        assert shared[i] == serve(engine(cfg, params),
                                  [Request(uid=i, prompt=others[i],
                                           max_new=6)])[i]


def test_sampled_stream_survives_preemption_replay():
    """Per-request keys make even temperature > 0 preemption-deterministic:
    a sampled request evicted mid-flight replays to the identical stream
    (the old shared-PRNG engine could not promise this)."""
    cfg, params = build()
    lens = (17, 15, 13, 11)
    sp = lambda: SamplingParams(temperature=0.9, seed=5)   # noqa: E731

    def reqs():
        rs = [Request(uid=i, prompt=p, max_new=6, sampling=sp())
              for i, p in enumerate(prompts_for(cfg, 3, lens))]
        return rs

    roomy = serve(engine(cfg, params, num_pages=64), reqs())
    tight_eng = engine(cfg, params, num_pages=14, lanes=4)
    tight = serve(tight_eng, reqs())
    assert tight_eng.scheduler.preempted_count > 0, (
        "pool never pressured — preemption path not exercised")
    assert tight == roomy


def test_seeded_streams_reproducible_and_seed_dependent():
    cfg, params = build()
    p = prompts_for(cfg, 1, (9,))[0]

    def stream(seed):
        return serve(engine(cfg, params),
                     [Request(uid=100, prompt=p, max_new=8,
                              sampling=SamplingParams(temperature=1.2,
                                                      seed=seed))])[100]

    assert stream(7) == stream(7)
    assert stream(7) != stream(8)           # overwhelmingly likely


def test_slot_engine_oracle_shares_sampling_semantics():
    """The slot engine draws through the same single-lane oracle kernel:
    same (seed, params, prompt) → same stream as EngineCore on a
    single-request trace (logits match exactly at lanes=1)."""
    cfg, params = build()
    p = prompts_for(cfg, 2, (8,))[0]
    sp = SamplingParams(temperature=1.0, seed=11)
    core = serve(engine(cfg, params, lanes=1),
                 [Request(uid=0, prompt=p, max_new=6, sampling=sp)])[0]
    slot = ServingEngine(cfg, params, slots=1, max_len=48)
    slot.submit(Request(uid=0, prompt=p, max_new=6, sampling=sp))
    assert slot.run()[0].tokens == core


# ------------------------------------------------------- degenerate params --

def _rows(seed=0, n=16, v=33):
    """Random rows plus the adversarial shapes: exact ties, a flat row, a
    one-token-dominant row (cumsum rounding pressure), NEG_INF-ish tails."""
    rng = np.random.default_rng(seed)
    lg = rng.normal(size=(n, v)).astype(np.float32)
    lg[0, :] = 0.0                                  # all tied
    lg[1, 5] = lg[1, 20] = lg[1].max() + 1.0        # joint maxima
    lg[2, 7] += 40.0                                # ~all mass on one token
    lg[3, :10] = -1e30                              # hard-masked head
    return lg


def _picks(lg, *, temps, top_k=0, top_p=1.0, seed=0):
    n, v = lg.shape
    return np.asarray(sample_rows(
        lg, np.full((n,), temps, np.float32),
        np.full((n,), top_k, np.int32), np.full((n,), top_p, np.float32),
        np.full((n,), seed, np.uint32), np.arange(n, dtype=np.int32)))


@pytest.mark.parametrize("top_k", [33, 40])      # k == V and k > V
def test_top_k_at_least_vocab_is_bit_identical_to_no_mask(top_k):
    """k ≥ V keeps the k-th-largest threshold at the row minimum, so the
    mask keeps every token: the drawn stream is BIT-identical to top_k
    disabled on the same seeds — exactly no-op, not almost-surely."""
    lg = _rows()
    for seed in (0, 3, 11, 2 ** 31):
        for temps in (0.7, 1.3):
            a = _picks(lg, temps=temps, top_k=top_k, seed=seed)
            b = _picks(lg, temps=temps, top_k=0, seed=seed)
            assert (a == b).all(), (top_k, seed, temps, a, b)


def test_top_p_one_keeps_the_whole_vocabulary():
    """p == 1.0 disables the nucleus mask *explicitly*: the cumulative
    sum's float rounding may touch 1.0 before the last sorted token (the
    dominant-token and hard-masked rows above push it there), and the
    mass-comparison alone would then drop positive-probability tail
    tokens.  The engine encodes top_p=None as 1.0, so the explicit-1.0
    request must ride the identical pipeline bit for bit."""
    lg = _rows()
    for seed in (0, 7, 123):
        a = _picks(lg, temps=1.1, top_p=1.0, seed=seed)
        b = _picks(lg, temps=1.1, top_p=np.float32(1.0), seed=seed)
        assert (a == b).all()
        assert ((0 <= a) & (a < lg.shape[1])).all()


@pytest.mark.parametrize("tiny", [1e-30, 1e-8, 1e-4])
def test_tiny_temperature_stays_finite_and_greedy_in_the_limit(tiny):
    """temperature → 0+ must not overflow: raw logits / t reaches ±inf at
    t = 1e-30 and a non-finite score poisons ``lut_log_softmax`` (NaN
    scores argmax to index 0, silently).  The max-shift keeps scaled
    scores in [-big, 0], so the draw is finite and — with the winner's
    scaled gap astronomically larger than any Gumbel noise — lands on the
    greedy token, which is NOT index 0 in these rows."""
    lg = _rows()
    want = np.asarray(greedy_rows(lg))
    assert (want[1:4] != 0).any()
    for seed in (0, 5, 99):
        got = _picks(lg, temps=tiny, seed=seed)
        # ties (rows 0–1) may legitimately break off-index under noise at
        # the larger tiny temps; the non-tied rows must be exactly greedy
        assert (got[2:] == want[2:]).all(), (tiny, seed, got, want)


@pytest.mark.parametrize("edge", ["top_k_full", "top_k_over", "top_p_one"])
def test_degenerate_mask_params_noop_end_to_end(edge):
    """Engine-level contract: an explicit top_k ≥ vocab or top_p = 1.0 in
    SamplingParams serves the same stream as the plain temperature-only
    request — the knobs are exact no-ops all the way through submit."""
    cfg, params = build()
    kw = {"top_k_full": dict(top_k=cfg.vocab_size),
          "top_k_over": dict(top_k=cfg.vocab_size + 9),
          "top_p_one": dict(top_p=1.0)}[edge]
    p = prompts_for(cfg, 6, (9,))[0]

    def stream(extra):
        return serve(engine(cfg, params),
                     [Request(uid=0, prompt=p, max_new=6,
                              sampling=SamplingParams(temperature=0.9,
                                                      seed=17, **extra))])[0]

    assert stream(kw) == stream({})


# ---------------------------------------------------------- stop sequences --

def _greedy_stream(cfg, params, prompt, max_new, **kw):
    return serve(engine(cfg, params, **kw),
                 [Request(uid=0, prompt=prompt, max_new=max_new)])[0]


def test_stop_sequence_truncates_and_finishes():
    cfg, params = build()
    p = prompts_for(cfg, 4, (9,))[0]
    g = _greedy_stream(cfg, params, p, 6)
    eng = engine(cfg, params)
    out = serve(eng, [Request(uid=0, prompt=p, max_new=6,
                              sampling=SamplingParams(
                                  stop=((g[2], g[3]),)))])[0]
    assert out == g[:2]                     # match excluded from output
    assert eng.pages_in_use == 0            # finished → pages released


def test_stop_sequence_across_step_boundary():
    """A stop sequence whose tokens commit in different steps (decode is
    one token per step) still truncates at the match start — tokens from
    the earlier step are retracted from the output."""
    cfg, params = build()
    p = prompts_for(cfg, 4, (9,))[0]
    g = _greedy_stream(cfg, params, p, 6)
    out = serve(engine(cfg, params),
                [Request(uid=0, prompt=p, max_new=6,
                         sampling=SamplingParams(
                             stop=((g[1], g[2], g[3]),)))])[0]
    assert out == g[:1]


def test_stop_sequence_mid_speculative_commit():
    """A drafting lane can commit several tokens in one step; a stop
    completing inside the commit truncates exactly there and rolls the
    pool back clean."""
    cfg, params = build()
    pat = np.array([7, 8, 9, 7, 8, 9, 7, 8, 9, 7, 8], np.int32)
    g = _greedy_stream(cfg, params, pat, 8, speculative=True, spec_k=3)
    eng = engine(cfg, params, speculative=True, spec_k=3)
    out = serve(eng, [Request(uid=0, prompt=pat, max_new=8,
                              sampling=SamplingParams(
                                  stop=((g[2], g[3]),)))])[0]
    assert out == g[:2]
    assert eng.pages_in_use == 0


def test_stop_holdback_never_streams_a_retracted_token():
    stops = ((5, 6, 7), (9,))
    # suffix [5, 6] is a proper stop prefix → held back
    assert stop_holdback([1, 5, 6], stops) == 1
    # completing the stop is the engine's job (truncation), not holdback's
    assert stop_holdback([1, 2, 3], stops) == 3
    # single-token stops hold nothing (a hit truncates before reporting)
    assert stop_holdback([1, 2], ((9,),)) == 2


# ----------------------------------------------------------- trace shape --

def test_sampling_params_are_data_O1_compiles():
    """Serving a second wave with entirely new sampling params (new
    temperatures, seeds, top-k/top-p) retraces nothing: the params ride
    the jitted step as arrays, never as static args."""
    cfg, params = build()
    eng = engine(cfg, params)

    def wave(seed, temps):
        rs = [Request(uid=seed * 100 + i, prompt=p, max_new=4,
                      sampling=SamplingParams(
                          temperature=t,
                          top_k=None if t == 0 else 20 + seed,
                          top_p=None if t == 0 else 0.8 + 0.01 * seed,
                          seed=None if t == 0 else seed * 7 + i))
              for i, (p, t) in enumerate(
                  zip(prompts_for(cfg, seed, (5, 9, 13, 7)), temps))]
        serve(eng, rs)

    wave(1, (0.0, 0.7, 1.3, 0.0))
    traced = eng.trace_count
    assert traced > 0
    wave(2, (1.1, 0.0, 0.5, 2.0))           # all-new params, same shapes
    assert eng.trace_count == traced, (
        f"sampling params retraced the step: {traced} → {eng.trace_count}")


def test_sampling_runs_inside_ragged_step_jaxpr():
    """Walk the traced ragged step: (1) it OUTPUTS int32 tokens — no
    (lanes, V) float logits ever leave the graph, so there is no host
    round-trip between logits and token; (2) the sampling region (the
    sort-based top-k/top-p masks) operates on the (lanes, V) last-idx
    gather only — never on a (T, V) full-stream tensor."""
    from tests._jaxpr import iter_eqns, jaxpr_shapes

    cfg, params = build()
    lanes, t, pw = 3, 48, 4
    eng = engine(cfg, params, lanes=lanes, page_size=8, chunk_size=24,
                 num_pages=32)
    cu = jnp.asarray([0, 1, 2, t, t], jnp.int32)
    jaxpr = jax.make_jaxpr(eng._ragged)(
        eng.params, eng.kv.pool, jnp.full((t, pw), eng.kv.scratch, jnp.int32),
        jnp.zeros((t,), jnp.int32), jnp.zeros((t,), jnp.int32),
        jnp.zeros((lanes,), jnp.int32), cu, *_sampling_args(lanes))

    v = cfg.vocab_size
    outs = [(o.aval.shape, o.aval.dtype) for o in jaxpr.jaxpr.outvars]
    assert (outs[0] == ((lanes,), jnp.int32)), outs[0]
    assert all(s != (lanes, v) for s, _ in outs), (
        "step leaks (lanes, V) logits to the host")

    # sampling region shape: every sort in the graph runs on the
    # (lanes, V) gathered rows — none on the (T, V) packed stream
    seen = [tuple(eqn.invars[0].aval.shape)
            for eqn in iter_eqns(jaxpr.jaxpr)
            if eqn.primitive.name == "sort"]
    assert seen, "sampling region not found in the traced step"
    assert set(seen) == {(lanes, v)}, seen
    assert all(s[0] != t for s in seen)
    # and no (T, V) tensor exists anywhere (logits stay last-idx-gathered)
    assert all(s[-2:] != (t, v) for s in jaxpr_shapes(jaxpr.jaxpr)
               if len(s) >= 2)
