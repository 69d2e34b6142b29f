"""Per-request sampling: :class:`SamplingParams` + the batched in-step sampler.

The paper's thesis is that softmax — max, LUT-exp, sum, normalize — deserves
dedicated compute (UCLMs, §III-B).  Serving has a second softmax besides
attention: the sampling distribution over the vocabulary.  This module puts
that distribution *inside* the jitted ragged step, built from the same LUT
machinery (``core/lut_exp`` / ``core/lut_softmax``):

    temperature-scale → top-k mask → top-p (nucleus) mask over the
    LUT-softmax probabilities → Gumbel-max categorical draw over the
    LUT log-softmax scores

One vectorized pass over the ragged step's ``last_idx`` logits ``(lanes, V)``
— no host round-trip between logits and token.  Every parameter rides in as
*data* (per-lane arrays, never static args), so sampling params cannot cause
a retrace: the O(1)-compile guarantee of the ragged step survives unchanged.

Determinism contracts
---------------------
- **Greedy is bit-exact**: a temperature ≤ 0 lane reproduces the serving
  stack's lowest-index tie-break (``core.greedy_token``) exactly — the
  speculative verify rule and every cross-engine equivalence suite survive.
- **Batch-invariant PRNG**: lane ``i``'s draw is a pure function of its
  request's ``(seed, #generated-tokens)`` — ``fold_in(PRNGKey(seed), n)`` —
  never of the lane index, the co-batched traffic, or any engine-global key.
  A request's token stream is identical whether it runs alone, shares a step
  with seven neighbours, or resumes after preemption.  (This replaces the
  PR-2/PR-3 per-engine ``self.key`` that every sampled lane advanced: under
  that scheme a stream depended on every other request ever served.  The old
  host path survives only as :func:`sample_row`, the single-lane oracle.)
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.lut_exp import lut_exp
from repro.core.lut_softmax import NEG_INF, lut_log_softmax, lut_softmax

# In-step pick for a logit row whose max is NaN or ±inf: never a vocab id,
# so the engine can refuse the step instead of streaming a garbage token.
NONFINITE_PICK = -1


class InvalidRequest(ValueError):
    """A request that can never be served correctly, rejected at
    construction/submit (the PR-3 empty-prompt rule, generalised: never
    wedge a lane on bad input).  ``field`` names the offending parameter so
    front doors can map the rejection to a structured client error."""

    def __init__(self, field: str, detail: str, uid=None):
        self.field = field
        self.uid = uid
        who = f"request {uid}: " if uid is not None else ""
        super().__init__(f"{who}invalid {field}: {detail}")


def _as_stop(stop) -> Tuple[Tuple[int, ...], ...]:
    seqs = []
    for s in stop:
        if isinstance(s, (int, np.integer)):
            s = (s,)
        seq = tuple(int(t) for t in s)
        if not seq:
            raise InvalidRequest("stop", "empty stop sequence")
        if any(t < 0 for t in seq):
            raise InvalidRequest("stop", f"negative token id in {seq}")
        seqs.append(seq)
    return tuple(seqs)


@dataclasses.dataclass(frozen=True)
class SamplingParams:
    """Per-request sampling record, validated at construction.

    ``temperature ≤ 0`` means greedy (lowest-index tie-break).  ``top_k`` /
    ``top_p`` of ``None`` disable the respective mask.  ``seed`` (default 0)
    roots the request's private PRNG stream; two requests with the same
    seed, prompt and params produce the same tokens wherever they run.
    ``stop`` is a tuple of stop sequences (token-id tuples; a bare int is a
    one-token sequence): generation finishes when the generated tokens end
    with one, and the match is truncated from the output.  ``max_tokens``
    caps generation (folded into ``Request.max_new`` as the min)."""
    temperature: float = 0.0
    top_k: Optional[int] = None
    top_p: Optional[float] = None
    seed: Optional[int] = None
    stop: Tuple[Tuple[int, ...], ...] = ()
    max_tokens: Optional[int] = None

    def __post_init__(self):
        if self.temperature < 0.0 and self.seed is not None:
            raise InvalidRequest(
                "temperature",
                f"negative temperature ({self.temperature}) is greedy — a "
                f"seed ({self.seed}) would never be used")
        if self.top_k is not None and self.top_k <= 0:
            raise InvalidRequest("top_k", f"must be >= 1, got {self.top_k}")
        if self.top_p is not None and not 0.0 < self.top_p <= 1.0:
            raise InvalidRequest("top_p",
                                 f"must be in (0, 1], got {self.top_p}")
        if self.seed is not None and not 0 <= self.seed < 2 ** 32:
            raise InvalidRequest("seed",
                                 f"must be a uint32, got {self.seed}")
        if self.max_tokens is not None and self.max_tokens <= 0:
            raise InvalidRequest("max_tokens",
                                 f"must be >= 1, got {self.max_tokens}")
        object.__setattr__(self, "stop", _as_stop(self.stop))

    @property
    def greedy(self) -> bool:
        return self.temperature <= 0.0


def validate_stop_tokens(params: SamplingParams, vocab_size: int,
                         uid=None) -> None:
    """Submit-time half of stop validation: token ids must be inside the
    model's vocab (only the engine knows the vocab; everything else is
    checked at construction)."""
    for s in params.stop:
        bad = [t for t in s if t >= vocab_size]
        if bad:
            raise InvalidRequest(
                "stop", f"token ids {bad} outside vocab of {vocab_size}",
                uid=uid)


# ----------------------------------------------------------- stop matching
def stop_hit(tokens: Sequence[int], stop: Tuple[Tuple[int, ...], ...]
             ) -> Optional[int]:
    """If the generated ``tokens`` end with a stop sequence, return the
    truncation point (index of the match's first token); else None.  Called
    after every committed token, so a stop completed mid-way through a
    multi-token speculative commit — or across step/chunk boundaries — is
    caught at exactly the token that completes it."""
    n = len(tokens)
    for s in stop:
        ls = len(s)
        if n >= ls and tuple(tokens[n - ls:]) == s:
            return n - ls
    return None


def stop_holdback(tokens: Sequence[int], stop: Tuple[Tuple[int, ...], ...]
                  ) -> int:
    """How many of ``tokens`` are safe to stream to a client: everything
    except the longest suffix that is a proper prefix of some stop sequence
    (it might still complete next step, and a streamed token cannot be
    retracted).  Single-token stop sequences hold nothing back — a hit
    truncates before the engine ever reports the token."""
    n = len(tokens)
    hold = 0
    for s in stop:
        for length in range(min(len(s) - 1, n), 0, -1):
            if tuple(tokens[n - length:]) == s[:length]:
                hold = max(hold, length)
                break
    return n - hold


# ------------------------------------------------------- in-step sampling
def greedy_rows(logits: jax.Array) -> jax.Array:
    """(..., V) → (...,) greedy picks, *lowest* index among joint maxima —
    the exact ``core.greedy_token`` math, batched.  ``max`` is an exact
    float op, so this agrees bit-for-bit with the host-side form on the
    same logits (the speculative verify rule depends on it)."""
    v = logits.shape[-1]
    iota = jnp.arange(v, dtype=jnp.int32)
    hit = logits == jnp.max(logits, axis=-1, keepdims=True)
    return jnp.min(jnp.where(hit, iota, v), axis=-1).astype(jnp.int32)


def _request_keys(seed: jax.Array, counter: jax.Array) -> jax.Array:
    """Per-lane PRNG keys: ``fold_in(PRNGKey(seed), counter)``.  The only
    inputs are the request's own seed and its generated-token count — the
    batch-invariance root (see module doc)."""
    def one(s, n):
        return jax.random.fold_in(jax.random.PRNGKey(s), n)
    return jax.vmap(one)(jnp.asarray(seed, jnp.uint32),
                         jnp.asarray(counter, jnp.uint32))


def sample_rows(logits: jax.Array, temperature: jax.Array,
                top_k: jax.Array, top_p: jax.Array, seed: jax.Array,
                counter: jax.Array, *, exp_fn=lut_exp) -> jax.Array:
    """The batched sampling kernel: (N, V) logits + per-row params → (N,)
    int32 tokens, entirely in-graph (jit/trace safe; every param is data).

    temperature ≤ 0 rows take the greedy pick; the full pipeline for the
    rest is temperature-scale → top-k → top-p over the LUT-softmax
    distribution → Gumbel-max argmax over the LUT log-softmax scores
    (adding per-row Gumbel noise to log-probs and taking argmax IS a
    categorical draw).  ``top_k == 0`` / ``top_p == 1`` disable the masks.
    A ``lax.cond`` skips the whole pipeline when no row needs it, so
    all-greedy steps (the common serving case, and every speculative
    verify row) pay only the argmax they always did."""
    logits = jnp.asarray(logits, jnp.float32)
    n, v = logits.shape
    temperature = jnp.asarray(temperature, jnp.float32)
    greedy = greedy_rows(logits)

    def drawn(_):
        t = jnp.where(temperature > 0.0, temperature, 1.0)[:, None]
        # Max-shift BEFORE the divide: raw logits / t overflows to ±inf as
        # t → 0+, and a non-finite score poisons lut_log_softmax.  Shifted
        # scores live in [-big, 0]; any -inf from the divide itself is
        # clamped to NEG_INF.  The shift is a per-row monotone map, so
        # top-k thresholds, nucleus order and the greedy pick are the same
        # token sets.
        x = jnp.maximum(
            (logits - jnp.max(logits, axis=-1, keepdims=True)) / t, NEG_INF)
        # top-k: keep the k largest logits (k-th-largest threshold);
        # k ≥ V keeps every token — bit-identical to no mask at all
        kth = jnp.take_along_axis(
            jnp.sort(x, axis=-1),
            (v - jnp.clip(jnp.where(top_k > 0, top_k, v), 1, v))[:, None],
            axis=-1)
        x = jnp.where(x >= kth, x, NEG_INF)
        # top-p: smallest prefix of the sorted LUT-softmax distribution
        # with mass ≥ p (a token survives while the mass strictly before
        # it is < p, so the head token always does).  p == 1 must keep the
        # whole vocabulary: the cumulative sum's float rounding can reach
        # 1.0 a couple of tokens early, so the disable value is tested
        # explicitly instead of through the mass comparison.
        order = jnp.argsort(-x, axis=-1)
        probs = jnp.take_along_axis(lut_softmax(x, axis=-1, exp_fn=exp_fn),
                                    order, axis=-1)
        csum = jnp.cumsum(probs, axis=-1)
        p = jnp.clip(jnp.asarray(top_p, jnp.float32), 0.0, 1.0)[:, None]
        keep_sorted = ((csum - probs) < p) | (p >= 1.0)
        keep = jnp.zeros((n, v), bool).at[
            jnp.arange(n)[:, None], order].set(keep_sorted)
        # Gumbel-max categorical over the LUT log-softmax scores, one
        # private key per request (never a shared stream)
        scores = lut_log_softmax(x, axis=-1, where=keep, exp_fn=exp_fn)
        g = jax.vmap(lambda key: jax.random.gumbel(key, (v,), jnp.float32))(
            _request_keys(seed, counter))
        pick = jnp.argmax(scores + g, axis=-1).astype(jnp.int32)
        return jnp.where(temperature > 0.0, pick, greedy)

    return jax.lax.cond(jnp.any(temperature > 0.0), drawn,
                        lambda _: greedy, None)


def sample_in_step(logits: jax.Array, *, temperature: jax.Array,
                   top_k: jax.Array, top_p: jax.Array, seed: jax.Array,
                   counter: jax.Array, exp_fn=lut_exp) -> jax.Array:
    """The ragged step's sampling region (see ``models.lm.lm_step_ragged``).

    ``(lanes, V)`` last-idx logits → ``(lanes,)`` tokens.  The speculative
    form ``(lanes, 1+k, V)`` → ``(lanes, 1+k)``: row 0 samples with the
    lane's params, rows ≥ 1 are forced greedy — they are the verify rows,
    and the acceptance rule is argmax equality (the proposer only drafts
    for greedy lanes, so row 0 of a drafting lane is greedy too).

    A row whose max logit is not finite picks :data:`NONFINITE_PICK`."""
    finite = jnp.isfinite(jnp.max(logits, axis=-1))
    if logits.ndim == 2:
        toks = sample_rows(logits, temperature, top_k, top_p, seed, counter,
                           exp_fn=exp_fn)
        return jnp.where(finite, toks, NONFINITE_PICK)
    lanes, r, v = logits.shape
    col0 = jnp.arange(r, dtype=jnp.int32)[None, :] == 0
    t = jnp.where(col0, jnp.asarray(temperature, jnp.float32)[:, None],
                  0.0).reshape(-1)
    rep = lambda a: jnp.repeat(jnp.asarray(a), r, axis=0)   # noqa: E731
    toks = sample_rows(logits.reshape(lanes * r, v), t, rep(top_k),
                       rep(top_p), rep(seed), rep(counter), exp_fn=exp_fn)
    return jnp.where(finite, toks.reshape(lanes, r), NONFINITE_PICK)


_jit_sample_rows = jax.jit(sample_rows)


def sample_row(logits_row: jax.Array, params: SamplingParams,
               n_generated: int) -> int:
    """Single-lane host oracle: the exact in-step kernel on one (1, V) row.

    This is what remains of the old host sampling path — the padded oracle
    mode and the slot engine draw through it, so every engine shares one
    sampling semantics (and the same per-request keys: temperature > 0
    streams agree across engines up to logit-level float drift)."""
    out = _jit_sample_rows(
        jnp.asarray(logits_row, jnp.float32)[None, :],
        jnp.asarray([params.temperature], jnp.float32),
        jnp.asarray([params.top_k or 0], jnp.int32),
        jnp.asarray([1.0 if params.top_p is None else params.top_p],
                    jnp.float32),
        jnp.asarray([params.seed or 0], jnp.uint32),
        jnp.asarray([n_generated], jnp.int32))
    return int(out[0])
