"""EngineCore: one ``step()`` drives every serving phase through the pool.

The engine owns three things: the page pool (``PagedKVCache``), the
scheduler, and one jitted step function per packing mode:

- ``mode="ragged"`` (default) — the token-level packed stream

      step(params, pool, token_pages, tokens, pos, last_idx,
           cu, temperature, top_k, top_p, seed, counter)
          → (tokens (lanes,), pool)

  The scheduler flattens the step into ``T = Σ live tokens`` dense rows
  (``RaggedBatch``): lane segments abut, each token carries its own
  position and page-table row, and T is bucketed to a few widths (powers
  of two plus 3/2 midpoints) with prefill chunks trimmed to land live work
  exactly on a bucket edge.  A step with 3 decode lanes and one 64-token
  prefill chunk costs ~67 token-rows of compute — not 4 × 64, which is
  what the padded block pays.  Every scheduled row is (almost always) live
  work: the paper's never-stall-on-padding pipelining (PAPER.md §IV)
  applied to the serving batch itself.  The stream's lane boundaries
  (``cu_seqlens``, dead padding rows covered by a trailing pseudo-segment)
  ride into the step as a real compute input: the varlen kernel tiles the
  stream into q-blocks of ``block_q`` same-lane rows, so a prefill chunk
  reads each KV page once per *block*, not once per token — full-width
  steps need no padded-block special case anymore (that dispatch is
  retired; ``mode="padded"`` survives only as the equivalence oracle).
  Block shapes come from the kernel autotuner's per-(model, platform)
  table (``kernels/autotune.py``), resolved once at engine construction
  and recorded in every ``StepOutput``.

- ``mode="padded"`` — the PR-3 right-aligned ``(lanes, C)`` block

      step(params, pool, table, tokens, kv_len, q_len) → (logits, pool)

  per lane ``q_len`` live tokens ending at row ``kv_len - 1``, dead rows
  left-padding.  C is 1 for decode-only steps and ``chunk_size`` whenever
  any lane prefills.  Kept as the equivalence oracle the ragged step is
  proven against (token-identical on the same traces, float and int8).

Both modes trace O(1) step functions across arbitrary prompt-length
streams — shapes are keyed by (width bucket × power-of-two table width),
never by prompt length.

Sampling lives *inside* the jitted ragged step (``serving/sampling.py``):
the step returns per-lane int32 tokens, drawn in one vectorized pass over
the ``last_idx`` logits — temperature-scale → top-k/top-p mask → Gumbel-max
categorical over the LUT log-softmax scores — with a private PRNG key per
request, ``fold_in(PRNGKey(sampling.seed), #generated)``.  Greedy
(temperature ≤ 0) reproduces the host-side lowest-index tie-break exactly,
so the speculative verify rule and every cross-engine equivalence suite
are unchanged.  The padded oracle mode still extracts (lanes, V) logits
and draws on the host through :func:`~repro.serving.sampling.sample_row`
— the *same* kernel on one row, so both modes share one sampling
semantics.

PRNG migration (PR 8): earlier revisions advanced one per-engine
``self.key`` on every sampled lane, which made a request's stream depend
on every other request the engine had ever served (and on lane placement).
That key is gone; seeds are per-request (``SamplingParams.seed``) and the
token stream is batch-invariant — identical whether the request runs
alone, co-batched, or resumes after preemption.  The engine's ``seed``
constructor arg is accepted but unused (kept so existing callers don't
break); :func:`sample_token` survives only as the deprecated host-key
form for code that still threads its own key.
"""
from __future__ import annotations

from typing import Any, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.kernels.paged_attention.varlen import kv_block_counts
from repro.models import build_model
from repro.serving.api import (Request, RequestState, StepOutput,
                               UnsupportedCacheLayout)
from repro.serving.paged import PagedKVCache
from repro.serving.prefix_cache import RadixPrefixCache
from repro.serving.sampling import (NONFINITE_PICK, InvalidRequest,
                                    sample_row, stop_hit,
                                    validate_stop_tokens)
from repro.serving.scheduler import Scheduler
from repro.serving.spec import NGramProposer
from repro.serving.tracing import ServingObservability


def greedy_token(logits: jax.Array) -> int:
    """Deterministic greedy pick: the *lowest* index among joint maxima.

    ``argmax`` tie behaviour is backend-defined; serving promises
    reproducible token streams across engines and platforms, so exact
    logit ties break to the lowest token id explicitly.
    """
    lg = jnp.asarray(logits)
    v = lg.shape[-1]
    hit = lg == jnp.max(lg)
    return int(jnp.min(jnp.where(hit, jnp.arange(v), v)))


def greedy_tokens(logits: np.ndarray) -> np.ndarray:
    """Vectorised :func:`greedy_token` over leading axes: (..., V) → (...,).

    The speculative verify rule is *argmax equality* against this exact
    pick, row by row — max is an exact float op, so the batched numpy form
    here and the per-row jax form above agree bit-for-bit on the same
    logits, which is what makes accepted drafts token-identical to the
    sequential greedy stream.
    """
    lg = np.asarray(logits)
    v = lg.shape[-1]
    hit = lg == lg.max(axis=-1, keepdims=True)
    return np.min(np.where(hit, np.arange(v), v), axis=-1)


def sample_token(logits: jax.Array, temperature: float,
                 key: jax.Array) -> tuple:
    """Deprecated host-key sampling → (token, next key).

    This is the pre-PR-8 path: one shared key advanced per draw, which
    made token streams depend on co-batched traffic.  Engines now draw
    per-request via :func:`repro.serving.sampling.sample_row` (the
    single-lane oracle of the in-step kernel); this form is kept only for
    external callers that thread their own key.  Greedy (temperature ≤ 0)
    is still the lowest-index tie-break.
    """
    if temperature <= 0.0:
        return greedy_token(logits), key
    key, sub = jax.random.split(key)
    return int(jax.random.categorical(sub, logits / temperature)), key


class EngineCore:
    """Request-level serving engine (see module doc).

    Lifecycle: ``submit(Request)`` → repeated ``step()`` (each returns a
    :class:`StepOutput`) → finished requests accumulate in ``finished``.
    ``run()`` drains everything.  Construction raises
    :class:`~repro.serving.api.UnsupportedCacheLayout` for cache families
    that cannot page (ring-buffer sliding windows, SSM state) — serve those
    with the slot-contiguous ``ServingEngine``.
    """

    def __init__(self, cfg: ModelConfig, params: Any, *, lanes: int = 4,
                 page_size: int = 16, num_pages: int = 64,
                 chunk_size: int = 16, max_len: Optional[int] = None,
                 step_tokens: Optional[int] = None, mode: str = "ragged",
                 token_buckets: Optional[Any] = None,
                 prefix_cache: bool = False,
                 cache_pages: Optional[int] = None, seed: int = 0,
                 speculative: bool = False, spec_k: int = 4,
                 proposer: Any = None, kernel_config: Any = None,
                 mesh: Any = None, metrics: bool = True,
                 registry: Any = None, trace_ring: int = 512):
        if mode not in ("ragged", "padded"):
            raise ValueError(f"unknown EngineCore mode {mode!r}; "
                             f"expected 'ragged' or 'padded'")
        # Tensor-parallel serving (opt-in): ``mesh`` is an int device count
        # or a jax Mesh with a "model" axis.  The page pool's KV-head axis
        # is sharded across it and the ragged step runs under shard_map —
        # each device attends its head band against its local pool shard
        # and one tiled all-gather rebuilds the head axis (HASTILY's
        # reduce-and-gather; docs/architecture.md).  All host-side state —
        # scheduler, page table, free heap, refcounts, prefix cache — is
        # mesh-oblivious, and mesh 1 (or None) takes the exact
        # single-device path: no shard_map, identical jaxpr.
        self.mesh = self._resolve_mesh(mesh)
        if self.mesh is not None:
            n = self.mesh.shape["model"]
            if mode != "ragged":
                raise ValueError("mesh > 1 requires mode='ragged' (the "
                                 "padded oracle step is single-device)")
            if cfg.num_heads % n or cfg.num_kv_heads % n:
                raise ValueError(
                    f"mesh of {n} devices must divide num_heads="
                    f"{cfg.num_heads} and num_kv_heads={cfg.num_kv_heads}")
        if speculative and mode != "ragged":
            # The verify step IS the ragged step (drafted rows ride the
            # packed stream); the padded block extracts last-row logits
            # only and has no lane room for 1 + k chunks.
            raise ValueError("speculative decoding requires mode='ragged'")
        if speculative and spec_k < 1:
            raise ValueError(f"speculative decoding needs spec_k >= 1, "
                             f"got {spec_k}")
        self.cfg = cfg
        self.mode = mode
        self.model = build_model(cfg)
        if self.model.prefill_chunk_paged is None or (
                mode == "ragged" and self.model.step_ragged is None):
            # Typed like the pool's rejections so launchers can catch
            # narrowly instead of swallowing every ValueError.
            raise UnsupportedCacheLayout(
                "no_paged_step", cfg.name,
                f"the {cfg.family} family exposes no paged chunk step")
        self.params = params
        self.lanes = lanes
        self.max_len = max_len or num_pages * page_size
        # One observability bundle for the whole stack (serving/tracing.py):
        # registry + request spans + step ring + the retrace sentinel.  All
        # hooks are host-side no-ops when ``metrics=False`` (the bench's
        # overhead A/B arm); ``registry=`` lets several engines share one.
        self.obs = ServingObservability(enabled=metrics, registry=registry,
                                        ring_capacity=trace_ring)
        self.kv = PagedKVCache(self.model, num_pages, page_size,
                               obs=self.obs)
        self._pool_specs = None
        if self.mesh is not None:
            # Shard the pool's KV-head axis; page ids stay whole on every
            # device, so all host-side page accounting is untouched.
            # Params are replicated once here (not per step call).
            from jax.sharding import NamedSharding, PartitionSpec
            from repro.parallel.sharding import pool_specs, shard_tree
            self._pool_specs = pool_specs(self.kv.pool, self.mesh)
            self.kv.pool = shard_tree(self.kv.pool, self._pool_specs,
                                      self.mesh)
            self.params = jax.device_put(
                params, NamedSharding(self.mesh, PartitionSpec()))
        # Shared-prefix KV reuse (opt-in): admission probes a radix cache of
        # page-aligned token blocks and grants resident pages for the hit
        # prefix; chunked prefill then starts at the first cold token.
        # Token streams are identical with the cache on or off (the prefix
        # pages hold the exact KV the skipped chunks would have written).
        self.prefix_cache = (RadixPrefixCache(self.kv, max_pages=cache_pages,
                                              obs=self.obs)
                             if prefix_cache else None)
        # Speculative decoding (opt-in): a host-side proposer drafts up to
        # spec_k tokens per greedy decode lane; the scheduler streams the
        # drafted chunk through the same ragged step, the engine verifies
        # every drafted position against its own argmax in that one step,
        # and commit/rollback happens in _finish.  Token streams are
        # identical with speculation on or off (the acceptance rule is
        # argmax equality against the exact greedy pick).
        self.speculative = speculative
        self.spec_k = spec_k if speculative else 0
        self.proposer = (proposer if proposer is not None
                         else NGramProposer(obs=self.obs)) \
            if speculative else None
        self.scheduler = Scheduler(self.kv, lanes=lanes,
                                   chunk_size=chunk_size,
                                   step_tokens=step_tokens,
                                   token_buckets=token_buckets,
                                   prefix_cache=self.prefix_cache,
                                   spec_k=self.spec_k,
                                   proposer=self.proposer,
                                   obs=self.obs)
        # Varlen-kernel block shapes: explicit override, else the
        # autotuner's persisted per-(model, platform) table, else the
        # hardcoded default.  Static for the engine's lifetime — the jitted
        # ragged step closes over it, so swapping configs means a new
        # engine (per-engine jit caches keep old traces from leaking).
        from repro.kernels.autotune import resolve_config
        self.kernel_config = (kernel_config if kernel_config is not None
                              else resolve_config(cfg.name))
        self.chunk_size = chunk_size
        del seed   # per-request now (SamplingParams.seed); see module doc
        self.finished: List[Request] = []
        self.trace_count = 0            # step-fn retraces (compile counter)
        self.drafted_total = 0          # speculative telemetry, lifetime
        self.accepted_total = 0
        self.spec_steps = 0             # steps that carried ≥ 1 draft

        m = self.model

        def step_fn(params, pool, tbl, toks, kv_len, q_len):
            self.trace_count += 1       # python side effect: counts traces
            self.obs.step_traced()      # retrace sentinel (tracing.py)
            return m.prefill_chunk_paged(params, toks, pool, tbl,
                                         kv_len, q_len)

        kc = self.kernel_config
        tp_axis = None if self.mesh is None else "model"

        def ragged_fn(params, pool, token_pages, toks, pos, last_idx, cu,
                      temperature, top_k, top_p, seed, counter):
            self.trace_count += 1       # python side effect: counts traces
            self.obs.step_traced()      # retrace sentinel (tracing.py)
            # The five (lanes,) sampling arrays are traced data — a new
            # temperature/seed can never be a retrace key — and the step
            # returns tokens, not logits: selection happens in-graph.
            return m.step_ragged(params, toks, pool, token_pages, pos,
                                 last_idx, cu_seqlens=cu, kernel_config=kc,
                                 sampling=dict(temperature=temperature,
                                               top_k=top_k, top_p=top_p,
                                               seed=seed, counter=counter),
                                 tp_axis=tp_axis)

        if self.mesh is not None:
            # One shard_map around the whole step: pool leaves arrive as
            # local head-band shards, everything else replicated.  The
            # sampled tokens are a deterministic function of replicated
            # inputs (the all-gather rebuilt the head axis before wo), so
            # every device computes identical picks — out_specs P() is
            # sound without a check pass (the varying-manual-axes checker
            # cannot see through the kernel's custom calls).
            from jax.sharding import PartitionSpec
            rep = PartitionSpec()
            ragged_fn = jax.shard_map(
                ragged_fn, mesh=self.mesh,
                in_specs=(rep, self._pool_specs) + (rep,) * 10,
                out_specs=(rep, self._pool_specs), check_vma=False)

        # donated pool: every layer's row writes update in place instead of
        # copying the whole pool each step.
        self._step = jax.jit(step_fn, donate_argnums=(1,))
        self._ragged = (None if self.model.step_ragged is None
                        else jax.jit(ragged_fn, donate_argnums=(1,)))
        self.obs.g_mesh.set(self.mesh_size)
        self.obs.g_coll_per_tok.set(self.collective_bytes_per_token)

    @staticmethod
    def _resolve_mesh(mesh):
        """Normalise the ``mesh`` arg: None / 1 / a size-1 Mesh → None (the
        exact single-device path — no shard_map anywhere near the graph);
        an int N > 1 → a 1×N ``("model",)`` mesh over the first N devices;
        a jax Mesh with a "model" axis passes through."""
        if mesh is None:
            return None
        if isinstance(mesh, int):
            if mesh <= 1:
                return None
            if len(jax.devices()) < mesh:
                raise ValueError(
                    f"mesh of {mesh} devices requested but only "
                    f"{len(jax.devices())} visible (set XLA_FLAGS="
                    f"--xla_force_host_platform_device_count for CPU tests)")
            from repro.launch.mesh import make_mesh
            return make_mesh((mesh,), ("model",))
        if "model" not in mesh.axis_names:
            raise ValueError(f"serving mesh needs a 'model' axis, got "
                             f"{mesh.axis_names}")
        return mesh if mesh.size > 1 else None

    # ------------------------------------------------------------------ API
    def validate(self, req: Request) -> None:
        """Engine-dependent request validation (construction already checked
        everything self-contained): budget vs ``max_len``/pool, stop-token
        ids vs the vocab.  Raises :class:`InvalidRequest`; never admits.
        The async front door calls this eagerly so a bad request fails in
        the client's own context instead of mid-serve."""
        if len(req.prompt) + req.max_new > self.max_len:
            raise InvalidRequest(
                "max_new", f"prompt {len(req.prompt)} + max_new "
                f"{req.max_new} exceeds max_len {self.max_len}", uid=req.uid)
        if len(req.prompt) == 0:
            raise InvalidRequest("prompt", "empty prompt", uid=req.uid)
        validate_stop_tokens(req.sampling, self.cfg.vocab_size, uid=req.uid)

    def submit(self, req: Request) -> None:
        self.validate(req)
        self.scheduler.submit(req)

    def abort(self, uid: int) -> bool:
        """Cancel a request (client disconnect / explicit cancel).

        Waiting requests leave the queue; a mid-flight request releases its
        lane and pages *immediately* — full pages are published to the
        prefix cache first (the computed KV stays reusable), exactly the
        :meth:`Scheduler.finish` dataflow.  Returns False for unknown /
        already-finished uids.  The freed lane admits new work next step;
        an abort can never wedge a lane.
        """
        return self.scheduler.abort(uid)

    def step(self) -> StepOutput:
        """Schedule → one batched model call → sample/finish.  All phases —
        chunked prefill, decode, admission, preemption — happen here; the
        engine's ``mode`` picks the packing (ragged stream / padded block),
        the token streams are identical either way."""
        # The step number matches the step ring's record of this step; a
        # metrics-off engine counts no steps, so its spans carry none.
        obs = self.obs
        step = ({"step": int(obs.c_steps.value()) + 1} if obs.enabled
                else {})
        with obs.span("serve.step", **step) as span:
            out = (self._step_ragged() if self.mode == "ragged"
                   else self._step_padded())
        s = self.scheduler
        self.obs.record_step(
            out, dur_ms=span.seconds * 1e3,
            sched=s, kv=self.kv, cache=self.prefix_cache,
            table_pages=s._table_pages,
            trimmed_prefill=s.trimmed_prefill_step,
            trimmed_drafts=s.trimmed_draft_step,
            width=out.padded_rows)
        return out

    def _step_padded(self) -> StepOutput:
        """The PR-3 right-aligned (lanes, C) block step (oracle mode)."""
        with self.obs.span("serve.schedule"):
            plans, preempted = self.scheduler.schedule()
        return self._run_block(plans, preempted)

    def _step_ragged(self) -> StepOutput:
        """The token-level step (default mode): one packed stream, always.

        Full-width steps (all-lanes decode, all-lanes full prefill chunks)
        used to dispatch to the padded block because the varlen kernel read
        each KV page once per *token* where the block form read it once per
        chunk.  The q-block-tiled varlen dataflow closed that gap — each
        page is read once per ``block_q`` rows regardless of how ragged the
        step is — so every ragged step now runs the one varlen kernel and
        the padded block survives only as ``mode="padded"``, the
        equivalence oracle.  Token streams are identical either way.
        """
        s = self.scheduler
        with self.obs.span("serve.schedule"):
            wants = s.begin_step()
            batch, preempted = s.batch_for(wants)
        return self._run_stream(batch, preempted)

    def _run_block(self, plans, preempted) -> StepOutput:
        """Execute lane plans as one right-aligned (lanes, C) block."""
        if not plans:
            return StepOutput(
                tokens={}, finished=(), preempted=preempted, lanes=0,
                prefill_tokens=0, decode_tokens=0,
                prefix_hit_tokens=self.scheduler.prefix_hit_tokens_step)
        obs = self.obs
        c = 1 if all(p.q_len == 1 for p in plans) else self.chunk_size
        b, scratch = self.lanes, self.kv.scratch
        with obs.span("serve.upload"):
            width = max(len(p.run.pages) for p in plans)
            width = 1 << max(width - 1, 0).bit_length()  # retrace bucketing
            toks = np.zeros((b, c), np.int32)
            kv_len = np.zeros((b,), np.int32)
            q_len = np.zeros((b,), np.int32)
            tbl = np.full((b, width), scratch, np.int32)
            for i, p in enumerate(plans):
                toks[i, c - p.q_len:] = p.stream_tokens()
                kv_len[i] = p.run.rows + p.q_len
                q_len[i] = p.q_len
                tbl[i, :len(p.run.pages)] = p.run.pages
            args = (jnp.asarray(tbl), jnp.asarray(toks), jnp.asarray(kv_len),
                    jnp.asarray(q_len))
        with obs.span("serve.dispatch"):
            logits, self.kv.pool = self._step(self.params, self.kv.pool,
                                              *args)
        with obs.span("serve.wait"):
            logits = np.asarray(logits)
        with obs.span("serve.commit"):
            del args                # frees the step's input buffers here
            return self._finish(plans, preempted, logits=logits,
                                live=int(sum(p.q_len for p in plans)),
                                padded=b * c)

    def _run_stream(self, batch, preempted) -> StepOutput:
        """Execute a RaggedBatch as one packed token stream."""
        plans = batch.plans
        if not plans:
            return StepOutput(
                tokens={}, finished=(), preempted=preempted, lanes=0,
                prefill_tokens=0, decode_tokens=0,
                prefix_hit_tokens=self.scheduler.prefix_hit_tokens_step)
        obs = self.obs
        with obs.span("serve.upload"):
            # Stream index of each plan's final token; idle tail lanes
            # point at row 0 (their logits are computed but never read —
            # the (lanes, V) output shape stays static across schedules).
            # Speculative engines always pass the (lanes, 1 + spec_k) form
            # — row j of lane i is the lane's decode row plus its j-th
            # drafted row, clamped to the last real draft — so the verify
            # extraction is one static-shape gather: k stays a
            # compile-time constant and trace count stays O(1) whether a
            # step carries 0 or k drafts.
            if self.speculative:
                last_idx = np.zeros((self.lanes, self.spec_k + 1), np.int32)
                ramp = np.arange(self.spec_k + 1, dtype=np.int32)
                for i, p in enumerate(plans):
                    d = len(p.drafts)
                    base = int(batch.cu_seqlens[i + 1]) - 1 - d
                    last_idx[i] = base + np.minimum(ramp, d)
            else:
                last_idx = np.zeros((self.lanes,), np.int32)
                last_idx[:len(plans)] = batch.cu_seqlens[1:] - 1

            # Lane boundaries as a compute input, static (lanes + 2,)
            # shape: the live plans' boundaries, then the bucket's dead
            # padding rows as one trailing pseudo-segment ending at T (so
            # cu[-1] == T — the kernel's validated packing contract), then
            # zero-width repeats.
            cu = np.full((self.lanes + 2,), batch.width, np.int32)
            cu[:len(batch.cu_seqlens)] = batch.cu_seqlens
            if obs.enabled:
                kc = self.kernel_config
                obs.kv_blocks(*kv_block_counts(
                    cu, batch.pos, batch.width, block_q=kc.block_q,
                    page_size=self.kv.page_size,
                    table_width=batch.table.shape[1],
                    block_pages=kc.block_pages))
            args = (jnp.asarray(batch.table), jnp.asarray(batch.tokens),
                    jnp.asarray(batch.pos), jnp.asarray(last_idx),
                    jnp.asarray(cu), *self._sampling_inputs(plans))
        with obs.span("serve.dispatch"):
            picks, self.kv.pool = self._ragged(self.params, self.kv.pool,
                                               *args)
        with obs.span("serve.wait"):
            picks = np.asarray(picks)
            bad = [p.run.req.uid for p, row in zip(plans, picks)
                   if np.any(row == NONFINITE_PICK)]
            if bad:
                raise FloatingPointError(
                    f"non-finite logits in this step for requests {bad}")
        with obs.span("serve.commit"):
            del args                # frees the step's input buffers here
            return self._finish(plans, preempted, picks=picks,
                                live=batch.live, padded=batch.width)

    def _sampling_inputs(self, plans):
        """Per-lane sampling arrays for the in-step draw, all (lanes,).

        Idle tail lanes get temperature 0 (their greedy pick is computed
        but never read).  ``counter`` is the request's generated-token
        count — with ``seed`` it fully determines the lane's PRNG key, so
        the draw is batch-invariant and preemption-replay-stable.
        """
        n = self.lanes
        temp = np.zeros((n,), np.float32)
        top_k = np.zeros((n,), np.int32)       # 0 = off
        top_p = np.ones((n,), np.float32)      # 1 = off
        seed = np.zeros((n,), np.uint32)
        counter = np.zeros((n,), np.int32)
        for i, p in enumerate(plans):
            sp = p.run.req.sampling
            temp[i] = max(sp.temperature, 0.0)
            top_k[i] = sp.top_k or 0
            top_p[i] = 1.0 if sp.top_p is None else sp.top_p
            seed[i] = sp.seed or 0
            counter[i] = len(p.run.req.tokens)
        return (jnp.asarray(temp), jnp.asarray(top_k), jnp.asarray(top_p),
                jnp.asarray(seed), jnp.asarray(counter))

    def _finish(self, plans, preempted, *, live: int, padded: int,
                picks=None, logits=None) -> StepOutput:
        """Shared step tail: advance cursors, commit/verify, retire finished.

        The ragged step hands back ``picks`` — per-lane tokens already
        drawn in-graph, (lanes,) or (lanes, 1+k) speculative; the padded
        oracle hands back ``logits`` and each sampling lane draws on the
        host through :func:`~repro.serving.sampling.sample_row` (the same
        kernel on one row).

        Non-speculative lanes commit exactly one token.  A drafting lane
        streamed ``1 + d`` rows; rows ≥ 1 of its picks are the in-graph
        greedy verify ``g[j]`` at every drafted position, and the lane
        commits ``g[0..acc]`` where ``acc`` is the longest prefix with
        ``g[j] == drafts[j]`` — exactly the tokens sequential greedy decode
        would have produced, one step at a time.  The cursor advances by
        ``base + (committed − 1)`` — the last committed token is *new* (its
        KV row is next step's mandatory write), the earlier ones already
        have their rows from this step — and :meth:`PagedKVCache.uncommit`
        returns any page holding only rejected rows, leaving pool state
        identical to never having drafted.

        Stop sequences are checked after every committed token (so a stop
        completed mid-way through a multi-token speculative commit — or
        across step boundaries — truncates at exactly the right token):
        the match is removed from the output and the rows cursor clamps to
        the surviving known tokens, keeping the prefix-cache publish
        KV-consistent.
        """
        out_tokens = {}
        finished = []
        # Phase comes from the scheduler (remaining-known at planning), not
        # from q_len: a chunk_size=1 engine still streams *prefill* rows one
        # at a time, and only the remaining==1 step is a decode.
        n_prefill = sum(p.q_len for p in plans
                        if p.run.req.state is RequestState.PREFILL)
        n_decode = sum(1 for p in plans
                       if p.run.req.state is RequestState.DECODE)
        lg = None if logits is None else np.asarray(logits)   # (lanes, V)
        drafted = sum(len(p.drafts) for p in plans)
        accepted = 0
        for i, p in enumerate(plans):
            run, req = p.run, p.run.req
            if not p.sample:
                run.rows += p.q_len
                continue
            base = p.q_len - len(p.drafts)
            if p.drafts:
                g = picks[i, :len(p.drafts) + 1]
                acc = 0
                while acc < len(p.drafts) and int(g[acc]) == p.drafts[acc]:
                    acc += 1
                commit = [int(t) for t in g[:acc + 1]]
            elif picks is not None:
                commit = [int(picks[i, 0] if picks.ndim == 2 else picks[i])]
            else:
                commit = [sample_row(lg[i], req.sampling, len(req.tokens))]
            done = stopped = False
            n = 0
            start = len(req.tokens)
            for tok in commit:        # eos/max_new/stop can cut this short
                req.tokens.append(tok)
                out_tokens[req.uid] = tok
                n += 1
                cut = stop_hit(req.tokens, req.sampling.stop)
                if cut is not None:
                    del req.tokens[cut:]     # stop match never surfaces
                    done = stopped = True
                    break
                if (len(req.tokens) >= req.max_new
                        or (req.eos_id is not None and tok == req.eos_id)):
                    done = True
                    break
            run.rows += base + n - 1
            if stopped:
                # Truncation may have swallowed every token this step
                # committed (and, for a stop spanning steps, earlier ones —
                # which is why streaming clients hold back stop prefixes,
                # see sampling.stop_holdback).  Report the last survivor of
                # this step, or nothing; clamp the rows cursor so _publish
                # never claims rows beyond the surviving known tokens.
                if len(req.tokens) > start:
                    out_tokens[req.uid] = req.tokens[-1]
                else:
                    out_tokens.pop(req.uid, None)
                run.rows = min(run.rows, run.known())
            self.obs.tokens_committed(req.uid, n, first=(start == 0))
            if p.drafts:
                accepted += n - 1
                self.obs.spec_verify(req.uid, len(p.drafts), n - 1)
                run.pages = self.kv.uncommit(run.pages, run.rows)
            if done:
                req.done = True
                finished.append(req.uid)
                self.finished.append(req)
                if self.proposer is not None and \
                        hasattr(self.proposer, "observe"):
                    self.proposer.observe(req.known_tokens())
                self.scheduler.finish(run)
        self.drafted_total += drafted
        self.accepted_total += accepted
        if drafted:
            self.spec_steps += 1
        return StepOutput(tokens=out_tokens, finished=tuple(finished),
                          preempted=preempted, lanes=len(plans),
                          prefill_tokens=n_prefill, decode_tokens=n_decode,
                          live_rows=live, padded_rows=padded,
                          prefix_hit_tokens=(
                              self.scheduler.prefix_hit_tokens_step),
                          drafted_tokens=drafted, accepted_tokens=accepted,
                          kernel_config=(self.kernel_config.describe()
                                         if self.mode == "ragged" else None))

    def run(self, max_steps: int = 100_000) -> List[Request]:
        steps = 0
        while self.scheduler.has_work():
            self.step()
            steps += 1
            if steps > max_steps:
                raise RuntimeError("serving did not drain")
        return self.finished

    # -------------------------------------------------------- introspection
    @property
    def pages_in_use(self) -> int:
        return self.kv.num_pages - len(self.kv.free)

    @property
    def mesh_size(self) -> int:
        """Devices on the serving mesh's model axis (1 = single-device)."""
        return 1 if self.mesh is None else int(self.mesh.shape["model"])

    @property
    def collective_bytes_per_token(self) -> int:
        """Per-device bytes *received* by the step's collectives for each
        token-row streamed: one tiled head all-gather per attention layer,
        ``Hq · Dh · itemsize · (N−1)/N`` each.  Analytic (the dataflow has
        exactly this one collective), so the bench can report collective
        traffic without instrumenting the compiled step; 0 off-mesh.

        The gathered tensor is the *pre-projection attention output* — a
        float32 activation (the varlen kernel accumulates in f32 and the
        residual stream runs f32 over the narrow params), not a
        ``cfg.dtype`` value.  Pricing it at ``cfg.dtype`` was a silent 2×
        undercount on bf16 models, caught by the measured-HLO cross-check
        (:meth:`measure_collective_bytes`); casting the gather operand
        down to ``cfg.dtype`` would halve the real wire traffic but
        change sharded-vs-single-device numerics — an open ROADMAP item,
        not a bookkeeping choice."""
        n = self.mesh_size
        if n == 1:
            return 0
        per_layer = (self.cfg.num_heads * self.cfg.d_head
                     * jnp.dtype(jnp.float32).itemsize)
        return self.cfg.num_layers * per_layer * (n - 1) // n

    def compiled_step_hlo(self, width: Optional[int] = None) -> str:
        """Optimized HLO text of the ragged step, compiled ahead of time at
        ``width`` (default: the widest token bucket) and the current
        table-width high-water mark; nothing executes.  Compiling *is*
        tracing, so call this before ``obs.mark_warm()`` or the sentinel
        counts it as a retrace."""
        t = int(width or self.scheduler.token_buckets[-1])
        pw = self.scheduler._table_pages
        lanes = self.lanes
        cu = np.full((lanes + 2,), t, np.int32)
        cu[0] = 0
        last_idx = (jnp.zeros((lanes, self.spec_k + 1), jnp.int32)
                    if self.speculative else jnp.zeros((lanes,), jnp.int32))
        args = (self.params, self.kv.pool,
                jnp.full((t, pw), self.kv.scratch, jnp.int32),
                jnp.zeros((t,), jnp.int32), jnp.zeros((t,), jnp.int32),
                last_idx, jnp.asarray(cu),
                jnp.zeros((lanes,), jnp.float32),
                jnp.zeros((lanes,), jnp.int32),
                jnp.ones((lanes,), jnp.float32),
                jnp.zeros((lanes,), jnp.uint32),
                jnp.zeros((lanes,), jnp.int32))
        return self._ragged.lower(*args).compile().as_text()

    def measure_collective_bytes(self, width: Optional[int] = None) -> int:
        """*Measured* per-device collective wire bytes for one compiled
        ragged step, by walking the step's optimized HLO
        (:meth:`compiled_step_hlo`) with
        :func:`repro.launch.hlo_analysis.hlo_totals` — the cross-check for
        the analytic :attr:`collective_bytes_per_token` (measured ≈
        analytic × stream width: every packed row, live or dead, runs the
        per-layer head all-gather).  Publishes the
        ``collective_bytes_per_step`` gauge; returns 0 off-mesh.
        """
        if self.mesh is None or self._ragged is None:
            self.obs.g_coll_per_step.set(0)
            return 0
        from repro.launch.hlo_analysis import hlo_totals
        try:
            # The trunk is a lax.scan over layer periods — one while loop
            # at depth 0 whose body must be multiplied by the trip count.
            from repro.models.lm import period_layout
            _, nper, _ = period_layout(self.cfg)
            hints = [int(nper)]
        except Exception:
            hints = None
        hlo = self.compiled_step_hlo(width)
        total = int(hlo_totals(hlo, trip_hints=hints)["total_wire_bytes"])
        self.obs.g_coll_per_step.set(total)
        return total

    @property
    def prefix_stats(self) -> dict:
        """Prefix-cache telemetry (empty dict when the cache is off)."""
        return self.prefix_cache.stats() if self.prefix_cache else {}

    @property
    def spec_stats(self) -> dict:
        """Speculative-decoding telemetry (empty dict when not drafting).

        ``acceptance`` is accepted/drafted; ``accepted_per_spec_step`` is
        the extra tokens each drafting step committed beyond the one it
        would have anyway — the bench's headline number.
        """
        if not self.speculative:
            return {}
        return {
            "drafted_tokens": self.drafted_total,
            "accepted_tokens": self.accepted_total,
            "spec_steps": self.spec_steps,
            "acceptance": (self.accepted_total / self.drafted_total
                           if self.drafted_total else 0.0),
            "accepted_per_spec_step": (self.accepted_total / self.spec_steps
                                       if self.spec_steps else 0.0),
        }

    @property
    def page_tables(self) -> List[List[int]]:
        """Live page table per resident request (scheduler ticket order)."""
        return [list(r.pages) for r in self.scheduler.running]
