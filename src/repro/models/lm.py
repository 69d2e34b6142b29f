"""Decoder-LM assembly for the dense / moe / ssm / hybrid / vlm families.

A model is a stack of *periods*: one period = one cycle of ``cfg.pattern``
(e.g. gemma3's 5×local+1×global) or, for the zamba2 hybrid, ``hybrid_period``
Mamba-2 blocks preceded by the *shared* attention block (weights reused every
period — only its KV cache is per-period).  Periods are homogeneous, so the
trunk is a ``lax.scan`` over stacked period params: compile time and HLO size
stay O(period), remat applies per period, and the dry-run scales to 64-layer
configs.  Layers that don't fill a whole period form an unrolled tail.

All functions are pure; caches are explicit pytrees threaded in and out.
Attention inside every layer dispatches through the backend registry
(``core/attention_api``) keyed by ``cfg.attn_backend`` — prefill traces
resolve to the streaming/Pallas paths, single-token decode to the O(L)
naive row; no attention implementation is imported here directly.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.parallel.ctx import maybe_shard
from repro.models.mamba import mamba_apply, mamba_cache_init, mamba_init
from repro.models.mamba2 import mamba2_apply, mamba2_cache_init, mamba2_init
from repro.models.moe import moe_apply, moe_init

Params = Dict[str, Any]


# --------------------------------------------------------------------------
# layout: periods / kinds
# --------------------------------------------------------------------------

def period_layout(cfg: ModelConfig) -> Tuple[Tuple[str, ...], int, int]:
    """→ (kinds within one period, n full periods, n tail layers)."""
    if cfg.family == "hybrid":
        per = max(cfg.hybrid_period, 1)
        kinds = ("mamba",) * per
    else:
        kinds = cfg.pattern
        per = len(kinds)
    nper, tail = divmod(cfg.num_layers, per)
    return kinds, nper, tail


# --------------------------------------------------------------------------
# per-layer init / apply / cache
# --------------------------------------------------------------------------

def _layer_init(key, cfg: ModelConfig, kind: str) -> Params:
    if kind == "mamba":
        init = mamba2_init if cfg.ssm_variant == "mamba2" else mamba_init
        return {"ln": L.norm_init(cfg, cfg.d_model), "mix": init(key, cfg)}
    ks = jax.random.split(key, 2)
    p = {"ln1": L.norm_init(cfg, cfg.d_model),
         "attn": L.attn_init(ks[0], cfg),
         "ln2": L.norm_init(cfg, cfg.d_model)}
    if cfg.family == "moe":
        p["moe"] = moe_init(ks[1], cfg)
    else:
        p["mlp"] = L.mlp_init(ks[1], cfg)
    if cfg.post_block_norm:
        p["ln1_post"] = L.norm_init(cfg, cfg.d_model)
        p["ln2_post"] = L.norm_init(cfg, cfg.d_model)
    return p


def _layer_apply(cfg: ModelConfig, kind: str, p: Params, x: jax.Array, *,
                 pos: jax.Array, cache: Optional[Params],
                 cache_index: Optional[jax.Array], causal: bool,
                 page_table: Optional[jax.Array] = None,
                 q_len: Optional[jax.Array] = None,
                 token_pages: Optional[jax.Array] = None,
                 cu_seqlens: Optional[jax.Array] = None,
                 kernel_config=None,
                 tp_axis: Optional[str] = None
                 ) -> Tuple[jax.Array, Optional[Params], jax.Array]:
    aux = jnp.zeros((), jnp.float32)
    if kind == "mamba":
        apply = mamba2_apply if cfg.ssm_variant == "mamba2" else mamba_apply
        h, new_cache = apply(cfg, p["mix"], L.norm_apply(cfg, p["ln"], x),
                             cache=cache)
        return x + h, new_cache, aux
    with jax.named_scope(L.SCOPE_ATTENTION):
        a, new_cache = L.attn_apply(
            cfg, p["attn"], L.norm_apply(cfg, p["ln1"], x), kind=kind,
            pos=pos, causal=causal, cache=cache, cache_index=cache_index,
            page_table=page_table, q_len=q_len, token_pages=token_pages,
            cu_seqlens=cu_seqlens, kernel_config=kernel_config,
            tp_axis=tp_axis)
        if cfg.post_block_norm:
            a = L.norm_apply(cfg, p["ln1_post"], a)
        x = x + a
    with jax.named_scope(L.SCOPE_MLP):
        h_in = L.norm_apply(cfg, p["ln2"], x)
        if cfg.family == "moe":
            h, aux = moe_apply(cfg, p["moe"], h_in)
        else:
            h = L.mlp_apply(cfg, p["mlp"], h_in)
        if cfg.post_block_norm:
            h = L.norm_apply(cfg, p["ln2_post"], h)
        return x + h, new_cache, aux


def _layer_cache(cfg: ModelConfig, kind: str, batch: int, max_len: int
                 ) -> Params:
    if kind == "mamba":
        init = mamba2_cache_init if cfg.ssm_variant == "mamba2" else mamba_cache_init
        return init(cfg, batch)
    return L.attn_cache_init(cfg, batch, max_len, dtype=L._dtype(cfg),
                             kind=kind)


# --------------------------------------------------------------------------
# trunk
# --------------------------------------------------------------------------

def trunk_init(key, cfg: ModelConfig) -> Params:
    kinds, nper, tail = period_layout(cfg)

    def period_init(k):
        ks = jax.random.split(k, len(kinds))
        return {str(i): _layer_init(ks[i], cfg, kind)
                for i, kind in enumerate(kinds)}

    p: Params = {}
    if nper:
        p["periods"] = jax.vmap(period_init)(
            jax.random.split(jax.random.fold_in(key, 0), nper))
    if tail:
        ks = jax.random.split(jax.random.fold_in(key, 1), tail)
        p["tail"] = [_layer_init(ks[i], cfg, kinds[i % len(kinds)])
                     for i in range(tail)]
    if cfg.family == "hybrid":
        p["shared_attn"] = L.block_init(jax.random.fold_in(key, 2), cfg)
    return p


def trunk_cache_init(cfg: ModelConfig, batch: int, max_len: int) -> Params:
    kinds, nper, tail = period_layout(cfg)

    def period_cache():
        c = {str(i): _layer_cache(cfg, kind, batch, max_len)
             for i, kind in enumerate(kinds)}
        if cfg.family == "hybrid":
            c["shared"] = L.attn_cache_init(cfg, batch, max_len,
                                            dtype=L._dtype(cfg))
        return c

    c: Params = {}
    if nper:
        c["periods"] = jax.tree.map(
            lambda a: jnp.zeros((nper,) + a.shape, a.dtype), period_cache())
    if tail:
        c["tail"] = [_layer_cache(cfg, kinds[i % len(kinds)], batch, max_len)
                     for i in range(tail)]
    return c


def trunk_apply(cfg: ModelConfig, params: Params, x: jax.Array, *,
                pos: jax.Array, caches: Optional[Params] = None,
                cache_index: Optional[jax.Array] = None, causal: bool = True,
                page_table: Optional[jax.Array] = None,
                q_len: Optional[jax.Array] = None,
                token_pages: Optional[jax.Array] = None,
                cu_seqlens: Optional[jax.Array] = None,
                kernel_config=None,
                tp_axis: Optional[str] = None
                ) -> Tuple[jax.Array, Optional[Params], jax.Array]:
    kinds, nper, tail = period_layout(cfg)
    shared = params.get("shared_attn")

    def period_apply(x, pp, pc):
        # Sequence-parallel residual stream: the scan carry is what remat
        # saves per period — sharding it over (dp, sp) is what keeps grok-1
        # training in HBM (DESIGN.md §4).
        x = maybe_shard(x, ("dp", "sp", None))
        new_c: Params = {}
        aux = jnp.zeros((), jnp.float32)
        if shared is not None:
            x, sc = L.block_apply(cfg, shared, x, pos=pos, causal=causal,
                                  cache=None if pc is None else pc["shared"],
                                  cache_index=cache_index)
            if pc is not None:
                new_c["shared"] = sc
        for i, kind in enumerate(kinds):
            x, lc, a = _layer_apply(
                cfg, kind, pp[str(i)], x, pos=pos,
                cache=None if pc is None else pc[str(i)],
                cache_index=cache_index, causal=causal,
                page_table=page_table, q_len=q_len,
                token_pages=token_pages, cu_seqlens=cu_seqlens,
                kernel_config=kernel_config, tp_axis=tp_axis)
            if pc is not None:
                new_c[str(i)] = lc
            aux = aux + a
        return x, (new_c if pc is not None else None), aux

    aux_total = jnp.zeros((), jnp.float32)
    new_caches: Params = {}
    if nper:
        if caches is None:
            def body(carry, pp):
                x, aux = carry
                x, _, a = period_apply(x, pp, None)
                return (x, aux + a), None
            if cfg.remat:
                body = jax.checkpoint(body)
            (x, aux_total), _ = jax.lax.scan(body, (x, aux_total),
                                             params["periods"])
        else:
            def body(carry, xs):
                x, aux = carry
                pp, pc = xs
                x, nc, a = period_apply(x, pp, pc)
                return (x, aux + a), nc
            (x, aux_total), nc = jax.lax.scan(
                body, (x, aux_total), (params["periods"], caches["periods"]))
            new_caches["periods"] = nc
    if tail:
        new_caches["tail"] = []
        for i in range(tail):
            x, lc, a = _layer_apply(
                cfg, kinds[i % len(kinds)], params["tail"][i], x, pos=pos,
                cache=None if caches is None else caches["tail"][i],
                cache_index=cache_index, causal=causal,
                page_table=page_table, q_len=q_len,
                token_pages=token_pages, cu_seqlens=cu_seqlens,
                kernel_config=kernel_config, tp_axis=tp_axis)
            aux_total = aux_total + a
            new_caches["tail"].append(lc)
    return x, (new_caches if caches is not None else None), aux_total


# --------------------------------------------------------------------------
# full model: embed → trunk → norm → logits
# --------------------------------------------------------------------------

def lm_init(key, cfg: ModelConfig) -> Params:
    ks = jax.random.split(key, 3)
    p = {"embed": L.embed_init(ks[0], cfg),
         "trunk": trunk_init(ks[1], cfg),
         "final_norm": L.norm_init(cfg, cfg.d_model)}
    if not cfg.tie_embeddings:
        p["lm_head"] = L.dense_init(ks[2], cfg.d_model, cfg.vocab_size,
                                    dtype=L._dtype(cfg))
    return p


def lm_apply(cfg: ModelConfig, params: Params, tokens: jax.Array, *,
             prefix_embed: Optional[jax.Array] = None,
             caches: Optional[Params] = None,
             cache_index: Optional[jax.Array] = None,
             causal: bool = True,
             page_table: Optional[jax.Array] = None,
             q_len: Optional[jax.Array] = None,
             logits_rows: Optional[int] = None
             ) -> Tuple[jax.Array, Optional[Params], jax.Array]:
    """tokens (B, L) [+ optional (B, Lp, D) prefix] → logits (B, L', V).

    ``prefix_embed`` (vlm patches / audio frames) is prepended to the token
    embeddings; returned logits cover the full L' = Lp + L sequence.
    ``cache_index`` may be a (B,) vector (paged decode / chunked prefill:
    lanes at different positions) — positions then broadcast to (B, L).
    ``q_len`` (paged path only): per-lane live rows of a right-aligned block
    (see ``layers.attn_apply``).  ``logits_rows=n`` unembeds only the last
    ``n`` positions — serving steps sample one row per lane, and the (B, L,
    V) logits tensor is the largest activation in the step.
    """
    offset = jnp.asarray(0 if cache_index is None else cache_index, jnp.int32)
    lp = 0 if prefix_embed is None else prefix_embed.shape[1]
    # offset () → positions (L,); offset (B,) → per-lane positions (B, L)
    pos_tok = (offset[..., None] + lp
               + jnp.arange(tokens.shape[1], dtype=jnp.int32))
    x = L.embed_apply(cfg, params["embed"], tokens, pos_tok)
    if prefix_embed is not None:
        x = jnp.concatenate([prefix_embed.astype(x.dtype), x], axis=1)
    pos = offset[..., None] + jnp.arange(x.shape[1], dtype=jnp.int32)
    x, new_caches, aux = trunk_apply(cfg, params["trunk"], x, pos=pos,
                                     caches=caches, cache_index=cache_index,
                                     causal=causal, page_table=page_table,
                                     q_len=q_len)
    x = L.norm_apply(cfg, params["final_norm"], x)
    if logits_rows is not None:
        x = x[:, -logits_rows:]
    logits = L.unembed_apply(cfg, params["embed"], params.get("lm_head"), x)
    # Keep the vocab dim sharded through the loss (logits are the largest
    # activation: batch × seq × vocab).
    logits = maybe_shard(logits, ("dp", None, "tp"))
    return logits, new_caches, aux


# --------------------------------------------------------------------------
# steps: loss / prefill / decode
# --------------------------------------------------------------------------

def cross_entropy(logits: jax.Array, labels: jax.Array,
                  mask: Optional[jax.Array] = None) -> jax.Array:
    """CE that keeps a vocab-sharded logits tensor sharded.

    ``take_along_axis`` on a sharded vocab dim would force an all-gather of
    the (B, L, V) logits (tens of GiB/device at 4k×256); the masked-sum
    below reduces over the sharded dim instead — GSPMD turns it into a
    partial reduce + psum, and the iota==label mask fuses into the
    reduction (never materialised).
    """
    logits = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    v_iota = jax.lax.broadcasted_iota(jnp.int32, logits.shape,
                                      logits.ndim - 1)
    gold = jnp.sum(jnp.where(v_iota == labels[..., None], logits, 0.0),
                   axis=-1)
    nll = lse - gold
    if mask is not None:
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)


def lm_loss(cfg: ModelConfig, params: Params, batch: Dict[str, jax.Array]
            ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    prefix = batch.get("prefix_embed")
    logits, _, aux = lm_apply(cfg, params, batch["tokens"],
                              prefix_embed=prefix)
    lp = 0 if prefix is None else prefix.shape[1]
    tok_logits = logits[:, lp:]
    ce = cross_entropy(tok_logits[:, :-1], batch["tokens"][:, 1:],
                       batch.get("loss_mask"))
    loss = ce + cfg.router_aux_weight * aux
    return loss, {"ce": ce, "aux": aux}


def lm_prefill(cfg: ModelConfig, params: Params, tokens: jax.Array,
               caches: Params, *, prefix_embed: Optional[jax.Array] = None
               ) -> Tuple[jax.Array, Params]:
    """Fill the caches; returns (last-position logits (B, V), caches)."""
    logits, caches, _ = lm_apply(cfg, params, tokens,
                                 prefix_embed=prefix_embed, caches=caches,
                                 cache_index=jnp.zeros((), jnp.int32))
    return logits[:, -1], caches


def lm_decode_step(cfg: ModelConfig, params: Params, token: jax.Array,
                   caches: Params, index: jax.Array
                   ) -> Tuple[jax.Array, Params]:
    """One token (B,) at absolute position ``index`` → (logits (B, V), caches)."""
    logits, caches, _ = lm_apply(cfg, params, token[:, None], caches=caches,
                                 cache_index=index)
    return logits[:, -1], caches


def lm_prefill_chunk_paged(cfg: ModelConfig, params: Params,
                           tokens: jax.Array, caches: Params,
                           page_table: jax.Array, kv_len: jax.Array,
                           q_len: jax.Array) -> Tuple[jax.Array, Params]:
    """One unified serving step: a right-aligned (B, C) block of tokens per
    lane — ``q_len[b]`` live tokens ending at row ``kv_len[b] - 1``, the
    rest left-padding.  Decode lanes are ``q_len == 1``, prefill lanes carry
    a chunk of ``q_len ≤ C`` prompt tokens, idle lanes ``q_len == 0``; all
    phases share this one traced function (C ∈ {1, chunk} — shapes are
    static, so a stream of arbitrary prompt lengths compiles O(1) step
    functions instead of one per length bucket).

    Every live row's KV is written in place at its (physical page, in-page
    offset) through ``page_table`` (B, P) and attention runs through the
    table with the causal intra-chunk mask (``kernels/paged_attention``);
    padding rows write to the pool's scratch page.  No contiguous
    (B, …, n·page_size, …) cache view is ever materialised — chunked prefill
    is the same in-place dataflow as decode, which is what deletes the old
    contiguous-prefill-then-scatter copy (``write_prefill``).

    Returns (last-row logits (B, V), caches).  The last row is the lane's
    newest live token, so the caller samples from it exactly when the step
    consumed the lane's final known token.
    """
    c = tokens.shape[1]
    offset = jnp.asarray(kv_len, jnp.int32) - c        # block-start row
    logits, caches, _ = lm_apply(cfg, params, tokens, caches=caches,
                                 cache_index=offset, page_table=page_table,
                                 q_len=jnp.asarray(q_len, jnp.int32),
                                 logits_rows=1)
    return logits[:, -1], caches


def lm_step_ragged(cfg: ModelConfig, params: Params, tokens: jax.Array,
                   caches: Params, token_pages: jax.Array, pos: jax.Array,
                   last_idx: jax.Array,
                   cu_seqlens: Optional[jax.Array] = None,
                   kernel_config=None,
                   sampling: Optional[Dict[str, jax.Array]] = None,
                   tp_axis: Optional[str] = None
                   ) -> Tuple[jax.Array, Params]:
    """The token-level (ragged) serving step: one packed ``(T,)`` stream.

    Where :func:`lm_prefill_chunk_paged` runs a right-aligned ``(lanes, C)``
    block — every decode lane padded to the prefill chunk width — this step
    flattens the batch to ``T = Σ live tokens`` rows (bucketed to a few
    widths by the scheduler): a step with 3 decode lanes and one 64-token
    prefill chunk costs 67 token-rows of compute, not 4 × 64.  ``tokens``
    (T,) is the packed stream (lane segments abutting, dead rows padding
    the tail), ``pos`` (T,) each token's absolute position (rope + causal
    bound), ``token_pages`` (T, P) each token's page-table row.  Every
    token's KV row is written in place at its (physical page, offset) and
    attention runs through the per-token tables (``paged_varlen``) — no
    ``(lanes, C)``-padded intermediate exists anywhere in this graph (the
    ragged-equivalence suite walks the jaxpr to prove it).

    Logit extraction is segment-masked: only ``last_idx`` — stream indices
    into the packed ``(T,)`` rows (duplicated/zero for idle lanes) — is
    unembedded.  ``last_idx`` (lanes,) → logits (lanes, V): each lane's
    final token this step; the caller samples lane ``i`` exactly when the
    step consumed that lane's last known token.  Speculative verify passes
    ``last_idx`` (lanes, 1 + k) → logits (lanes, 1 + k, V): the lane's
    decode row plus its k drafted rows, so one forward pass yields the
    argmax at every drafted position (the gather is still O(lanes · k)
    rows, never the (T, V) tensor, and there is no per-draft loop — the
    drafted rows ride the same packed stream).

    ``cu_seqlens`` (S+1,) lane boundaries (dead padding rows covered by a
    trailing pseudo-segment so ``cu[-1] == T``) switch the attention layers
    to the q-block-tiled varlen dataflow; ``kernel_config`` (static) pins
    the autotuned block shapes.

    ``sampling`` — per-lane arrays ``{temperature, top_k, top_p, seed,
    counter}``, each ``(lanes,)`` — moves token selection *into this
    graph*: instead of (logits, caches) the step returns (tokens, caches),
    where tokens are (lanes,) int32 (or (lanes, 1+k) for speculative
    verify, rows ≥ 1 greedy).  The draw is one vectorized pass over the
    last-idx logits through the same LUT-exp/softmax machinery the
    attention layers use (``serving/sampling.sample_in_step``) — no host
    round-trip between logits and token, and the (lanes, V) tensor never
    leaves the device.  All five arrays are traced data, so sampling
    params can never trigger a retrace.

    ``tp_axis`` — mesh axis name when this step runs inside ``shard_map``
    over a KV-head-sharded page pool (``EngineCore(mesh=N)``): every
    attention layer then attends its local head band against its local
    pool shard and all-gathers the head axis (see ``layers.attn_apply``);
    embed/norms/MLP/unembed/sampling run replicated and unchanged.
    """
    p_tok = jnp.asarray(pos, jnp.int32)
    with jax.named_scope(L.SCOPE_EMBED):
        x = L.embed_apply(cfg, params["embed"], tokens[None], p_tok[None])
    x, caches, _ = trunk_apply(cfg, params["trunk"], x, pos=p_tok[None],
                               caches=caches, cache_index=None, causal=True,
                               token_pages=token_pages, cu_seqlens=cu_seqlens,
                               kernel_config=kernel_config, tp_axis=tp_axis)
    with jax.named_scope(L.SCOPE_HEAD):
        x = L.norm_apply(cfg, params["final_norm"], x)
        # (lanes,) gather BEFORE unembedding: the (T, V) logits tensor
        # would be the largest activation of the step; only lanes' last
        # rows are needed.
        idx = jnp.asarray(last_idx, jnp.int32)
        x = jnp.take(x[0], idx, axis=0)   # (lanes, D) or (lanes, 1+k, D)
        logits = L.unembed_apply(cfg, params["embed"], params.get("lm_head"),
                                 x)
        spec = ("dp", "tp") if idx.ndim == 1 else ("dp", None, "tp")
        logits = maybe_shard(logits, spec)
    if sampling is None:
        return logits, caches
    # In-step sampling: logits → tokens without leaving the graph.
    # Deferred import — repro.serving imports repro.models at module load;
    # resolving the sampler at trace time keeps the packages acyclic.
    from repro.serving.sampling import sample_in_step
    with jax.named_scope(L.SCOPE_SAMPLE):
        return sample_in_step(logits, **sampling), caches
