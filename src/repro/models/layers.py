"""Shared transformer building blocks (pure functional JAX).

Every layer is a pair of functions: ``*_init(key, cfg, ...) -> params`` and
``*_apply(cfg, params, x, ...) -> y``.  Params are plain nested dicts of
jnp arrays so they flow through jit / shard_map / checkpointing unchanged and
sharding rules can be assigned by leaf path (``parallel/sharding.py``).

Attention dispatches through the backend registry (``core/attention_api``):
``cfg.attn_backend`` names a registered implementation ("jnp", "pallas",
"ring", "naive") or "auto" to resolve per-call from device platform and call
shape.  The legacy ``cfg.attn_impl`` field keeps working via
``backend_for_config``.  The INT8-quantised KV path keeps its dedicated
entry point (different operand signature).
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.attention_api import attention, backend_for_config
from repro.core.streaming_attention import (quantize_kv_rows,
                                            streaming_attention_quantized)

Params = Dict[str, Any]

# Device regions of the serving step, as ``jax.named_scope`` names.  XLA
# keeps them in each op's metadata (``op_name``), so a profiler trace can
# name the region every device op belongs to: the embedding, per layer its
# attention (with the KV pool update inside it) and MLP, the head (final
# norm, last-row gather, unembedding) and the in-step sampler.
SCOPE_EMBED = "embed"
SCOPE_ATTENTION = "attention"
SCOPE_KV_WRITE = "kv_write"
SCOPE_MLP = "mlp"
SCOPE_HEAD = "head"
SCOPE_SAMPLE = "sample"


def _dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


# --------------------------------------------------------------------------
# dense / norms / embeddings
# --------------------------------------------------------------------------

def dense_init(key, d_in: int, d_out: int, *, bias: bool = False,
               dtype=jnp.float32, scale: Optional[float] = None) -> Params:
    scale = (d_in ** -0.5) if scale is None else scale
    p = {"w": (jax.random.normal(key, (d_in, d_out), jnp.float32) * scale
               ).astype(dtype)}
    if bias:
        p["b"] = jnp.zeros((d_out,), dtype)
    return p


def dense_apply(p: Params, x: jax.Array) -> jax.Array:
    y = jnp.einsum("...k,kn->...n", x, p["w"],
                   preferred_element_type=jnp.float32).astype(x.dtype)
    if "b" in p:
        y = y + p["b"].astype(y.dtype)
    return y


def norm_init(cfg: ModelConfig, d: int) -> Params:
    p = {"scale": jnp.ones((d,), _dtype(cfg))}
    if cfg.norm == "layernorm":
        p["bias"] = jnp.zeros((d,), _dtype(cfg))
    return p


def norm_apply(cfg: ModelConfig, p: Params, x: jax.Array) -> jax.Array:
    xf = x.astype(jnp.float32)
    if cfg.norm == "layernorm":
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + 1e-6)
        y = y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    else:  # rmsnorm (gemma-style: scale offset by 1)
        ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        y = xf * jax.lax.rsqrt(ms + 1e-6) * (1.0 + p["scale"].astype(jnp.float32))
    return y.astype(x.dtype)


def rms_head_norm(scale: jax.Array, x: jax.Array) -> jax.Array:
    """Per-head RMS norm on q/k (gemma3 qk_norm).  x: (B, H, L, Dh)."""
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(jnp.square(xf), -1, keepdims=True) + 1e-6)
    return (y * (1.0 + scale.astype(jnp.float32))).astype(x.dtype)


def embed_init(key, cfg: ModelConfig) -> Params:
    p = {"tokens": (jax.random.normal(key, (cfg.vocab_size, cfg.d_model),
                                      jnp.float32) * 0.02).astype(_dtype(cfg))}
    if cfg.pos_embedding == "learned":
        p["positions"] = (jax.random.normal(
            jax.random.fold_in(key, 1), (cfg.max_position, cfg.d_model),
            jnp.float32) * 0.02).astype(_dtype(cfg))
    return p


def embed_apply(cfg: ModelConfig, p: Params, tokens: jax.Array,
                pos: jax.Array) -> jax.Array:
    x = jnp.take(p["tokens"], tokens, axis=0)
    if cfg.embed_scale:
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
    if cfg.pos_embedding == "learned":
        x = x + jnp.take(p["positions"], pos, axis=0)
    return x


def unembed_apply(cfg: ModelConfig, embed_p: Params, head_p: Optional[Params],
                  x: jax.Array) -> jax.Array:
    """Final logits; tied → reuse the token table.  Applies gemma final softcap."""
    if cfg.tie_embeddings or head_p is None:
        logits = jnp.einsum("...d,vd->...v", x, embed_p["tokens"],
                            preferred_element_type=jnp.float32)
    else:
        logits = jnp.einsum("...d,dv->...v", x, head_p["w"],
                            preferred_element_type=jnp.float32)
    if cfg.final_softcap:
        logits = cfg.final_softcap * jnp.tanh(logits / cfg.final_softcap)
    return logits


# --------------------------------------------------------------------------
# RoPE
# --------------------------------------------------------------------------

def rope_apply(x: jax.Array, pos: jax.Array, theta: float) -> jax.Array:
    """Rotary embedding.  x: (B, H, L, D); pos: (L,) absolute positions, or
    (B, L) when lanes sit at different positions (batched paged decode)."""
    d = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = pos.astype(jnp.float32)[..., :, None] * freqs   # (…, L, D/2)
    if angles.ndim == 3:
        angles = angles[:, None]                             # (B, 1, L, D/2)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = x[..., 0::2].astype(jnp.float32), x[..., 1::2].astype(jnp.float32)
    out = jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


# --------------------------------------------------------------------------
# attention block
# --------------------------------------------------------------------------

def attn_init(key, cfg: ModelConfig) -> Params:
    ks = jax.random.split(key, 5)
    d, dh = cfg.d_model, cfg.d_head
    dt = _dtype(cfg)
    p = {
        "wq": dense_init(ks[0], d, cfg.num_heads * dh, bias=cfg.attn_bias, dtype=dt),
        "wk": dense_init(ks[1], d, cfg.num_kv_heads * dh, bias=cfg.attn_bias, dtype=dt),
        "wv": dense_init(ks[2], d, cfg.num_kv_heads * dh, bias=cfg.attn_bias, dtype=dt),
        "wo": dense_init(ks[3], cfg.num_heads * dh, d, dtype=dt),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((dh,), dt)
        p["k_norm"] = jnp.ones((dh,), dt)
    return p


def _heads(x: jax.Array, n: int) -> jax.Array:
    b, l, hd = x.shape
    return x.reshape(b, l, n, hd // n).transpose(0, 2, 1, 3)  # (B,H,L,Dh)


def attn_apply(cfg: ModelConfig, p: Params, x: jax.Array, *,
               kind: str = "global",
               pos: jax.Array,
               causal: bool = True,
               cache: Optional[Params] = None,
               cache_index: Optional[jax.Array] = None,
               page_table: Optional[jax.Array] = None,
               q_len: Optional[jax.Array] = None,
               token_pages: Optional[jax.Array] = None,
               cu_seqlens: Optional[jax.Array] = None,
               kernel_config=None,
               tp_axis: Optional[str] = None,
               xkv: Optional[jax.Array] = None,
               ) -> Tuple[jax.Array, Optional[Params]]:
    """One attention layer.

    ``pos``: (L,) absolute positions of the query rows ((B, L) when lanes
    decode at different positions — the paged path).
    ``cache``: {"k","v"} of shape (B, Hkv, Lmax, Dh) for decode; new K/V rows
    are written at ``cache_index`` and attention runs against the whole cache
    with ``kv_len = cache_index + L``.
    ``page_table``: (B, P) physical-page table — ``cache`` leaves are then
    *page pools* (num_pages, Hkv, page_size, Dh) shared by all lanes and
    ``cache_index`` is the (B,) absolute row of the block's first query (so
    ``kv_len = cache_index + L``).  Each live row's K/V is written straight
    into its physical page and attention runs in place through the table (no
    gathered contiguous cache view): L == 1 is decode, L > 1 a chunked
    prefill block.
    ``q_len``: (B,) live rows per lane in a right-aligned paged block (rows
    before ``L - q_len`` are padding: their writes land on the pool's
    scratch page and their outputs are garbage the caller never reads).
    ``None`` means every row is live (the decode path).
    ``token_pages``: (T, P) per-token page-table rows — switches the paged
    path to the *ragged* packed-stream convention: x is one ``(1, T,
    d_model)`` stream of live tokens from many lanes (no per-lane padding),
    ``pos`` carries each token's absolute position (1, T), each token's KV
    row is written at its own (page, offset) and attention runs through the
    per-token table with per-token causal bounds (``paged_varlen``).  Dead
    rows (stream padding to the bucket width) carry an all-scratch table
    row; their writes land on the scratch page, their outputs are garbage
    the caller never reads.
    ``cu_seqlens``: (S+1,) ragged-stream lane boundaries — enables the
    q-block-tiled varlen dataflow (each KV page read once per q-block);
    ``kernel_config``: the autotuned ``KernelConfig`` block shapes (static;
    ``None`` consults the autotuner's active config).
    ``tp_axis``: mesh axis name when this apply runs *inside shard_map*
    over KV-head-sharded page pools (the tensor-parallel ragged step).
    The residual stream, params and projections stay replicated; this
    layer slices its own contiguous head band (rope/qk_norm are per-head,
    so slicing after them is bit-identical to projecting the band alone),
    writes the band's KV rows into the local pool shard, attends over
    local heads only, and rebuilds the full head axis with one tiled
    all-gather before ``wo``.  Ragged (``token_pages``) path only.
    ``xkv``: cross-attention source (encoder output); disables cache/rope-k.
    """
    b, l, _ = x.shape
    q = _heads(dense_apply(p["wq"], x), cfg.num_heads)
    kv_src = x if xkv is None else xkv
    k = _heads(dense_apply(p["wk"], kv_src), cfg.num_kv_heads)
    v = _heads(dense_apply(p["wv"], kv_src), cfg.num_kv_heads)

    if cfg.qk_norm:
        q = rms_head_norm(p["q_norm"], q)
        k = rms_head_norm(p["k_norm"], k)

    window = cfg.window if kind == "local" else None
    theta = cfg.rope_theta
    if kind == "local" and cfg.local_rope_theta is not None:
        theta = cfg.local_rope_theta

    if cfg.pos_embedding == "rope" and xkv is None:
        q = rope_apply(q, pos, theta)
        k = rope_apply(k, pos, theta)
    elif cfg.pos_embedding == "rope":
        q = rope_apply(q, pos, theta)
        k = rope_apply(k, jnp.arange(k.shape[2], dtype=jnp.int32), theta)

    scale_default = cfg.attn_scale if cfg.attn_scale else cfg.d_head ** -0.5
    if cache is not None and (token_pages is not None
                              or page_table is not None):
        # Paged attention, two packings over one write path.  Cache leaves
        # are page pools; every live row's K/V is written in place at its
        # (physical page, in-page offset) and attention reads through the
        # tables — no gathered (B, …, P·ps, …) view exists.
        #
        # - padded block (`page_table` (B, P)): right-aligned rows at
        #   absolute positions cache_index + i; L == 1 is decode, L > 1 a
        #   chunked-prefill block; rows before L - q_len are padding.
        # - ragged stream (`token_pages` (T, P)): x is ONE (1, T, d) packed
        #   stream of live tokens from many lanes, each with its own
        #   position (causal bound) and page-table row.  Intra-chunk
        #   causality holds because a chunk's rows are written before the
        #   attend; cross-lane isolation because a token's table row names
        #   only its own lane's pages.  Dead bucket-padding rows carry an
        #   all-scratch table row.
        assert xkv is None, "paged attention has no cross-attention path"
        # Tensor-parallel ragged step: the local pool shard's head count
        # tells us the shard factor statically; the device index only feeds
        # a dynamic_slice start.
        shards = 1
        if tp_axis is not None:
            assert token_pages is not None, \
                "tp_axis is only supported on the ragged (token_pages) path"
            hkv_local = cache["k"].shape[1]
            shards = cfg.num_kv_heads // hkv_local
        if shards > 1:
            hq_local = cfg.num_heads // shards
            band = jax.lax.axis_index(tp_axis)
            q = jax.lax.dynamic_slice_in_dim(q, band * hq_local, hq_local, 1)
            k = jax.lax.dynamic_slice_in_dim(k, band * hkv_local,
                                             hkv_local, 1)
            v = jax.lax.dynamic_slice_in_dim(v, band * hkv_local,
                                             hkv_local, 1)
        ps = cache["k"].shape[2]
        scratch = cache["k"].shape[0] - 1               # pool's sink page
        if token_pages is not None:
            p_tok = jnp.asarray(pos, jnp.int32).reshape(-1)     # (T,)
            slot = jnp.clip(p_tok // ps, 0, token_pages.shape[1] - 1)
            pids = jnp.take_along_axis(token_pages, slot[:, None], axis=1).T
            off = (p_tok % ps)[None]                    # (1, T) like pids
        else:
            idx = jnp.asarray(cache_index, jnp.int32)   # (B,) block start
            kv_len = idx + l
            rows = idx[:, None] + jnp.arange(l, dtype=jnp.int32)[None]
            if q_len is None:
                live = jnp.ones(rows.shape, bool)       # decode: all rows
            else:
                live = (jnp.arange(l, dtype=jnp.int32)[None]
                        >= l - jnp.asarray(q_len, jnp.int32)[:, None])
            # Padding rows (and their possibly-negative positions) must
            # never touch a live page: clamp the table lookup, then route
            # them to the scratch page (masked by kv_len on every read).
            slot = jnp.clip(rows // ps, 0, page_table.shape[1] - 1)
            pids = jnp.where(live,
                             jnp.take_along_axis(page_table, slot, axis=1),
                             scratch)                   # (B, L)
            off = rows % ps

        def put(pool, val):
            # val (B, Hkv, L, …) → rows-major (B, L, Hkv, …); the advanced
            # (B, L) page/offset indices scatter one row at a time — the
            # transient is O(B·L), never the (B, P·ps, …) gathered view.
            # (Ragged: B == 1, L == T, indices shaped (1, T).)
            with jax.named_scope(SCOPE_KV_WRITE):
                return pool.at[pids, :, off].set(
                    jnp.moveaxis(val, 2, 1).astype(pool.dtype))

        attn_kw = dict(scale=scale_default, cap=cfg.attn_softcap,
                       window=window, exp_mode=cfg.exp_mode)
        if "ks" in cache:                    # INT8 pool: values + row scales
            kq_new, ks_new = quantize_kv_rows(k)
            vq_new, vs_new = quantize_kv_rows(v)
            new_cache = {
                "k": put(cache["k"], kq_new), "v": put(cache["v"], vq_new),
                "ks": put(cache["ks"], ks_new), "vs": put(cache["vs"], vs_new),
            }
            from repro.kernels.paged_attention import (
                paged_attention, paged_attention_varlen)
            attn_kw.update(k_scale=new_cache["ks"], v_scale=new_cache["vs"])
            if token_pages is not None:
                from repro.kernels.autotune import active_config
                kc = (kernel_config if kernel_config is not None
                      else active_config())
                out = paged_attention_varlen(
                    jnp.moveaxis(q[0], 1, 0), new_cache["k"], new_cache["v"],
                    token_pages, p_tok, cu_seqlens=cu_seqlens,
                    block_q=kc.block_q, block_pages=kc.block_pages,
                    dequant=kc.dequant, **attn_kw)      # (T, Hq', Dh)
                out = jnp.moveaxis(out, 0, 1)[None]     # (1, Hq', T, Dh)
                if shards > 1:
                    out = jax.lax.all_gather(out, tp_axis, axis=1,
                                             tiled=True)
            else:
                out = paged_attention(q, new_cache["k"], new_cache["v"],
                                      page_table, kv_len, **attn_kw)
        else:
            new_cache = {"k": put(cache["k"], k), "v": put(cache["v"], v)}
            conv = (dict(q_pos=p_tok, page_table=token_pages,
                         cu_seqlens=cu_seqlens, kernel_config=kernel_config)
                    if token_pages is not None
                    else dict(kv_len=kv_len, page_table=page_table))
            if shards > 1:
                conv["axis_name"] = tp_axis     # varlen backend all-gathers
            out = attention(q, new_cache["k"], new_cache["v"],
                            backend=backend_for_config(cfg.attn_backend,
                                                       cfg.attn_impl),
                            causal=causal, block_k=cfg.block_k,
                            fallback=True, **attn_kw, **conv)
        out = out.transpose(0, 2, 1, 3).reshape(b, l,
                                                cfg.num_heads * cfg.d_head)
        return dense_apply(p["wo"], out), new_cache

    new_cache = None
    q_offset = 0
    kv_len = None
    kv_pos = None
    if cache is not None and "pos" in cache:
        # Ring-buffer sliding-window cache (local layers at long context):
        # capacity Lc == window; slot = position mod Lc; cache["pos"] tracks
        # each slot's absolute position (-1 = never written).  Prefill (l > 1,
        # assumes an empty cache) attends within the chunk and then writes the
        # last Lc rows; decode (l == 1) writes then attends against the ring.
        idx = jnp.asarray(cache_index, jnp.int32)
        lc = cache["k"].shape[2]
        if l == 1:
            # decode: one ring slot — dynamic_update_slice is shard-local,
            # whereas a traced-index scatter costs a collective-permute of
            # the whole cache under GSPMD (§Perf pair 3).
            slot = idx % lc
            kc = jax.lax.dynamic_update_slice(
                cache["k"], k.astype(cache["k"].dtype), (0, 0, slot, 0))
            vc = jax.lax.dynamic_update_slice(
                cache["v"], v.astype(cache["v"].dtype), (0, 0, slot, 0))
            pc = jax.lax.dynamic_update_slice(
                cache["pos"], jnp.broadcast_to(idx, (b, 1)).astype(jnp.int32),
                (0, slot))
        else:
            keep = min(l, lc)
            pos_keep = idx + l - keep + jnp.arange(keep, dtype=jnp.int32)
            slots = pos_keep % lc
            kc = cache["k"].at[:, :, slots].set(
                k[:, :, l - keep:].astype(cache["k"].dtype))
            vc = cache["v"].at[:, :, slots].set(
                v[:, :, l - keep:].astype(cache["v"].dtype))
            pc = cache["pos"].at[:, slots].set(pos_keep[None, :])
        new_cache = {"k": kc, "v": vc, "pos": pc}
        if l == 1:
            k, v = kc, vc
            kv_pos = pc
        q_offset = idx
    elif cache is not None and "ks" in cache:
        # INT8-quantised KV cache (cfg.kv_quant): rows are quantised on
        # write, the resident cache stays int8 + per-row f32 scales, and
        # attention dequantises block-by-block inside its scan.
        idx = jnp.asarray(cache_index, jnp.int32)
        kq_new, ks_new = quantize_kv_rows(k)
        vq_new, vs_new = quantize_kv_rows(v)
        kc = jax.lax.dynamic_update_slice(cache["k"], kq_new, (0, 0, idx, 0))
        vc = jax.lax.dynamic_update_slice(cache["v"], vq_new, (0, 0, idx, 0))
        ks = jax.lax.dynamic_update_slice(cache["ks"], ks_new, (0, 0, idx))
        vs = jax.lax.dynamic_update_slice(cache["vs"], vs_new, (0, 0, idx))
        new_cache = {"k": kc, "v": vc, "ks": ks, "vs": vs}
        scale = cfg.attn_scale if cfg.attn_scale else cfg.d_head ** -0.5
        out = streaming_attention_quantized(
            q, kc, vc, ks, vs, scale=scale, causal=causal and xkv is None,
            window=window, cap=cfg.attn_softcap, block_k=cfg.block_k,
            exp_mode=cfg.exp_mode, q_offset=idx, kv_len=idx + l)
        out = out.transpose(0, 2, 1, 3).reshape(b, l,
                                                cfg.num_heads * cfg.d_head)
        return dense_apply(p["wo"], out), new_cache
    elif cache is not None:
        idx = jnp.asarray(cache_index, jnp.int32)
        kc = jax.lax.dynamic_update_slice(
            cache["k"], k.astype(cache["k"].dtype), (0, 0, idx, 0))
        vc = jax.lax.dynamic_update_slice(
            cache["v"], v.astype(cache["v"].dtype), (0, 0, idx, 0))
        new_cache = {"k": kc, "v": vc}
        k, v = kc, vc
        q_offset = idx
        kv_len = idx + l

    scale = cfg.attn_scale if cfg.attn_scale else cfg.d_head ** -0.5
    # Registry dispatch: fallback=True degrades an explicit backend that
    # cannot serve this call (e.g. "pallas" on the traced-length cached
    # decode path) to auto resolution instead of raising mid-trace.
    out = attention(q, k, v,
                    backend=backend_for_config(cfg.attn_backend,
                                               cfg.attn_impl),
                    scale=scale, causal=causal and xkv is None, window=window,
                    cap=cfg.attn_softcap, block_k=cfg.block_k,
                    exp_mode=cfg.exp_mode, q_offset=q_offset, kv_len=kv_len,
                    kv_pos=kv_pos, fallback=True)

    out = out.transpose(0, 2, 1, 3).reshape(b, l, cfg.num_heads * cfg.d_head)
    return dense_apply(p["wo"], out), new_cache


def attn_cache_init(cfg: ModelConfig, batch: int, max_len: int,
                    dtype=jnp.bfloat16, kind: str = "global") -> Params:
    """KV cache.  Local layers at long context get a ring buffer of capacity
    ``window`` (O(window) memory instead of O(max_len)) with per-slot absolute
    positions — the cache-side statement of HASTILY's O(l)→O(1) streaming."""
    if kind == "local" and cfg.window is not None and cfg.window < max_len:
        lc = cfg.window
        shape = (batch, cfg.num_kv_heads, lc, cfg.d_head)
        return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype),
                "pos": jnp.full((batch, lc), -1, jnp.int32)}
    shape = (batch, cfg.num_kv_heads, max_len, cfg.d_head)
    if cfg.kv_quant:
        # INT8 cache: 2× (vs bf16) / 4× (vs f32) smaller resident state.
        return {"k": jnp.zeros(shape, jnp.int8),
                "v": jnp.zeros(shape, jnp.int8),
                "ks": jnp.zeros(shape[:3], jnp.float32),
                "vs": jnp.zeros(shape[:3], jnp.float32)}
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


# --------------------------------------------------------------------------
# MLP
# --------------------------------------------------------------------------

_ACTS = {"silu": jax.nn.silu, "gelu": jax.nn.gelu, "relu": jax.nn.relu}


def mlp_init(key, cfg: ModelConfig, d_ff: Optional[int] = None) -> Params:
    d, f = cfg.d_model, d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    dt = _dtype(cfg)
    p = {"up": dense_init(ks[0], d, f, bias=cfg.attn_bias and not cfg.mlp_gated, dtype=dt),
         "down": dense_init(ks[1], f, d, bias=cfg.attn_bias and not cfg.mlp_gated, dtype=dt)}
    if cfg.mlp_gated:
        p["gate"] = dense_init(ks[2], d, f, dtype=dt)
    return p


def mlp_apply(cfg: ModelConfig, p: Params, x: jax.Array) -> jax.Array:
    act = _ACTS[cfg.act]
    h = dense_apply(p["up"], x)
    if cfg.mlp_gated:
        h = act(dense_apply(p["gate"], x)) * h
    else:
        h = act(h)
    return dense_apply(p["down"], h)


# --------------------------------------------------------------------------
# transformer block (pre-norm or BERT post-norm; optional gemma post norms)
# --------------------------------------------------------------------------

def block_init(key, cfg: ModelConfig) -> Params:
    ks = jax.random.split(key, 2)
    p = {"ln1": norm_init(cfg, cfg.d_model),
         "attn": attn_init(ks[0], cfg),
         "ln2": norm_init(cfg, cfg.d_model),
         "mlp": mlp_init(ks[1], cfg)}
    if cfg.post_block_norm:
        p["ln1_post"] = norm_init(cfg, cfg.d_model)
        p["ln2_post"] = norm_init(cfg, cfg.d_model)
    return p


def block_apply(cfg: ModelConfig, p: Params, x: jax.Array, *,
                kind: str = "global", pos: jax.Array, causal: bool = True,
                cache: Optional[Params] = None,
                cache_index: Optional[jax.Array] = None,
                ) -> Tuple[jax.Array, Optional[Params]]:
    if cfg.postnorm:  # BERT: sublayer → add → LN
        a, new_cache = attn_apply(cfg, p["attn"], x, kind=kind, pos=pos,
                                  causal=causal, cache=cache,
                                  cache_index=cache_index)
        x = norm_apply(cfg, p["ln1"], x + a)
        x = norm_apply(cfg, p["ln2"], x + mlp_apply(cfg, p["mlp"], x))
        return x, new_cache
    a, new_cache = attn_apply(cfg, p["attn"], norm_apply(cfg, p["ln1"], x),
                              kind=kind, pos=pos, causal=causal, cache=cache,
                              cache_index=cache_index)
    if cfg.post_block_norm:
        a = norm_apply(cfg, p["ln1_post"], a)
    x = x + a
    h = mlp_apply(cfg, p["mlp"], norm_apply(cfg, p["ln2"], x))
    if cfg.post_block_norm:
        h = norm_apply(cfg, p["ln2_post"], h)
    return x + h, new_cache
