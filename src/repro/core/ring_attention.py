"""Inter-chip streaming attention — the distributed form of HASTILY §IV.

``ring_attention``: the KV sequence is sharded across a mesh axis; KV blocks flow
around the ring via ``ppermute`` while each chip's Q stays resident.  This is the
paper's fine-grained pipeline lifted one level: the "vector fed through the
pipeline" is a KV shard travelling the ICI ring, and the online max/sum rescale is
the same associative combine that makes the paper's row pipeline legal.  Because
compute on block *r* overlaps the permute of block *r+1* (XLA schedules ppermute
async), the collective cost hides behind the matmuls — the paper's
"concurrent execution of logit calculation and softmax" in ICI form.

``distributed_decode_attention``: one new token attends to a KV cache sharded over
a mesh axis (the ``long_500k`` cell).  Each shard produces partial (m, Σexp, acc)
and the partials are tree-combined — *literally* the paper's multi-core softmax
gather (§III-B2, Fig. 5), with chips as cores.

Both are ``shard_map`` bodies: call them with the relevant operands sharded over
``axis_name``.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.lut_exp import lut_exp
from repro.core.lut_softmax import NEG_INF, softcap
from repro.core.streaming_attention import _EXP_FNS, _split_heads


def ring_attention(q: jax.Array, k: jax.Array, v: jax.Array, axis_name: str, *,
                   scale: Optional[float] = None, causal: bool = False,
                   window: Optional[int] = None, cap: Optional[float] = None,
                   exp_mode: str = "lut") -> jax.Array:
    """Ring attention over a sequence-sharded KV.  Shapes are per-shard:

    q: (B, Hq, Lq_loc, D), k/v: (B, Hkv, Lkv_loc, D).  Device i owns global rows
    [i·Lq_loc, (i+1)·Lq_loc).  Returns the local (B, Hq, Lq_loc, D) output.
    """
    n = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    b, hq, lq, d = q.shape
    hkv, lkv = k.shape[1], k.shape[2]
    if scale is None:
        scale = d ** -0.5
    exp_fn = _EXP_FNS[exp_mode]
    qg = _split_heads(q.astype(jnp.float32), hkv)
    q_pos = idx * lq + jnp.arange(lq, dtype=jnp.int32)
    fwd = [(i, (i + 1) % n) for i in range(n)]

    def body(carry, r):
        m, l, acc, k_blk, v_blk = carry
        src = (idx - r) % n  # original owner of the block currently resident
        kv_pos = src * lkv + jnp.arange(lkv, dtype=jnp.int32)
        s = jnp.einsum("bhgqd,bhkd->bhgqk", qg, k_blk,
                       preferred_element_type=jnp.float32) * scale
        s = softcap(s, cap)
        mask = jnp.ones((lq, lkv), bool)
        if causal:
            mask &= kv_pos[None, :] <= q_pos[:, None]
        if window is not None:
            mask &= (q_pos[:, None] - kv_pos[None, :]) < window
        s = jnp.where(mask[None, None, None], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.where(mask[None, None, None], exp_fn(s - m_new[..., None]), 0.0)
        alpha = exp_fn(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bhgqk,bhkd->bhgqd", p, v_blk, preferred_element_type=jnp.float32)
        # Rotate the KV shard one hop; overlaps with the next step's compute.
        k_blk = jax.lax.ppermute(k_blk, axis_name, fwd)
        v_blk = jax.lax.ppermute(v_blk, axis_name, fwd)
        return (m_new, l_new, acc_new, k_blk, v_blk), None

    # init derives from the (axis-varying) operands so shard_map's
    # varying-manual-axes check sees consistent carry types
    init = (jnp.full_like(qg[..., 0], NEG_INF),
            jnp.zeros_like(qg[..., 0]),
            jnp.zeros_like(qg),
            k.astype(jnp.float32), v.astype(jnp.float32))
    (m, l, acc, _, _), _ = jax.lax.scan(
        jax.checkpoint(body), init, jnp.arange(n, dtype=jnp.int32))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.reshape(b, hq, lq, d).astype(q.dtype)


def distributed_decode_attention(q: jax.Array, k_cache: jax.Array,
                                 v_cache: jax.Array, axis_name: str, *,
                                 kv_len: jax.Array, scale: Optional[float] = None,
                                 window: Optional[int] = None,
                                 cap: Optional[float] = None,
                                 exp_mode: str = "lut") -> jax.Array:
    """One-token decode against a sequence-sharded KV cache (paper Fig. 5 gather).

    q: (B, Hq, 1, D) replicated over ``axis_name``; caches (B, Hkv, Lloc, D)
    sharded on L.  ``kv_len`` is the *global* number of valid cache rows.
    Returns the replicated (B, Hq, 1, D) attention output.
    """
    n = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    b, hq, lq, d = q.shape
    hkv, lloc = k_cache.shape[1], k_cache.shape[2]
    if scale is None:
        scale = d ** -0.5
    exp_fn = _EXP_FNS[exp_mode]
    qg = _split_heads(q.astype(jnp.float32), hkv)
    kv_pos = idx * lloc + jnp.arange(lloc, dtype=jnp.int32)
    q_pos = kv_len - 1  # the new token's absolute position

    s = jnp.einsum("bhgqd,bhkd->bhgqk", qg, k_cache.astype(jnp.float32),
                   preferred_element_type=jnp.float32) * scale
    s = softcap(s, cap)
    mask = kv_pos < kv_len
    if window is not None:
        mask &= (q_pos - kv_pos) < window
    s = jnp.where(mask[None, None, None, None, :], s, NEG_INF)

    # --- the multi-core softmax: local partials + tree gather across chips ---
    m_loc = jnp.max(s, axis=-1)
    m = jax.lax.pmax(m_loc, axis_name)                    # tree max (O(log n))
    p = jnp.where(mask[None, None, None, None, :],
                  exp_fn(s - m[..., None]), 0.0)
    l_loc = jnp.sum(p, axis=-1)
    acc_loc = jnp.einsum("bhgqk,bhkd->bhgqd", p, v_cache.astype(jnp.float32),
                         preferred_element_type=jnp.float32)
    l = jax.lax.psum(l_loc, axis_name)                    # tree sum (O(log n))
    acc = jax.lax.psum(acc_loc, axis_name)
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.reshape(b, hq, lq, d).astype(q.dtype)
