"""Varlen (ragged) paged attention: one packed token stream, no lane padding.

The serving step used to be a right-aligned ``(lanes, C)`` block — every
decode lane paid ``C`` rows of padding whenever any lane prefilled.  The
ragged step flattens the batch into one dense stream of ``T = Σ live
tokens`` rows:

    q            (T, Hq, D)     packed query rows, lane segments abutting
    token_pages  (T, P)         each token's *own* page-table row (its
                                lane's pages; dead/padding rows all-scratch)
    q_pos        (T,)           each token's absolute position — which is
                                also its causal bound: token t attends
                                pool rows at positions ``0 .. q_pos[t]``
    cu_seqlens   (S+1,)         lane boundaries (cumulative token counts);
                                with ``block_q > 1`` this is a real compute
                                input — it derives the q-block tiling below.

Two dataflows share this entry point:

**batch = T (untiled).**  The original identity: a packed token is exactly a
one-row lane whose page table is its lane's row and whose live length is
``q_pos + 1`` — paged decode at batch = T.  Correct, but a prefill chunk of
L tokens in one lane reads that lane's KV pages **L times** (once per
token-row of the grid).

**q-block tiled (``block_q = Bq > 1``, needs ``cu_seqlens``).**  The packed
stream is cut into q-blocks of up to ``Bq`` *contiguous same-lane* rows
(lane boundaries from ``cu_seqlens`` — a block never straddles a lane).
Each block becomes one lane of a ``(NB, Hq, Bq, D)`` chunked-prefill call:
its page-table row is the lane's row, its ``kv_len`` is
``q_pos[start] + Bq`` so kernel row ``i`` sits at position
``q_pos[start] + i`` — exactly the packed positions, because serving packs
each lane's chunk rows at contiguous ascending positions.  The grid becomes
``(q_block, kv_head, page_block)`` and each KV page is read **once per
q-block instead of once per token** — ~Bq× less KV traffic on prefill
chunks.  Outputs scatter back to stream order through a token→slot map.
Block shapes (``block_q``, ``block_pages``, dequant granularity) are picked
by ``kernels/autotune.py`` against the ``perfmodel`` roofline.

Partial blocks carry dead tail rows (a lane whose chunk is not a multiple
of Bq): they compute finite garbage at positions past the lane's live end
and are never gathered back — same contract as the dead padding rows of the
stream itself.  Dead *stream* rows (bucket padding past ``cu[-1]``) must be
covered by a trailing pseudo-segment so ``cu[-1] == T`` (the scheduler does
this); their blocks also produce unread garbage.

INT8 pools and sliding windows thread straight through: per-row dequant
scales ride the same per-block gather, and a window masks
``q_pos - row < window`` per row.
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.paged_attention.ops import paged_attention
from repro.kernels.paged_attention.ref import (default_block_pages,
                                               paged_attention_reference)


def varlen_positions(cu_seqlens, seq_lens) -> np.ndarray:
    """Per-token absolute positions of a packed stream → (T,) int32.

    ``cu_seqlens`` (S+1,) are lane boundaries in the stream; ``seq_lens``
    (S,) each lane's live KV length *after* this step's rows land.  Lane
    ``i``'s segment holds its final ``cu[i+1] - cu[i]`` positions, i.e.
    ``seq_lens[i] - n_i .. seq_lens[i] - 1`` — the packed restatement of the
    padded step's per-row bound ``kv_len - Lq + i``.
    """
    cu = np.asarray(cu_seqlens, np.int64)
    lens = np.asarray(seq_lens, np.int64)
    t = int(cu[-1])
    pos = np.zeros((t,), np.int32)
    for i in range(len(cu) - 1):
        n = int(cu[i + 1] - cu[i])
        pos[cu[i]:cu[i + 1]] = np.arange(lens[i] - n, lens[i], dtype=np.int32)
    return pos


def validate_cu_seqlens(cu_seqlens, t: int) -> jax.Array:
    """Validate packed-stream lane boundaries against stream width ``t``.

    Shape checks always apply.  Value checks (``cu[0] == 0``, monotone
    non-decreasing, ``cu[-1] == t``) run eagerly on concrete inputs and
    raise ``ValueError`` so packing bugs fail loudly instead of producing
    garbage attention; traced values (inside jit) skip them — the serving
    step validates at pack time on the host copy.

    Dead padding rows (stream bucketed wider than the live tokens) must be
    *covered* by the boundaries — append a trailing pseudo-segment ending at
    ``t`` rather than stopping ``cu`` at the live width.
    """
    cu = jnp.asarray(cu_seqlens, jnp.int32)
    if cu.ndim != 1 or cu.shape[0] < 2:
        raise ValueError(
            f"cu_seqlens must be 1-D with >= 2 entries, got shape {cu.shape}")
    if not isinstance(cu, jax.core.Tracer):
        host = np.asarray(cu)
        if int(host[0]) != 0:
            raise ValueError(f"cu_seqlens must start at 0, got {host[0]}")
        if np.any(np.diff(host) < 0):
            raise ValueError(
                f"cu_seqlens must be non-decreasing, got {host.tolist()}")
        if int(host[-1]) != t:
            raise ValueError(
                f"cu_seqlens[-1] = {int(host[-1])} must equal the packed "
                f"stream width T = {t}; cover dead padding rows with a "
                f"trailing pseudo-segment instead of truncating")
    return cu


def q_block_layout(cu: jax.Array, q_pos: jax.Array, t: int, bq: int
                   ) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Cut the packed stream into q-blocks of ``bq`` same-lane rows.

    All shapes are static (``NB = t // bq + S`` is the worst-case block
    count: ``Σ ceil(n_i/bq) ≤ floor(Σ n_i / bq) + S``); which blocks are
    live is data.  Returns:

    - ``rows``   (NB, bq) stream row gathered into each block slot (dead
      slots clamped into range — they compute unread garbage);
    - ``start``  (NB,)    first stream row of each block (clamped), which
      carries the block's page-table row and base position;
    - ``kv_len`` (NB,)    per-block kernel length ``q_pos[start] + bq`` so
      kernel row ``i`` sits at position ``q_pos[start] + i`` (dead blocks
      pinned to 1 to bound their page walk);
    - ``slot``   (t,)     flattened block-output slot of each stream token
      (the inverse map: ``out[t] = block_out.reshape(-1, ...)[slot[t]]``).
    """
    s = cu.shape[0] - 1
    nb = t // bq + s
    n = cu[1:] - cu[:-1]
    nbi = (n + bq - 1) // bq
    off = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                           jnp.cumsum(nbi).astype(jnp.int32)])
    blk = jnp.arange(nb, dtype=jnp.int32)
    lane = jnp.clip(jnp.searchsorted(off, blk, side="right") - 1, 0, s - 1)
    start = cu[lane] + (blk - off[lane]) * bq
    live = blk < off[-1]
    rows = start[:, None] + jnp.arange(bq, dtype=jnp.int32)[None, :]
    rows = jnp.clip(rows, 0, t - 1)
    start = jnp.clip(start, 0, t - 1)
    kv_len = jnp.where(live, q_pos[start] + bq, 1)
    tok = jnp.arange(t, dtype=jnp.int32)
    lane_t = jnp.clip(jnp.searchsorted(cu, tok, side="right") - 1, 0, s - 1)
    within = tok - cu[lane_t]
    slot = (off[lane_t] + within // bq) * bq + within % bq
    slot = jnp.clip(slot, 0, nb * bq - 1)
    return rows, start, kv_len, slot


def kv_block_counts(cu_seqlens, q_pos, t: int, *, block_q: int,
                    page_size: int, table_width: int,
                    block_pages: Optional[int]) -> Tuple[int, int]:
    """``(walked, live)`` (q_block, page_block) pairs of one kernel call,
    counted on the host in O(blocks).

    ``walked`` is the grid's ``NB × ceil(P / bp)``; ``live`` counts the
    pairs holding a row before the block's ``kv_len`` (cut at the table's
    end) — the pairs the kernel copies and computes.  Mirrors
    :func:`paged_attention_varlen`'s dispatch (``Bq = min(block_q, T)``),
    :func:`q_block_layout`'s ``kv_len`` and the kernel's
    ``bp = min(block_pages, P)``.
    """
    pos = np.asarray(q_pos, np.int64)
    bq = min(block_q, max(t, 1))
    if bq > 1:
        cu = np.asarray(cu_seqlens, np.int64)
        nbi = -(-np.diff(cu) // bq)                  # q-blocks per lane
        first = np.repeat(np.cumsum(nbi) - nbi, nbi)
        starts = np.repeat(cu[:-1], nbi) + bq * (
            np.arange(int(nbi.sum())) - first)
        dead = t // bq + len(nbi) - len(starts)      # pinned to kv_len 1
        kv = np.concatenate([pos[starts] + bq, np.ones(dead, np.int64)])
    else:                                            # one block per token
        kv = pos[:t] + 1
    bp = min(block_pages or default_block_pages(page_size), table_width)
    nblk = -(-table_width // bp)
    kv = np.minimum(kv, table_width * page_size)
    live = np.minimum(-(-kv // (bp * page_size)), nblk)
    return len(kv) * nblk, int(live.sum())


def _as_4d(q: jax.Array) -> jax.Array:
    t, hq, d = q.shape
    return q.reshape(t, hq, 1, d)


def _tiled(q: jax.Array, token_pages: jax.Array, q_pos: jax.Array,
           cu: jax.Array, bq: int, attend) -> jax.Array:
    """Regather (T,)-stream → (NB, Hq, Bq, D) blocks, attend, scatter back."""
    t, hq, d = q.shape
    rows, start, kv_len, slot = q_block_layout(cu, q_pos, t, bq)
    qb = jnp.take(q, rows.reshape(-1), axis=0)       # (NB*bq, Hq, D)
    qb = qb.reshape(rows.shape[0], bq, hq, d)
    qb = jnp.moveaxis(qb, 1, 2)                      # (NB, Hq, bq, D)
    tbl = jnp.take(token_pages, start, axis=0)       # (NB, P)
    out = attend(qb, tbl, kv_len)                    # (NB, Hq, bq, Dv)
    flat = jnp.moveaxis(out, 2, 1).reshape(-1, hq, out.shape[-1])
    return jnp.take(flat, slot, axis=0)              # (T, Hq, Dv)


def paged_attention_varlen(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                           token_pages: jax.Array, q_pos: jax.Array, *,
                           cu_seqlens: Optional[Sequence[int]] = None,
                           scale: Optional[float] = None,
                           cap: Optional[float] = None,
                           window: Optional[int] = None,
                           exp_mode: str = "lut",
                           k_scale: Optional[jax.Array] = None,
                           v_scale: Optional[jax.Array] = None,
                           block_q: Optional[int] = None,
                           block_pages: Optional[int] = None,
                           dequant: str = "block",
                           interpret: Optional[bool] = None) -> jax.Array:
    """Ragged paged attention over a packed (T,)-token stream → (T, Hq, D).

    q: (T, Hq, D); k_pool/v_pool: (N, Hkv, page_size, D) with
    ``Hq % Hkv == 0`` (GQA); token_pages: (T, P) per-token page-table rows;
    q_pos: (T,) per-token absolute position / causal bound.

    ``block_q = Bq > 1`` with ``cu_seqlens`` selects the q-block-tiled
    dataflow (module docstring): grid ``(q_block, kv_head, page_block)``,
    each KV page read once per block instead of once per token.  Tiling
    additionally requires each lane's packed rows to sit at contiguous
    ascending positions (``q_pos[i+1] = q_pos[i] + 1`` within a lane) —
    the serving packing invariant.  ``block_q in (None, 1)`` or a missing
    ``cu_seqlens`` keeps the batch = T dataflow.  ``cu_seqlens``, when
    given, is validated (:func:`validate_cu_seqlens`) either way.

    Dispatch matches :func:`paged_attention`: Pallas kernel on TPU (the
    batch axis is tokens untiled, q-blocks tiled), jnp page-block scan
    elsewhere; ``interpret=True`` forces the kernel in interpret mode.
    ``dequant`` picks the int8 scale-application granularity in the scan
    ("block" | "page" — numerically identical, structurally different).
    """
    t = q.shape[0]
    cu = (validate_cu_seqlens(cu_seqlens, t)
          if cu_seqlens is not None else None)
    q_pos = jnp.asarray(q_pos, jnp.int32)
    kw = dict(scale=scale, cap=cap, window=window, exp_mode=exp_mode,
              k_scale=k_scale, v_scale=v_scale, block_pages=block_pages,
              dequant=dequant, interpret=interpret)
    bq = None if block_q is None else int(min(block_q, max(t, 1)))
    if cu is not None and bq is not None and bq > 1:
        return _tiled(
            q, token_pages, q_pos, cu, bq,
            lambda qb, tbl, kv_len: paged_attention(
                qb, k_pool, v_pool, tbl, kv_len, **kw))
    kv_len = q_pos + 1
    out = paged_attention(_as_4d(q), k_pool, v_pool, token_pages, kv_len,
                          **kw)
    return out[:, :, 0, :]


def paged_attention_varlen_reference(q: jax.Array, k_pool: jax.Array,
                                     v_pool: jax.Array,
                                     token_pages: jax.Array,
                                     q_pos: jax.Array, *,
                                     cu_seqlens: Optional[Sequence[int]] = None,
                                     scale: Optional[float] = None,
                                     cap: Optional[float] = None,
                                     window: Optional[int] = None,
                                     exp_mode: str = "lut",
                                     k_scale: Optional[jax.Array] = None,
                                     v_scale: Optional[jax.Array] = None,
                                     block_q: Optional[int] = None,
                                     block_pages: Optional[int] = None,
                                     dequant: str = "block") -> jax.Array:
    """Pure-jnp varlen reference (the CPU/CI path), pinned explicitly —
    same reduction as :func:`paged_attention_varlen` (batch = T untiled,
    q-block tiled when ``block_q > 1`` and ``cu_seqlens`` is given) but
    always the page-block scan, never the Pallas kernel."""
    t = q.shape[0]
    cu = (validate_cu_seqlens(cu_seqlens, t)
          if cu_seqlens is not None else None)
    q_pos = jnp.asarray(q_pos, jnp.int32)
    kw = dict(scale=scale, cap=cap, window=window, exp_mode=exp_mode,
              k_scale=k_scale, v_scale=v_scale, block_pages=block_pages,
              dequant=dequant)
    bq = None if block_q is None else int(min(block_q, max(t, 1)))
    if cu is not None and bq is not None and bq > 1:
        return _tiled(
            q, token_pages, q_pos, cu, bq,
            lambda qb, tbl, kv_len: paged_attention_reference(
                qb, k_pool, v_pool, tbl, kv_len, **kw))
    kv_len = q_pos + 1
    out = paged_attention_reference(
        _as_4d(q), k_pool, v_pool, token_pages, kv_len, **kw)
    return out[:, :, 0, :]
