"""Pallas TPU kernel: paged attention — KV pages read in place.

The serving pool keeps KV as ``(num_pages, Hkv, page_size, D)``; each lane's
logical sequence is its page table row.  The grid is

    (batch, kv_head, page_slot)           page_slot innermost, sequential

and the *page table is a scalar-prefetch operand*: the k/v BlockSpec index
maps dereference ``tbl_ref[b, j]`` so the DMA engine streams exactly the
physical page each grid step needs — no gathered contiguous copy of the
cache is ever built in HBM (the PR-1 gather this kernel deletes).  Each step
loads one ``(page_size, D)`` page tile, computes the ``(G·Lq, page_size)``
logits tile for the lane's G grouped query heads × Lq query rows, and folds
it into the online-softmax carry ``(m, l, acc)`` in VMEM scratch — the
paper's multicore partial-max/partial-sum gather (§III-B2) across page
blocks.  The last page slot normalises and emits.

One kernel serves both serving phases:

- **decode** (``Lq == 1``): the query row sits at ``kv_len - 1`` and the
  live-length mask is the causal mask;
- **chunked prefill** (``Lq > 1``): query row ``i`` sits at absolute
  position ``kv_len - Lq + i`` (the chunk is the tail of the live rows,
  already written to its pages), so the mask is the per-row causal bound
  ``row ≤ kv_len - Lq + i`` — intra-chunk causal on the diagonal pages,
  plain length gating before them.

Dead pages cost no compute: ``@pl.when(j·page_size < kv_len[b])`` skips
every slot past the lane's live length (their DMAs still land on a valid
page — idle table slots point at the pool's scratch page).

The INT8 variant prefetch-loads the per-row scale page alongside the values
and dequantises inside the step, so quantised serving keeps its 2×-smaller
resident cache *and* the in-place read path.

Like the streaming kernel, the exponential is the paper's LUT decomposition
(``lut_exp_block``).  VMEM per step is one page tile + the (G·Lq,
page_size) logits + the carry — KiBs, far under budget.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.lut_exp import K as LUT_K
from repro.core.lut_softmax import NEG_INF
from repro.kernels.lut_exp.kernel import lut_exp_block

LANES = 128  # m/l carries are broadcast across one lane register
# The kernel's name in compiled programs and profiler traces.  Its device
# ops are found by the substring "paged_attention", which no other op of
# the serving step holds.
KERNEL_NAME = "paged_attention_varlen"


def _exp_fn(mode: str, table):
    if mode == "lut":
        return lambda x: lut_exp_block(x, table, order=1)
    if mode == "lut0":
        return lambda x: lut_exp_block(x, table, order=0)
    return jnp.exp


def paged_attention_kernel(tbl_ref, len_ref, q_ref, k_ref, v_ref,
                           ks_ref, vs_ref, table_ref, o_ref,
                           m_ref, l_ref, acc_ref, *,
                           scale: float, cap: Optional[float],
                           window: Optional[int], exp_mode: str,
                           page_size: int, num_slots: int, q_len: int,
                           quantized: bool):
    b, _, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    exp = _exp_fn(exp_mode, table_ref[...])
    kv_len = len_ref[b]

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Live-page gate: slots at or past the lane's length hold no rows.
    @pl.when(j * page_size < kv_len)
    def _step():
        q = q_ref[...].astype(jnp.float32)                   # (G·Lq, D)
        k = k_ref[...].astype(jnp.float32)                   # (ps, D)
        v = v_ref[...].astype(jnp.float32)                   # (ps, D)
        if quantized:
            k = k * ks_ref[0][:, None]
            v = v * vs_ref[0][:, None]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale      # (G·Lq, ps)
        if cap is not None:
            s = cap * jnp.tanh(s / cap)

        # Structural column index == absolute position (pages are in table
        # order); logits row r covers query index r % Lq, whose position is
        # kv_len - Lq + (r % Lq) — its own causal bound.  Decode (Lq == 1)
        # degenerates to the plain kv_len length mask.
        row = j * page_size + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        qi = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) % q_len
        q_pos = kv_len - q_len + qi
        mask = row <= q_pos
        if window is not None:
            mask &= (q_pos - row) < window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[:, :1]                                # (G·Lq, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.where(mask, exp(s - m_new), 0.0)
        alpha = exp(m_prev - m_new)
        l_new = l_ref[:, :1] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)              # (G·Lq, D)
        acc_ref[...] = acc_ref[...] * alpha + pv
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(j == num_slots - 1)
    def _emit():
        l = jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[...] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("scale", "cap", "window", "exp_mode", "group", "q_len",
                     "interpret"))
def paged_attention_4d(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                       k_scale: Optional[jax.Array],
                       v_scale: Optional[jax.Array],
                       page_table: jax.Array, kv_len: jax.Array,
                       table: jax.Array, *, scale: float,
                       cap: Optional[float], window: Optional[int],
                       exp_mode: str, group: int, q_len: int = 1,
                       interpret: bool = False) -> jax.Array:
    """q: (B, Hkv, G·Lq, D) with row r ↔ (head group r // Lq, query index
    r % Lq); pools: (N, Hkv, ps, D); page_table: (B, P) int32; kv_len: (B,)
    int32.  → (B, Hkv, G·Lq, D) in q's dtype."""
    b, hkv, rows, d = q.shape
    n, _, ps, dv = v_pool.shape
    p = page_table.shape[1]
    assert rows == group * q_len, (rows, group, q_len)
    quantized = k_scale is not None
    if quantized:
        # (N, Hkv, 1, ps): the scale block (1, ps) spans the last two dims
        # in full, which TPU tiling requires; a (1, ps) block of (Hkv, ps)
        # would not be.
        k_scale = k_scale.reshape(n, hkv, 1, ps)
        v_scale = v_scale.reshape(n, hkv, 1, ps)
    else:
        # Uniform kernel arity: dummy 1-page scale pools, never dereferenced
        # (the index map pins them to page 0 and `quantized` elides the load).
        k_scale = jnp.ones((1, hkv, 1, ps), jnp.float32)
        v_scale = jnp.ones((1, hkv, 1, ps), jnp.float32)

    kernel = functools.partial(
        paged_attention_kernel, scale=scale, cap=cap, window=window,
        exp_mode=exp_mode, page_size=ps, num_slots=p, q_len=q_len,
        quantized=quantized)

    def page_map(b_, h, j, tbl, lens):
        del lens
        return (tbl[b_, j], h, 0, 0)

    def scale_map(b_, h, j, tbl, lens):
        del lens
        return ((tbl[b_, j], h, 0, 0) if quantized else (0, h, 0, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                 # page table + per-lane lengths
        grid=(b, hkv, p),
        in_specs=[
            pl.BlockSpec((None, None, rows, d),
                         lambda b_, h, j, tbl, lens: (b_, h, 0, 0)),
            pl.BlockSpec((None, None, ps, d), page_map),
            pl.BlockSpec((None, None, ps, dv), page_map),
            pl.BlockSpec((None, None, 1, ps), scale_map),
            pl.BlockSpec((None, None, 1, ps), scale_map),
            pl.BlockSpec((1, LUT_K),
                         lambda b_, h, j, tbl, lens: (0, 0)),
        ],
        out_specs=pl.BlockSpec((None, None, rows, dv),
                               lambda b_, h, j, tbl, lens: (b_, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((rows, LANES), jnp.float32),  # running max
            pltpu.VMEM((rows, LANES), jnp.float32),  # running denominator
            pltpu.VMEM((rows, dv), jnp.float32),     # weighted accumulator
        ],
    )

    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        name=KERNEL_NAME,
        out_shape=jax.ShapeDtypeStruct((b, hkv, rows, dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(page_table.astype(jnp.int32), kv_len.astype(jnp.int32),
      q, k_pool, v_pool, k_scale, v_scale, table.reshape(1, LUT_K))
